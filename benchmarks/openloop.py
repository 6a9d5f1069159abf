"""The open-loop load generator: requests are sent on a schedule drawn
from the seed whether or not earlier ones have finished, as independent
users send them.

A corrected copy of the idea in ``lightgbm_tpu/serving/loadgen.py``
``open_loop``: a request's latency runs from the moment it was **due**
on the schedule, not from when it was sent, so a stall counts against
every request it delays; how late the generator itself ran is reported
beside it, so a starved generator is not read as a fast server; request
sizes are drawn from a distribution, not cycled; and the whole
schedule is fixed by the seed before the window opens.

One thread sends and one collects. The collector waits for the
requests in the order they were sent and stamps each when it is done;
the engine answers in that order too.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Tuple

import numpy as np


@dataclass
class Schedule:
    due_s: np.ndarray       # seconds from the window's start
    rows: np.ndarray        # rows in each request
    klass: np.ndarray       # index of each request's size class
    offset: np.ndarray      # first pool row of each request


def arrival_times(rng: np.random.Generator, seconds: float,
                  rate_rps: float, arrival: Dict[str, Any]) -> np.ndarray:
    """Arrival times in ``[0, seconds)``. ``arrival["kind"]``:
    ``poisson`` at ``rate_rps``; ``onoff``, a Poisson process whose
    rate is ``burst_factor`` times the mean during the first
    ``burst_s`` of every ``period_s`` and lower outside them so that
    the mean stays ``rate_rps``."""
    # unit-rate arrivals in operational time, then mapped to the clock
    # through the inverse of the cumulative rate
    n_max = int(rate_rps * seconds * 1.5 + 10 * (rate_rps * seconds) ** 0.5
                + 50)
    unit = np.cumsum(rng.exponential(1.0, n_max))
    kind = arrival["kind"]
    if kind == "poisson":
        t = unit / rate_rps
    elif kind == "onoff":
        period, burst = float(arrival["period_s"]), float(arrival["burst_s"])
        hi = rate_rps * float(arrival["burst_factor"])
        lo = (rate_rps * period - hi * burst) / (period - burst)
        if lo < 0:
            raise ValueError("bursts alone exceed the mean rate")
        per_period = hi * burst + lo * (period - burst)
        k, rem = np.divmod(unit, per_period)
        in_burst = rem < hi * burst
        t = k * period + np.where(
            in_burst, rem / hi,
            burst + (rem - hi * burst) / max(lo, 1e-300))
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    return t[t < seconds]


def request_rows(rng: np.random.Generator, n: int,
                 sizes: List[Dict[str, Any]]
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """``(rows, class)`` per request. ``sizes`` is a list of classes,
    each with a ``share`` and either ``rows`` (a fixed count) or
    ``log_uniform: [lo, hi]`` (whole numbers, log-uniform, both ends
    included)."""
    shares = np.asarray([c["share"] for c in sizes], np.float64)
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError("size shares must sum to 1")
    which = rng.choice(len(sizes), size=n, p=shares)
    u = rng.random(n)
    out = np.ones(n, np.int64)
    for i, c in enumerate(sizes):
        pick = which == i
        if "rows" in c:
            out[pick] = int(c["rows"])
        else:
            lo, hi = c["log_uniform"]
            out[pick] = np.floor(
                lo * ((hi + 1.0) / lo) ** u[pick]).astype(np.int64)
    return out, which


def mean_rows(sizes: List[Dict[str, Any]]) -> float:
    """The distribution's mean rows per request."""
    total = 0.0
    for c in sizes:
        if "rows" in c:
            m = float(c["rows"])
        else:
            lo, hi = c["log_uniform"]
            ks = np.arange(lo, hi + 1, dtype=np.float64)
            # P(k) = log((k+1)/k) / log((hi+1)/lo)
            m = float((ks * np.log((ks + 1) / ks)).sum()
                      / np.log((hi + 1.0) / lo))
        total += c["share"] * m
    return total


def make_schedule(seed: int, seconds: float, rate_rps: float,
                  arrival: Dict[str, Any], sizes: List[Dict[str, Any]],
                  pool_rows: int) -> Schedule:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x10ad]))
    due = arrival_times(rng, seconds, rate_rps, arrival)
    rows, klass = request_rows(rng, len(due), sizes)
    offset = rng.integers(0, pool_rows - rows.max(initial=1) + 1, len(due))
    return Schedule(due, rows, klass, offset)


@dataclass
class Outcome:
    """What happened to each request, times on the benchmark's clock
    in seconds from the window's start."""
    due_s: np.ndarray
    sent_s: np.ndarray
    done_s: np.ndarray
    ok: np.ndarray
    errors: Dict[str, int]      # error class name -> count
    replies: List[Any]          # what wait() returned, per request

    @property
    def latency_ms(self) -> np.ndarray:
        """Completion minus due time, completed requests only."""
        return (self.done_s[self.ok] - self.due_s[self.ok]) * 1e3

    @property
    def late_ms(self) -> np.ndarray:
        """How late each request was sent."""
        return (self.sent_s - self.due_s) * 1e3


def drive(sched: Schedule, submit: Callable[[int], Any],
          wait: Callable[[Any], Tuple[bool, Any]],
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> Outcome:
    """Send every request of ``sched`` when it is due. ``submit(i)``
    sends request ``i`` and returns a handle, or raises when the
    server refuses it; ``wait(handle)`` blocks until it is done and
    returns ``(ok, reply)``. A refused or failed request is not ok and
    its error's class name is counted."""
    n = len(sched.due_s)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    replies: List[Any] = [None] * n
    errors: Dict[str, int] = {}
    errors_lock = threading.Lock()
    handles: "queue.Queue" = queue.Queue()
    t0 = clock()

    def count_error(e: Exception) -> None:
        with errors_lock:
            errors[type(e).__name__] = errors.get(type(e).__name__, 0) + 1

    def collect() -> None:
        while True:
            item = handles.get()
            if item is None:
                return
            i, handle = item
            try:
                ok[i], replies[i] = wait(handle)
            except Exception as e:       # the server failed the request
                count_error(e)
            done[i] = clock() - t0

    collector = threading.Thread(target=collect, name="bench-collector")
    collector.start()
    try:
        for i in range(n):
            delay = t0 + sched.due_s[i] - clock()
            if delay > 0:
                sleep(delay)
            sent[i] = clock() - t0
            try:
                handles.put((i, submit(i)))
            except Exception as e:       # refused at the door
                count_error(e)
                done[i] = clock() - t0
    finally:
        handles.put(None)
        collector.join()
    return Outcome(np.asarray(sched.due_s), sent, done, ok, errors,
                   replies)
