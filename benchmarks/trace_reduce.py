"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU
trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds every operation the chip ran, containers (``while``,
``conditional``) with their bodies nested inside them, and one host
plane (``/host:CPU``) with a line per thread. On a TPU an operation's
event is named by its whole HLO instruction,
``%fused_split_step_segment.7 = (s32[19,255]{...}, ...)
custom-call(...), custom_call_target="tpu_custom_call", ...``: patterns
are matched against that text, and numbers are reported under the
short name before the ``=`` (looked at by hand on a v5e, PR 22).

Definitions, the same for every PR:

* an operation's **self time** is its duration less the durations of
  the operations nested directly inside it;
* a chip is **busy** while a *leaf* operation (one with nothing nested
  inside it) runs, so a ``while`` that waits between two of its body's
  operations does not count as busy there;
* **idle share** is 1 - busy / window on the chip that is busy least;
* a collective's **exposed** time is the part of it during which no
  other leaf operation runs on that chip.

Times are seconds; per-chip values are averaged over the chips unless
said otherwise.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVES = re.compile(
    r"^%?(all[-_]reduce|reduce[-_]scatter|all[-_]gather|all[-_]to[-_]all|"
    r"collective[-_]permute|collective[-_]broadcast)")
# the program's Pallas kernels run as Mosaic custom calls
MOSAIC = re.compile(r'custom_call_target="tpu_custom_call"')
# host events that say nothing about what the host was doing
_HOST_NOISE = re.compile(r"^(ThreadpoolListener|\$)")


# -- interval arithmetic (pure; tested on their own) -------------------
def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length covered by the intervals ``[starts, ends)``."""
    if len(starts) == 0:
        return 0.0
    merged_s, merged_e = merge(starts, ends)
    return float((merged_e - merged_s).sum())


def merge(starts: np.ndarray, ends: np.ndarray
          ) -> Tuple[np.ndarray, np.ndarray]:
    """The intervals merged into disjoint, sorted ones."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="mergesort")
    s = np.asarray(starts, np.float64)[order]
    e = np.maximum.accumulate(np.asarray(ends, np.float64)[order])
    # a new run starts where an interval begins after everything before
    # it has ended
    new_run = np.r_[True, s[1:] > e[:-1]]
    run_starts = s[new_run]
    run_ends = e[np.r_[new_run[1:], True]]
    return run_starts, run_ends


def overlap_length(a_s, a_e, b_s, b_e) -> float:
    """Length of the intersection of two sets of intervals."""
    a_s, a_e = merge(a_s, a_e)
    b_s, b_e = merge(b_s, b_e)
    total, j = 0.0, 0
    for s, e in zip(a_s, a_e):
        while j < len(b_s) and b_e[j] <= s:
            j += 1
        k = j
        while k < len(b_s) and b_s[k] < e:
            total += min(e, b_e[k]) - max(s, b_s[k])
            k += 1
    return float(total)


def nest(starts: np.ndarray, ends: np.ndarray
         ) -> Tuple[np.ndarray, np.ndarray]:
    """For events of one line, ``(self_time, is_leaf)``: an event that
    begins and ends inside another is nested in it; one that only
    overlaps another is its sibling."""
    n = len(starts)
    order = np.lexsort((-np.asarray(ends), np.asarray(starts)))
    self_t = (np.asarray(ends, np.float64)
              - np.asarray(starts, np.float64))
    leaf = np.ones(n, bool)
    stack: List[int] = []
    for i in order:
        while stack and (ends[stack[-1]] <= starts[i]
                         or ends[i] > ends[stack[-1]]):
            stack.pop()
        if stack:
            parent = stack[-1]
            leaf[parent] = False
            self_t[parent] -= ends[i] - starts[i]
        stack.append(i)
    return np.maximum(self_t, 0.0), leaf


# -- the trace ---------------------------------------------------------
def short_name(text: str) -> str:
    """``%fusion.92 = s32[...] fusion(...)`` -> ``fusion.92``; a name
    that is not an HLO instruction stays as it is."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


class DeviceOps:
    """One chip's operations, times in seconds from the trace's
    origin. ``texts`` holds each distinct event name once and ``which``
    says which of them each event has."""

    def __init__(self, names: List[str], start: np.ndarray,
                 end: np.ndarray):
        index: Dict[str, int] = {}
        self.which = np.fromiter(
            (index.setdefault(n, len(index)) for n in names), np.int64,
            len(names))
        self.texts = list(index)
        self.start = np.asarray(start, np.float64)
        self.end = np.asarray(end, np.float64)
        self.self_s, self.leaf = nest(self.start, self.end)

    def __len__(self) -> int:
        return len(self.which)

    def select(self, pattern: "re.Pattern[str]") -> np.ndarray:
        """Which events' names match ``pattern``."""
        hit = np.fromiter((bool(pattern.search(t)) for t in self.texts),
                          bool, len(self.texts))
        return hit[self.which]


@dataclass
class HostEvent:
    line: str
    name: str
    start: float
    end: float


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    found = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


class Trace:
    """A reduced trace: per-chip operations and host events."""

    def __init__(self, devices: Dict[int, DeviceOps],
                 host: List[HostEvent], window_s: float):
        self.devices = devices
        self.host = host
        self.window_s = float(window_s)
        self._busy: Optional[Dict[int, float]] = None

    @classmethod
    def from_file(cls, path: str, window_s: Optional[float] = None,
                  cpu_stand_in: bool = False) -> "Trace":
        """Read ``path``. ``window_s`` is the traced window's length on
        the host's clock; left out, it is the span of the device
        events. ``cpu_stand_in`` (tests only) takes XLA:CPU's op events
        in place of a chip's, so that the CPU rehearsal exercises the
        same code."""
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        raw: Dict[int, List[Tuple[str, float, float]]] = {}
        host: List[HostEvent] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m and not cpu_stand_in:
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    raw.setdefault(int(m.group(1)), []).extend(
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.duration_ns <= 0:
                            continue
                        lo = ev.start_ns * 1e-9
                        hi = lo + ev.duration_ns * 1e-9
                        if cpu_stand_in and any(
                                k == "hlo_op" for k, _ in ev.stats):
                            raw.setdefault(0, []).append(
                                (ev.name, lo, hi))
                        elif not _HOST_NOISE.match(ev.name):
                            host.append(HostEvent(line.name, ev.name,
                                                  lo, hi))
        devices = {}
        for dev, events in sorted(raw.items()):
            if not events:
                continue
            devices[dev] = DeviceOps([e[0] for e in events],
                                     [e[1] for e in events],
                                     [e[2] for e in events])
        if window_s is None:
            lo = min((d.start.min() for d in devices.values()),
                     default=0.0)
            hi = max((d.end.max() for d in devices.values()),
                     default=0.0)
            window_s = hi - lo
        return cls(devices, host, window_s)

    # -- busy and idle -------------------------------------------------
    def busy_by_device(self) -> Dict[int, float]:
        if self._busy is None:      # several readers ask; merge once
            self._busy = {
                dev: union_length(ops.start[ops.leaf], ops.end[ops.leaf])
                for dev, ops in self.devices.items()}
        return self._busy

    def busy_s(self) -> float:
        """Seconds a leaf operation ran, averaged over the chips."""
        per = list(self.busy_by_device().values())
        return float(np.mean(per)) if per else 0.0

    def idle_share(self) -> Optional[float]:
        """1 - busy / window on the chip that is busy least."""
        per = list(self.busy_by_device().values())
        if not per or self.window_s <= 0:
            return None
        return max(0.0, 1.0 - min(per) / self.window_s)

    # -- per-name and per-pattern times ----------------------------------
    def self_time_by_name(self) -> Dict[str, float]:
        """Self time summed by short operation name, averaged over
        chips."""
        out: Dict[str, float] = {}
        for ops in self.devices.values():
            sums = np.bincount(ops.which, ops.self_s, len(ops.texts))
            for text, t in zip(ops.texts, sums):
                name = short_name(text)
                out[name] = out.get(name, 0.0) + float(t)
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def time_matching(self, pattern: "re.Pattern[str]") -> float:
        """Self time of the operations whose name matches, averaged
        over chips."""
        per = [float(ops.self_s[ops.select(pattern)].sum())
               for ops in self.devices.values()]
        return float(np.mean(per)) if per else 0.0

    def collective_times(self) -> Tuple[float, float]:
        """``(total, exposed)`` seconds of the collectives, averaged
        over chips; exposed is the part during which no other leaf
        operation runs on that chip. An asynchronous collective lasts
        from its ``-start`` operation to the matching ``-done``."""
        totals, exposed = [], []
        for ops in self.devices.values():
            c_s, c_e = _collective_intervals(ops)
            rest = ~ops.select(COLLECTIVES) & ops.leaf
            total = union_length(c_s, c_e)
            hidden = overlap_length(c_s, c_e, ops.start[rest],
                                    ops.end[rest])
            totals.append(total)
            exposed.append(total - hidden)
        if not totals:
            return 0.0, 0.0
        return float(np.mean(totals)), float(np.mean(exposed))

    # -- the breakdown the ledger keeps -------------------------------------
    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest gaps between leaf operations on the first chip,
        summed by what the host was doing at the gap's middle: the
        shortest host event that covers it, as ``line: event``."""
        if not self.devices:
            return []
        ops = self.devices[min(self.devices)]
        s, e = merge(ops.start[ops.leaf], ops.end[ops.leaf])
        gap_s, gap_e = e[:-1], s[1:]
        keep = np.argsort(gap_e - gap_s)[::-1][:2000]
        host = sorted(self.host, key=lambda h: h.start)
        h_start = np.asarray([h.start for h in host])
        sums: Dict[str, float] = {}
        for i in keep:
            mid = (gap_s[i] + gap_e[i]) / 2.0
            label = "no host event"
            best = None
            # events that began before the middle; scan the nearest few
            j = int(np.searchsorted(h_start, mid, side="right"))
            for h in host[max(0, j - 64):j]:
                if h.end >= mid and (best is None
                                     or h.end - h.start
                                     < best.end - best.start):
                    best = h
            if best is not None:
                thread = best.line.split("/")[0] or best.line
                label = f"{thread}: {best.name}"
            sums[label] = sums.get(label, 0.0) \
                + float(gap_e[i] - gap_s[i])
        ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
        return [(k, v) for k, v in ranked]

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        by_name = sorted(self.self_time_by_name().items(),
                         key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in by_name],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(top)]}


def _collective_intervals(ops: DeviceOps
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """One chip's collectives as intervals: a synchronous one is its
    own event; ``<kind>-start`` is paired with the next ``<kind>-done``
    in time order."""
    starts: List[float] = []
    ends: List[float] = []
    pending: Dict[str, List[float]] = {}
    picked = np.flatnonzero(ops.select(COLLECTIVES))
    for i in picked[np.argsort(ops.start[picked], kind="mergesort")]:
        base = short_name(ops.texts[ops.which[i]]).split(".")[0]
        if base.endswith("-start"):
            pending.setdefault(base[:-6], []).append(ops.start[i])
        elif base.endswith("-done"):
            opened = pending.get(base[:-5])
            starts.append(opened.pop(0) if opened else ops.start[i])
            ends.append(ops.end[i])
        else:
            starts.append(ops.start[i])
            ends.append(ops.end[i])
    return np.asarray(starts, np.float64), np.asarray(ends, np.float64)


def describe(path: str, top: int = 40) -> Dict[str, object]:
    """A structural summary of a trace file for a human to read before
    writing patterns: planes, lines, event counts, the longest
    operation names and one event's stats for each."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            total: Dict[str, float] = {}
            count: Dict[str, int] = {}
            example: Dict[str, Dict[str, str]] = {}
            n = 0
            for ev in line.events:
                n += 1
                total[ev.name] = total.get(ev.name, 0.0) \
                    + ev.duration_ns * 1e-9
                count[ev.name] = count.get(ev.name, 0) + 1
                if ev.name not in example and len(example) < 400:
                    example[ev.name] = {
                        str(k): str(v)[:200] for k, v in ev.stats}
            ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
            lines.append({
                "line": line.name, "events": n,
                "top": [{"name": k, "seconds": v, "count": count[k],
                         "stats": example.get(k, {})}
                        for k, v in ranked]})
        planes.append({"plane": plane.name, "lines": lines})
    return {"file": path, "planes": planes}
