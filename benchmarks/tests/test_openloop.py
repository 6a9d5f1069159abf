"""The open-loop generator against a fake server: latency from the due
time, lateness, and the seeded distributions."""

import threading
import time

import numpy as np
import pytest

from benchmarks import openloop

SIZES = [{"share": 0.8, "rows": 1}, {"share": 0.2, "log_uniform": [2, 512]}]


class _Clock:
    """A clock the test moves: sleeping advances it."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


def _sched(due):
    n = len(due)
    return openloop.Schedule(np.asarray(due, float), np.ones(n, int),
                             np.zeros(n, int), np.zeros(n, int))


def test_latency_runs_from_the_due_time_not_from_the_send():
    """A server that stalls the sender for 0.5 s on the first request
    makes the second, due at 0.1 s, late by 0.4 s; its latency counts
    that wait although it is served at once."""
    clock = _Clock()

    def submit(i):
        if i == 0:
            clock.sleep(0.5)        # the stall
        return i

    out = openloop.drive(_sched([0.0, 0.1, 1.0]), submit,
                         wait=lambda h: (True, h), clock=clock,
                         sleep=clock.sleep)
    assert out.ok.all() and not out.errors
    assert out.late_ms == pytest.approx([0.0, 400.0, 0.0])
    # request 0 is stamped done by the collector, whenever it runs
    assert out.latency_ms[1] >= 400.0 - 1e-6
    assert out.sent_s == pytest.approx([0.0, 0.5, 1.0])


def test_a_refused_and_a_failed_request_count_as_failed():
    class Refused(Exception):
        pass

    class Failed(Exception):
        pass

    def submit(i):
        if i == 1:
            raise Refused()
        return i

    def wait(h):
        if h == 2:
            raise Failed()
        return h != 3, None           # 3 comes back, but wrong

    out = openloop.drive(_sched([0.0, 0.0, 0.0, 0.0, 0.0]), submit, wait,
                         sleep=lambda s: None)
    assert out.ok.tolist() == [True, False, False, False, True]
    assert out.errors == {"Refused": 1, "Failed": 1}
    assert len(out.latency_ms) == 2
    assert not np.isnan(out.done_s).any()


def test_completion_is_stamped_by_the_benchmark_when_the_reply_lands():
    """Real clock: a reply that takes 50 ms has a latency of 50 ms and
    more, measured here and not taken from the server."""
    def submit(i):
        ev = threading.Event()
        threading.Timer(0.05, ev.set).start()
        return ev

    def wait(ev):
        assert ev.wait(5.0)
        return True, None

    out = openloop.drive(_sched([0.0, 0.01, 0.02]), submit, wait)
    assert (out.latency_ms >= 49.0).all() and (out.latency_ms < 500).all()
    assert (out.late_ms >= 0).all()


def test_the_same_seed_gives_the_same_schedule():
    a = openloop.make_schedule(7, 10.0, 200.0, {"kind": "poisson"},
                               SIZES, 100_000)
    b = openloop.make_schedule(7, 10.0, 200.0, {"kind": "poisson"},
                               SIZES, 100_000)
    c = openloop.make_schedule(8, 10.0, 200.0, {"kind": "poisson"},
                               SIZES, 100_000)
    for field in ("due_s", "rows", "klass", "offset"):
        assert np.array_equal(getattr(a, field), getattr(b, field))
    assert len(a.due_s) != len(c.due_s) or not np.array_equal(
        a.due_s, c.due_s)
    assert (np.diff(a.due_s) >= 0).all() and a.due_s.max() < 10.0
    assert (a.offset + a.rows <= 100_000).all()


def test_sizes_follow_the_stated_distribution():
    rng = np.random.default_rng(3)
    rows, klass = openloop.request_rows(rng, 200_000, SIZES)
    assert rows.min() == 1 and rows.max() == 512
    assert (rows[klass == 0] == 1).all()
    assert rows[klass == 1].min() == 2
    assert abs((klass == 0).mean() - 0.8) < 0.005
    stated = openloop.mean_rows(SIZES)
    assert 19.0 < stated < 19.6            # "about 19 rows a request"
    assert abs(rows.mean() - stated) < 0.4
    # log-uniform: as many requests in [2, 32) as in [32, 512]
    # (log(32/2) = log(513/32) within 0.1 %)
    big = rows[klass == 1]
    assert abs((big < 32).mean() - 0.5) < 0.01


@pytest.mark.parametrize("arrival", [
    {"kind": "poisson"},
    {"kind": "onoff", "period_s": 2.0, "burst_s": 0.2, "burst_factor": 4.0}])
def test_arrivals_keep_the_mean_rate(arrival):
    rng = np.random.default_rng(11)
    t = openloop.arrival_times(rng, 400.0, 50.0, arrival)
    assert abs(len(t) / 400.0 - 50.0) < 1.5
    assert (np.diff(t) >= 0).all()
    if arrival["kind"] == "onoff":
        in_burst = (t % 2.0) < 0.2
        # a tenth of the time at four times the mean rate
        assert abs(in_burst.mean() - 0.4) < 0.02


def test_real_sleep_lateness_is_reported_not_hidden():
    t0 = time.perf_counter()
    out = openloop.drive(_sched(np.arange(20) * 0.005), lambda i: i,
                         lambda h: (True, None))
    assert time.perf_counter() - t0 >= 0.095
    assert (out.late_ms >= 0).all() and out.late_ms.max() < 100
