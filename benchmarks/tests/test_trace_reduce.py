"""The reduction from a trace to numbers: the interval arithmetic on
made-up events, and the whole reader on a small trace recorded on a
TPU v5e (``data/``, see ``data/README.md``)."""

import os
import re

import numpy as np
import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ops(events):
    """``DeviceOps`` from ``(name, start, end)`` triples."""
    return tr.DeviceOps([e[0] for e in events], [e[1] for e in events],
                        [e[2] for e in events])


def test_union_merges_overlaps_and_keeps_gaps():
    s, e = np.array([0.0, 1.0, 5.0, 5.5]), np.array([2.0, 3.0, 6.0, 5.8])
    assert tr.union_length(s, e) == pytest.approx(4.0)
    ms, me = tr.merge(s, e)
    assert ms.tolist() == [0.0, 5.0] and me.tolist() == [3.0, 6.0]
    assert tr.union_length(np.zeros(0), np.zeros(0)) == 0.0


def test_overlap_of_two_interval_sets():
    assert tr.overlap_length([0, 10], [4, 12], [2, 11], [6, 20]) \
        == pytest.approx(2.0 + 1.0)
    assert tr.overlap_length([0], [1], [2], [3]) == 0.0


def test_self_time_and_leaves_of_nested_events():
    # a while from 0 to 10 holding two body ops and an idle stretch,
    # one of the body ops holding a nested call
    ops = _ops([("while.1", 0, 10), ("fusion.1", 1, 4), ("call.1", 5, 9),
                ("fusion.2", 6, 8), ("copy.1", 12, 13)])
    assert ops.self_s.tolist() == [3.0, 3.0, 2.0, 2.0, 1.0]
    assert ops.leaf.tolist() == [False, True, False, True, True]


def test_busy_idle_per_name_and_exposed_collectives():
    # chip 0: while [0, 10] with fusion [1, 4], all-reduce [4, 6] and a
    # fusion [5, 7] that hides half of it; chip 1: one op [0, 2]
    trace = tr.Trace(
        {0: _ops([("while.1", 0, 10), ("fusion.1", 1, 4),
                  ("all-reduce.3", 4, 6), ("fusion.2", 5, 7)]),
         1: _ops([("fusion.1", 0, 2)])},
        host=[tr.HostEvent("python", "bench.step", 0.0, 10.0),
              tr.HostEvent("python", "device_get", 7.5, 9.9)],
        window_s=10.0)
    # leaves only: [1, 7] on chip 0, [0, 2] on chip 1
    assert trace.busy_by_device() == {0: pytest.approx(6.0),
                                      1: pytest.approx(2.0)}
    assert trace.busy_s() == pytest.approx(4.0)
    assert trace.idle_share() == pytest.approx(0.8)     # the worst chip
    by_name = trace.self_time_by_name()
    assert by_name["fusion.1"] == pytest.approx((3.0 + 2.0) / 2)
    assert by_name["while.1"] == pytest.approx((10.0 - 3 - 2 - 2) / 2)
    assert trace.time_matching(re.compile(r"^fusion")) \
        == pytest.approx((3.0 + 2.0 + 2.0) / 2)
    total, exposed = trace.collective_times()
    assert total == pytest.approx(2.0 / 2)      # chip 1 has none
    assert exposed == pytest.approx(1.0 / 2)
    gaps = dict(trace.idle_gaps())
    # chip 0's leaves leave no gap between 1 and 7
    assert gaps == {}
    bd = trace.breakdown()
    assert bd["device_ops"][0][0] in ("while.1", "fusion.1")
    assert len(bd["device_ops"]) == 4


def test_idle_gaps_are_named_by_the_host_event_that_covers_them():
    trace = tr.Trace(
        {0: _ops([("fusion.1", 0, 1), ("fusion.2", 3, 4),
                  ("fusion.3", 4.5, 5)])},
        host=[tr.HostEvent("python", "bench.step", 0.0, 5.0),
              tr.HostEvent("python", "np.asarray(jax.Array)", 1.5, 2.5)],
        window_s=5.0)
    assert trace.idle_gaps() == [
        ("python: np.asarray(jax.Array)", pytest.approx(2.0)),
        ("python: bench.step", pytest.approx(0.5))]


def test_collective_names_of_both_backends_match():
    for name in ("all-reduce.12", "all-reduce-start.1", "reduce-scatter.3",
                 "all-gather.7", "reduce_scatter.15", "all_gather.23",
                 "collective-permute.2", "all-to-all.1"):
        assert tr.COLLECTIVES.match(name), name
    for name in ("fusion.3", "while.2", "reduce.4", "gather.1"):
        assert not tr.COLLECTIVES.match(name), name


def test_an_asynchronous_collective_lasts_from_start_to_done():
    trace = tr.Trace(
        {0: _ops([("all-reduce-start.1", 0.0, 0.1), ("fusion.1", 0.1, 0.7),
                  ("all-reduce-done.1", 0.9, 1.0)])},
        host=[], window_s=1.0)
    total, exposed = trace.collective_times()
    assert total == pytest.approx(1.0)
    assert exposed == pytest.approx(0.4)


# -- the whole reader on a trace recorded on a TPU v5e --------------------
@pytest.fixture(scope="module")
def recorded():
    return tr.Trace.from_file(os.path.join(DATA, "tiny-train-v5e.xplane.pb"))


def test_recorded_trace_planes_events_and_names(recorded):
    assert list(recorded.devices) == [0]
    ops = recorded.devices[0]
    assert len(ops) == 531 and int(ops.leaf.sum()) == 526
    # event names are whole HLO instructions; numbers go by short name
    assert all(t.startswith("%") and " = " in t for t in ops.texts)
    kernels = sorted(tr.short_name(t) for t in ops.texts
                     if tr.MOSAIC.search(t))
    assert kernels == ["_histogram_segment_nibble.8", "_scan_call.8",
                       "fused_split_step_segment.7"]
    # 2 trees of 15 leaves: 28 megakernel calls, 2 root histograms,
    # 2 root scans
    assert ops.select(tr.MOSAIC).sum() == 32
    assert ops.select(re.compile(r"^%fused_split_step_segment")).sum() == 28
    assert recorded.host, "host events name the idle gaps"


def test_recorded_trace_busy_idle_and_sums(recorded):
    busy = recorded.busy_s()
    assert busy == pytest.approx(5.210191e-3, rel=1e-6)
    # no window given: the span of the device's events
    assert recorded.window_s == pytest.approx(9.100416e-3, rel=1e-6)
    assert recorded.idle_share() == pytest.approx(1 - busy / 9.100416e-3)
    by_name = recorded.self_time_by_name()
    top = max(by_name, key=by_name.get)
    assert top == "fused_split_step_segment.7"
    assert by_name[top] == pytest.approx(3.196e-3, rel=1e-3)
    assert recorded.time_matching(tr.MOSAIC) \
        == pytest.approx(3.878919e-3, rel=1e-6)
    # self times partition the time under the top-level events, and
    # leaves cannot be busy longer than that
    ops = recorded.devices[0]
    top_level = tr.union_length(ops.start, ops.end)
    assert sum(by_name.values()) == pytest.approx(top_level, rel=1e-9)
    assert busy <= top_level
    assert recorded.collective_times() == (0.0, 0.0)   # one chip


def test_recorded_trace_breakdown_fits_the_result_line(recorded):
    bd = recorded.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) == 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "fused_split_step_segment.7"
    for name, seconds in bd["device_ops"] + bd["idle_gaps"]:
        assert isinstance(name, str) and len(name) < 100
        assert seconds >= 0
    assert [s for _, s in bd["device_ops"]] == sorted(
        (s for _, s in bd["device_ops"]), reverse=True)
    # the longest gap: the host copying the trees back between steps
    assert bd["idle_gaps"][0][0] == "pjrt-tpu-tasks: XlaDelinearize"


def test_layer_readers_on_the_recorded_trace(recorded):
    from benchmarks.layers import (grow_kernels_roofline,
                                   grow_ms_per_split, kernel_share_of_busy)
    tree = {"leaves": 15, "split_rows": [20000.0] + [5000.0] * 13,
            "smaller_child_rows": [8000.0] + [2000.0] * 13}
    facts = {"trace": recorded, "traced_trees": [tree, tree],
             "features": 28, "chips": 1, "device_kind": "TPU v5 lite"}
    assert grow_ms_per_split.read(facts) \
        == pytest.approx(5.210191 / 28, rel=1e-6)
    assert kernel_share_of_busy.read(facts) \
        == pytest.approx(100 * 3.878919 / 5.210191, rel=1e-6)
    # bytes by benchmarks/peaks.py: 85,000 rows partitioned at 88 B and
    # 34,000 + 20,000 rows histogrammed at 40 B, a tree
    need = 2 * (85000 * 88 + 54000 * 40)
    assert grow_kernels_roofline.read(facts) == pytest.approx(
        100 * need / 819e9 / 3.878919e-3, rel=1e-6)
