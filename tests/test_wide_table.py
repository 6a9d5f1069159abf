"""A dense numeric table wider than the split-step megakernel takes
(ISSUE 31: Epsilon, 2,000 columns) through the normal path at a small
size on the CPU: ``PartitionedTreeLearner`` with the kernels' interpret
twins (the histogram a column slice at a time) against the serial
learner and against the benchmark's plain reference."""

import functools

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability.telemetry import get_telemetry
from lightgbm_tpu.ops.hist_pallas import SLICE_F

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "learning_rate": 0.1, "metric": "", "verbosity": -1}
TREES = 3
# the Epsilon table's width, and the narrowest table the plan calls
# wide (MAX_FUSED_F + 1: two slices, the second nearly empty)
WIDTHS = [pytest.param(2000, 1200, id="epsilon-2000"),
          pytest.param(193, 2000, id="narrowest-wide-193")]


def _table(f, n):
    """Unit-length rows, a noisy dense linear label (the benchmark's
    generator, ``benchmarks/generators/epsilon_like.py``)."""
    from benchmarks.generators import epsilon_like
    return epsilon_like.make(31, n, f)


@functools.lru_cache(maxsize=None)
def _trained(f, n, learner):
    x, y = _table(f, n)
    tel = get_telemetry()
    tel.ensure_ring()
    before = dict(tel.counters)
    bst = lgb.train(dict(PARAMS, tree_learner=learner),
                    lgb.Dataset(x, label=y), num_boost_round=TREES)
    delta = {k: v - before.get(k, 0) for k, v in tel.counters.items()}
    return bst, np.asarray(bst.predict(x, raw_score=True)), delta


@pytest.mark.parametrize("f,n", WIDTHS)
def test_partitioned_learner_matches_the_serial_learner(f, n):
    bst, raw, delta = _trained(f, n, "partitioned")
    ln = bst._gbdt.learner
    assert type(ln).__name__ == "PartitionedTreeLearner"
    assert ln.num_groups == f and ln.interpret
    # every histogram call traced was cut into column slices
    slices = -(-f // SLICE_F)
    assert delta["kernels.hist_feature_slices"] >= 2 * slices
    assert delta["kernels.hist_feature_slices"] % slices == 0
    assert delta.get("learner.megakernel_traces", 0) == 0
    serial, raw_serial, _ = _trained(f, n, "serial")
    assert type(serial._gbdt.learner).__name__ == "SerialTreeLearner"
    assert len(bst._gbdt.models) == len(serial._gbdt.models) == TREES
    assert min(t.num_leaves for t in bst._gbdt.models) == 15
    # the same trees but for float32 sums in another order
    np.testing.assert_allclose(raw, raw_serial, atol=2e-5)
    same = [np.array_equal(a.split_feature[:14], b.split_feature[:14])
            for a, b in zip(bst._gbdt.models, serial._gbdt.models)]
    assert all(same)


@pytest.mark.parametrize("f,n", WIDTHS)
def test_partitioned_learner_matches_the_plain_reference(f, n):
    """``benchmarks/reference/gbdt_numpy.py`` (float64, no kernels) on
    the program's own bins: the comparison the cell's check (a) makes."""
    from benchmarks import stats
    from benchmarks.reference import gbdt_numpy
    bst, raw, _ = _trained(f, n, "partitioned")
    _, y = _table(f, n)
    inner = bst._gbdt.train_data
    want = gbdt_numpy.train(np.asarray(inner.binned),
                            inner.num_bins_array(), y, PARAMS, TREES)
    assert abs(stats.auc(y, raw) - stats.auc(y, want)) <= 1e-6
    assert abs(stats.logloss(y, raw) - stats.logloss(y, want)) <= 1e-6
    np.testing.assert_allclose(raw, want, atol=2e-5)


def test_wide_table_counter_counts_the_plans_refusal(monkeypatch):
    """``learner.wide_table_traces`` is counted where a grow program
    is traced with a plan that refused the megakernel for the width
    alone: a TPU's plan only, so the platform stands in through the
    one module that asks it."""
    import lightgbm_tpu.learner.split_step as split_step
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    x, y = _table(193, 600)
    cfg = Config.from_params(dict(PARAMS, num_leaves=4))
    ds = Dataset.from_numpy(x, cfg, label=y)
    tel = get_telemetry()
    tel.ensure_ring()

    def traces():
        return tel.counters.get("learner.wide_table_traces", 0)
    grad = np.where(y > 0, -0.5, 0.5).astype(np.float32)
    hess = np.full(len(y), 0.25, np.float32)
    before = traces()
    ln = PartitionedTreeLearner(ds, cfg, interpret=True)
    assert not ln.split_plan().wide
    ln.train(grad, hess)
    assert traces() == before               # the CPU's plan: not wide
    monkeypatch.setattr(split_step, "on_tpu", lambda: True)
    # the kernels stay interpret twins, so the scan stays XLA's; the
    # body is the one a TPU's plan picks for this width
    ln = PartitionedTreeLearner(ds, cfg, interpret=True)
    plan = ln.split_plan()
    assert plan.wide and plan.body == "per_phase"
    res = ln.train(grad, hess)
    assert traces() == before + 1
    assert int(res.tree.num_leaves) == 4
