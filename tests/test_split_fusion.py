"""Fused split-step (learner/split_step.py) + HLO dispatch census.

Two contracts from the round-6 perf directive:

* the fused packing (merged single-scatter state, slim carry —
  ``LGBM_TPU_SPLIT_FUSION=1``, the default) trains BYTE-identical
  models to the legacy r05 layout (``=0``) across bagging,
  categorical and linear_tree configs, on both the serial and the
  partitioned learners;

* the compiled grow programs stay within the committed per-split
  dispatch budget (``tools/hlo_census_budget.json``) — the census is
  shape-independent, so a tiny config compiles fast and must report
  EXACTLY the same while-body op census as the bench fixed config.
"""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.io.model_text import save_model_to_string
from lightgbm_tpu.models.variants import create_boosting


def _data(n=1200, f=6, seed=3, categorical=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f)
    if categorical:
        x[:, 0] = rng.randint(0, 12, n)
    y = (x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
         + (np.isin(x[:, 0], [2, 5, 7]) if categorical else 0)
         + 0.1 * rng.randn(n) > 0.3).astype(np.float32)
    return x.astype(np.float32), y


def _model_text(monkeypatch, fused, params, x, y, categorical=False,
                iters=6):
    monkeypatch.setenv("LGBM_TPU_SPLIT_FUSION", "1" if fused else "0")
    p = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.1,
         "verbosity": -1, "metric": "", **params}
    cfg = Config.from_params(p)
    ds = Dataset.from_numpy(
        x, cfg, label=y,
        categorical_features=[0] if categorical else [])
    b = create_boosting(cfg, ds)
    b.train(iters)
    return save_model_to_string(b)


@pytest.mark.parametrize("params,categorical", [
    ({"bagging_freq": 1, "bagging_fraction": 0.7}, False),
    ({}, True),
    ({"linear_tree": True, "linear_lambda": 0.01}, False),
    ({"monotone_constraints": [0, 1, -1, 0, 0, 0]}, False),
], ids=["bagging", "categorical", "linear_tree", "monotone"])
def test_fused_vs_legacy_models_byte_identical(monkeypatch, params,
                                               categorical):
    x, y = _data(categorical=categorical)
    t_legacy = _model_text(monkeypatch, False, params, x, y,
                           categorical)
    t_fused = _model_text(monkeypatch, True, params, x, y, categorical)
    assert t_fused == t_legacy


def test_fused_vs_legacy_partitioned_bit_identical(monkeypatch):
    import jax.numpy as jnp

    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    x, y = _data()
    cfg = Config.from_params({"objective": "binary", "num_leaves": 15,
                              "min_data_in_leaf": 20, "verbosity": -1})
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((len(y),), 0.25, jnp.float32)
    results = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("LGBM_TPU_SPLIT_FUSION", mode)
        ds = Dataset.from_numpy(x, cfg, label=y)
        res = PartitionedTreeLearner(ds, cfg).train(grad, hess)
        results[mode] = res
    for fld in results["0"].tree._fields:
        a = np.asarray(getattr(results["0"].tree, fld))
        b = np.asarray(getattr(results["1"].tree, fld))
        assert a.tobytes() == b.tobytes(), fld
    assert (np.asarray(results["0"].leaf_id).tobytes()
            == np.asarray(results["1"].leaf_id).tobytes())


def test_fused_grow_no_implicit_host_transfers():
    import jax.numpy as jnp

    from lightgbm_tpu.learner.serial import SerialTreeLearner
    from tools.graftlint.runtime import no_implicit_host_transfers
    x, y = _data(n=800)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "verbosity": -1})
    ds = Dataset.from_numpy(x, cfg, label=y)
    lrn = SerialTreeLearner(ds, cfg)
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((len(y),), 0.25, jnp.float32)
    with no_implicit_host_transfers():
        res = lrn.train(grad, hess)
        res.tree.num_leaves.block_until_ready()


def test_slim_carry_drops_derivable_rows():
    from lightgbm_tpu.learner.split_step import (StatePack,
                                                 make_grow_pack)
    fused = make_grow_pack(merged=True, has_cat=False,
                           has_monotone=False, big_l=15)
    legacy = make_grow_pack(merged=False, has_cat=True,
                            has_monotone=True, big_l=15)
    for name in ("leaf_weight", "leaf_count", "leaf_cmin", "leaf_cmax"):
        assert name not in fused.sf_fields
        assert name in legacy.sf_fields
    assert "leaf_parent" not in fused.si_fields
    for name in ("leaf_weight", "leaf_count", "leaf_parent",
                 "leaf_cmin", "leaf_cmax", "bs_bitset", "cat_bitsets"):
        assert name in fused.derived
    # left_child/right_child must stay adjacent for the fused 2-row
    # pointer fixup
    ti = StatePack.GROW_TI
    assert ti.index("right_child") == ti.index("left_child") + 1


_FOIL_PROGRAMS = ["serial_grow", "partitioned_grow"]


def test_census_within_budget_and_below_unpacked_carry():
    """The committed dispatch budget holds at the tiny config (the
    slow test_census_shape_independence_exact compares tiny against
    the canonical shape; here the fast path checks budget + slack).
    Foil programs only — the megakernel programs compile once in
    tests/test_split_megakernel.py instead of twice per run.

    The packed carry also still cuts the per-split op count against
    the legacy unpacked carry (``pre_pr``: LGBM_TPU_SPLIT_FUSION=0 on
    the same jaxlib) by a quarter or more. The 2x the cut was first
    recorded at belonged to an XLA:CPU that merged the scalar unpack
    ops into their consumers; jaxlib 0.9.0 leaves them as separate
    small fusions (packed serial 44 -> 73) while the unpacked carry's
    own count fell (113 -> 103), so the cut is about 1.4x now
    (tools/hlo_census_budget.json note; ROADMAP S5 re-measures it on
    the chip)."""
    from tools import hlo_census
    budget = hlo_census.load_budget()
    current = hlo_census.run_census(programs=_FOIL_PROGRAMS,
                                    rows=512, features=8, leaves=15)
    foil_budget = {"programs": {
        k: v for k, v in budget["programs"].items()
        if k in _FOIL_PROGRAMS}}
    ok, msgs = hlo_census.check(current, foil_budget)
    assert ok, "\n".join(msgs)
    for name, prog in current["programs"].items():
        assert prog["collectives"] == 0, name
        assert prog["ops_per_split"] \
            <= 0.75 * budget["programs"][name]["pre_pr"], name


@pytest.mark.slow
def test_census_shape_independence_exact():
    """The claim the fast tests and the bench lean on: the while-body
    op census is EXACTLY shape-independent — the tiny config must
    report the same ops_per_split as the canonical budget shape
    (compiled here in the same process/jax, so the comparison cannot
    drift with toolchain versions the way the committed numbers
    could)."""
    from tools import hlo_census
    tiny = hlo_census.run_census(rows=512, features=8, leaves=15)
    full = hlo_census.run_census(rows=hlo_census.CENSUS_ROWS,
                                 features=hlo_census.CENSUS_FEATURES,
                                 leaves=hlo_census.CENSUS_LEAVES)
    for name in hlo_census.PROGRAMS:
        assert (tiny["programs"][name]["ops_per_split"]
                == full["programs"][name]["ops_per_split"]), name


def test_census_carry_slimmer_than_pre_pr():
    from tools import hlo_census
    current = hlo_census.run_census(programs=["serial_grow"],
                                    rows=512, features=8, leaves=15)
    budget = hlo_census.load_budget()["programs"]["serial_grow"]
    assert (current["programs"]["serial_grow"]["carry_arrays"]
            < budget["pre_pr_carry_arrays"])
