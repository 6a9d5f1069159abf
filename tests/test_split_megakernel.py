"""Fused split-step megakernel (ops/split_step_pallas.py).

Contracts:

* the megakernel path (``LGBM_TPU_FUSED_SPLIT_KERNEL=1`` — on CPU its
  interpret-mode twin) trains BYTE-identical models to the per-phase
  lax foil across bagging, categorical, linear_tree and monotone
  configs, on BOTH the serial and the partitioned learners — the twin
  replicates the foil's exact helpers, so any divergence is a real
  semantic drift;
* the fused grow dispatches no implicit host transfers;
* the committed census budget (``serial_grow_fused`` /
  ``partitioned_grow_fused``: <= 10 dispatches/split) holds at the
  tiny config — the megakernel is ONE dispatch per split;
* the gate is a static rule of config and platform: ineligible
  configs keep the foil, and a Mosaic body the rule selects and the
  compiler refuses raises (tests/test_mosaic_lowering.py).
"""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.io.model_text import save_model_to_string
from lightgbm_tpu.models.variants import create_boosting


# n/f/iters deliberately MATCH tests/test_split_fusion.py's fixtures:
# the foil-side grow programs then hit the in-process jit cache warmed
# by that file (same static config), so this suite only pays for the
# megakernel-side compiles.
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
    monkeypatch.setenv("LGBM_TPU_FUSED_SPLIT_KERNEL",
                       "1" if fused else "0")
    p = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.1,
         "verbosity": -1, "metric": "", **params}
    cfg = Config.from_params(p)
    ds = Dataset.from_numpy(
        x, cfg, label=y,
        categorical_features=[0] if categorical else [])
    b = create_boosting(cfg, ds)
    b.train(iters)
    return save_model_to_string(b)


@pytest.mark.parametrize("learner", ["serial", "partitioned"])
@pytest.mark.parametrize("params,categorical", [
    ({"bagging_freq": 1, "bagging_fraction": 0.7}, False),
    ({}, True),
    ({"linear_tree": True, "linear_lambda": 0.01}, False),
    ({"monotone_constraints": [0, 1, -1, 0, 0, 0]}, False),
], ids=["bagging", "categorical", "linear_tree", "monotone"])
def test_megakernel_vs_foil_models_byte_identical(monkeypatch, params,
                                                  categorical,
                                                  learner):
    x, y = _data(categorical=categorical)
    p = dict(params, tree_learner=learner)
    t_foil = _model_text(monkeypatch, False, p, x, y, categorical)
    t_fused = _model_text(monkeypatch, True, p, x, y, categorical)
    assert t_fused == t_foil


def test_megakernel_partitioned_leaf_id_bit_identical(monkeypatch):
    import jax.numpy as jnp

    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    x, y = _data()
    cfg = Config.from_params({"objective": "binary", "num_leaves": 15,
                              "min_data_in_leaf": 20, "verbosity": -1})
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((len(y),), 0.25, jnp.float32)
    results = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("LGBM_TPU_FUSED_SPLIT_KERNEL", mode)
        ds = Dataset.from_numpy(x, cfg, label=y)
        results[mode] = PartitionedTreeLearner(ds, cfg).train(grad,
                                                              hess)
    for fld in results["0"].tree._fields:
        a = np.asarray(getattr(results["0"].tree, fld))
        b = np.asarray(getattr(results["1"].tree, fld))
        assert a.tobytes() == b.tobytes(), fld
    assert (np.asarray(results["0"].leaf_id).tobytes()
            == np.asarray(results["1"].leaf_id).tobytes())


def test_megakernel_serial_leaf_id_bit_identical(monkeypatch):
    import jax.numpy as jnp

    from lightgbm_tpu.learner.serial import SerialTreeLearner
    x, y = _data()
    cfg = Config.from_params({"objective": "binary", "num_leaves": 15,
                              "min_data_in_leaf": 20, "verbosity": -1})
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((len(y),), 0.25, jnp.float32)
    results = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("LGBM_TPU_FUSED_SPLIT_KERNEL", mode)
        ds = Dataset.from_numpy(x, cfg, label=y)
        results[mode] = SerialTreeLearner(ds, cfg).train(grad, hess)
    for fld in results["0"].tree._fields:
        a = np.asarray(getattr(results["0"].tree, fld))
        b = np.asarray(getattr(results["1"].tree, fld))
        assert a.tobytes() == b.tobytes(), fld
    assert (np.asarray(results["0"].leaf_id).tobytes()
            == np.asarray(results["1"].leaf_id).tobytes())


def test_fused_grow_no_implicit_host_transfers(monkeypatch):
    import jax.numpy as jnp

    from lightgbm_tpu.learner.serial import SerialTreeLearner
    from tools.graftlint.runtime import no_implicit_host_transfers
    monkeypatch.setenv("LGBM_TPU_FUSED_SPLIT_KERNEL", "1")
    x, y = _data(n=800)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "verbosity": -1})
    ds = Dataset.from_numpy(x, cfg, label=y)
    lrn = SerialTreeLearner(ds, cfg)
    assert lrn._fused_kernel_on()
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((len(y),), 0.25, jnp.float32)
    with no_implicit_host_transfers():
        res = lrn.train(grad, hess)
        res.tree.num_leaves.block_until_ready()


def test_fused_census_within_budget():
    """The committed <= 10 dispatches/split megakernel budget holds at
    the tiny config (shape-independent, like the foil census)."""
    from tools import hlo_census
    budget = hlo_census.load_budget()
    current = hlo_census.run_census(
        programs=["serial_grow_fused", "partitioned_grow_fused"],
        rows=512, features=8, leaves=15)
    ok, msgs = hlo_census.check(
        {"programs": {**budget["programs"],
                      **current["programs"]}}, budget)
    assert ok, "\n".join(msgs)
    for name in ("serial_grow_fused", "partitioned_grow_fused"):
        prog = current["programs"][name]
        assert prog["ops_per_split"] <= 10, (name, prog)
        assert prog["collectives"] == 0, name


def test_fused_census_cuts_foil_budget():
    """The acceptance bar: the megakernel path's committed budget is
    <= 10 dispatches/split, and its ``pre_pr`` is the lax foil's
    committed count (73 serial / 110 partitioned on jaxlib 0.9.0)."""
    from tools import hlo_census
    budget = hlo_census.load_budget()["programs"]
    for name in ("serial_grow", "partitioned_grow"):
        b = budget[name + "_fused"]
        assert b["ops_per_split"] + b.get("slack", 0) <= 10, b
        assert b["pre_pr"] == budget[name]["ops_per_split"], name


def test_gate_ineligible_configs_fall_back(monkeypatch):
    """CEGB / extra-trees / by-node sampling keep the per-phase foil
    even with the env forced on (the kernel does not model their
    per-split bookkeeping)."""
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    monkeypatch.setenv("LGBM_TPU_FUSED_SPLIT_KERNEL", "1")
    x, y = _data(n=400)
    for extra in ({"cegb_tradeoff": 1.0, "cegb_penalty_split": 0.1},
                  {"extra_trees": True},
                  {"feature_fraction_bynode": 0.5}):
        cfg = Config.from_params({"objective": "binary",
                                  "num_leaves": 7, "verbosity": -1,
                                  **extra})
        ds = Dataset.from_numpy(x, cfg, label=y)
        lrn = SerialTreeLearner(ds, cfg)
        assert not lrn._fused_kernel_on(), extra


def test_gate_env_and_config_resolution(monkeypatch):
    from lightgbm_tpu.learner.split_step import fused_split_kernel_mode
    monkeypatch.delenv("LGBM_TPU_FUSED_SPLIT_KERNEL", raising=False)
    assert fused_split_kernel_mode("auto") == "auto"
    assert fused_split_kernel_mode("on") == "on"
    assert fused_split_kernel_mode("off") == "off"
    monkeypatch.setenv("LGBM_TPU_FUSED_SPLIT_KERNEL", "0")
    assert fused_split_kernel_mode("on") == "off"
    monkeypatch.setenv("LGBM_TPU_FUSED_SPLIT_KERNEL", "1")
    assert fused_split_kernel_mode("off") == "on"
    monkeypatch.setenv("LGBM_TPU_FUSED_SPLIT_KERNEL", "auto")
    assert fused_split_kernel_mode("on") == "auto"


def test_gate_auto_is_a_static_rule(monkeypatch):
    """auto = on a TPU, at the compiled body's static scope, for the
    layouts that have a Mosaic body. Off on the CPU (the
    per-phase XLA path IS the CPU fast path, so auto never engages the
    twin outside tests); no lowering probe stands behind the rule."""
    import lightgbm_tpu.ops.split_step_pallas as sp
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    monkeypatch.delenv("LGBM_TPU_FUSED_SPLIT_KERNEL", raising=False)
    x, y = _data(n=400)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "verbosity": -1})
    ds = Dataset.from_numpy(x, cfg, label=y)
    serial = SerialTreeLearner(ds, cfg)
    part = PartitionedTreeLearner(ds, cfg)
    assert not serial._fused_kernel_on()
    assert not part._fused_kernel_on()
    monkeypatch.setattr(sp, "on_tpu", lambda: True)
    assert part._fused_kernel_on()
    assert "leaf" not in sp.COMPILED_LAYOUTS
    assert not serial._fused_kernel_on()


def test_forced_splits_keep_foil_for_forced_steps(monkeypatch,
                                                  tmp_path):
    """A forcedsplits plan coexists with the fused while-loop body:
    forced pre-steps run the foil, the remaining splits the kernel —
    byte-identical models either way."""
    import json
    x, y = _data(n=900)
    fn = tmp_path / "forced.json"
    fn.write_text(json.dumps({"feature": 1, "threshold": 0.0}))
    params = {"forcedsplits_filename": str(fn)}
    t_foil = _model_text(monkeypatch, False, params, x, y)
    t_fused = _model_text(monkeypatch, True, params, x, y)
    assert t_fused == t_foil
