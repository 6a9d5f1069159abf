"""Fused split-step megakernel (ops/split_step_pallas.py).

Contracts:

* the megakernel path (``fused_split_kernel=on`` — on CPU its
  interpret-mode twin) trains BYTE-identical models to the per-phase
  lax foil across bagging, categorical, linear_tree and monotone
  configs on the partitioned learner — the twin replicates the foil's
  exact helpers, so any divergence is a real semantic drift;
* the fused grow dispatches no implicit host transfers;
* the committed census budget (``partitioned_grow_fused``: <= 10
  dispatches/split) holds at the tiny config — the megakernel is ONE
  dispatch per split;
* ONE function decides which split step runs
  (``learner/split_step.py`` ``plan_split_step``), from what it is
  given: the decision is tested as a table, the platform passed in;
  ineligible configs keep the foil, and a Mosaic body the rule selects
  and the compiler refuses raises (tests/test_mosaic_lowering.py).
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


def _model_text(fused, params, x, y, categorical=False, iters=6):
    p = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.1,
         "verbosity": -1, "metric": "", "tree_learner": "partitioned",
         "fused_split_kernel": "on" if fused else "off", **params}
    cfg = Config.from_params(p)
    ds = Dataset.from_numpy(
        x, cfg, label=y,
        categorical_features=[0] if categorical else [])
    b = create_boosting(cfg, ds)
    b.train(iters)
    # the parameter dump names the mode itself; everything else (the
    # trees above it included) must be byte-identical
    return "\n".join(ln for ln in save_model_to_string(b).split("\n")
                     if not ln.startswith("[fused_split_kernel:"))


@pytest.mark.parametrize("learner", ["partitioned"])
@pytest.mark.parametrize("params,categorical", [
    ({"bagging_freq": 1, "bagging_fraction": 0.7}, False),
    ({}, True),
    ({"linear_tree": True, "linear_lambda": 0.01}, False),
    ({"monotone_constraints": [0, 1, -1, 0, 0, 0]}, False),
], ids=["bagging", "categorical", "linear_tree", "monotone"])
def test_megakernel_vs_foil_models_byte_identical(params, categorical,
                                                  learner):
    x, y = _data(categorical=categorical)
    p = dict(params, tree_learner=learner)
    t_foil = _model_text(False, p, x, y, categorical)
    t_fused = _model_text(True, p, x, y, categorical)
    assert t_fused == t_foil


def test_megakernel_partitioned_leaf_id_bit_identical():
    import jax.numpy as jnp

    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    x, y = _data()
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((len(y),), 0.25, jnp.float32)
    results = {}
    for mode in ("off", "on"):
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": 15,
            "min_data_in_leaf": 20, "fused_split_kernel": mode,
            "verbosity": -1})
        ds = Dataset.from_numpy(x, cfg, label=y)
        lrn = PartitionedTreeLearner(ds, cfg)
        assert (lrn.split_plan().body == "megakernel") == (mode == "on")
        results[mode] = lrn.train(grad, hess)
    for fld in results["off"].tree._fields:
        a = np.asarray(getattr(results["off"].tree, fld))
        b = np.asarray(getattr(results["on"].tree, fld))
        assert a.tobytes() == b.tobytes(), fld
    assert (np.asarray(results["off"].leaf_id).tobytes()
            == np.asarray(results["on"].leaf_id).tobytes())


@pytest.mark.parametrize("features,leaves", [(6, 8), (28, 15)])
def test_mosaic_body_under_the_interpreter_vs_foil(monkeypatch, features,
                                                   leaves):
    """The WHOLE Mosaic body (not the twin) grows a tree under the
    Pallas interpreter, every ``pallas_call`` of the process forced to
    interpret: phase 0's partition stream, the histogram stream over
    the smaller child's compact segment (left and right children both
    come up as the smaller one), phase 1's scans and writes. Gate as on
    the chip (``tools/check_kernels_on_chip.py fused_split``): equal
    leaf counts, outputs within 1e-3 of the per-phase foil, and the
    stream handed at most half of the partitioned rows."""
    import jax
    from jax.experimental import pallas as pl

    from lightgbm_tpu.observability.telemetry import get_telemetry
    from tools.check_kernels_on_chip import stage_fused_split
    real_call = pl.pallas_call

    def interpreted(*args, **kw):
        kw["interpret"] = True
        return real_call(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", interpreted)
    tel = get_telemetry()
    was_on = tel.enabled
    tel.ensure_ring()
    name = "kernels.hist_child_stream"
    before = tel.counters.get(name, 0)
    try:
        # interpret=False: the learner asks for the compiled body
        failures = stage_fused_split(interpret=False, rows=3000,
                                     features=features, leaves=leaves)
        traced = tel.counters.get(name, 0) - before
    finally:
        # programs traced here hold interpreted kernels under the
        # compiled path's cache keys
        jax.clear_caches()
        if not was_on:
            tel.reset()
    assert failures == 0
    assert traced >= 1


def test_fused_grow_no_implicit_host_transfers():
    import jax.numpy as jnp

    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from tools.graftlint.runtime import no_implicit_host_transfers
    x, y = _data(n=800)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "fused_split_kernel": "on",
                              "verbosity": -1})
    ds = Dataset.from_numpy(x, cfg, label=y)
    lrn = PartitionedTreeLearner(ds, cfg)
    assert lrn.split_plan().body == "megakernel"
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
        programs=["partitioned_grow_fused"],
        rows=512, features=8, leaves=15)
    ok, msgs = hlo_census.check(
        {"programs": {**budget["programs"],
                      **current["programs"]}}, budget)
    assert ok, "\n".join(msgs)
    assert "serial_grow_fused" not in budget["programs"]
    prog = current["programs"]["partitioned_grow_fused"]
    assert prog["ops_per_split"] <= 10, prog
    assert prog["collectives"] == 0


def test_fused_census_cuts_foil_budget():
    """The acceptance bar: the megakernel path's committed budget is
    <= 10 dispatches/split, and its ``pre_pr`` is the lax foil's
    committed count (129 on jaxlib 0.9.0)."""
    from tools import hlo_census
    budget = hlo_census.load_budget()["programs"]
    b = budget["partitioned_grow_fused"]
    assert b["ops_per_split"] + b.get("slack", 0) <= 10, b
    assert b["pre_pr"] == budget["partitioned_grow"]["ops_per_split"]


def test_gate_ineligible_configs_fall_back():
    """CEGB / extra-trees / by-node sampling keep the per-phase foil
    even with the kernel forced on (it does not model their per-split
    bookkeeping)."""
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    x, y = _data(n=400)
    for extra in ({"cegb_tradeoff": 1.0, "cegb_penalty_split": 0.1},
                  {"extra_trees": True},
                  {"feature_fraction_bynode": 0.5}):
        cfg = Config.from_params({"objective": "binary",
                                  "num_leaves": 7, "verbosity": -1,
                                  "fused_split_kernel": "on", **extra})
        ds = Dataset.from_numpy(x, cfg, label=y)
        lrn = PartitionedTreeLearner(ds, cfg)
        assert lrn.split_plan().body == "per_phase", extra


def test_gate_env_and_config_resolution():
    """The config parameter alone resolves the mode (no environment
    variable doubles it any more): its three values reach the plan as
    they are, and any other value is refused when the config is
    built."""
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    x, y = _data(n=400)
    for mode, body in (("auto", "per_phase"), ("off", "per_phase"),
                       ("on", "megakernel")):
        cfg = Config.from_params({"objective": "binary",
                                  "num_leaves": 7, "verbosity": -1,
                                  "fused_split_kernel": mode})
        assert cfg.fused_split_kernel == mode
        ds = Dataset.from_numpy(x, cfg, label=y)
        assert PartitionedTreeLearner(ds, cfg).split_plan().body == body
    assert Config().fused_split_kernel == "auto"
    with pytest.raises(Exception, match="auto, on or off"):
        Config.from_params({"fused_split_kernel": "force"})


def test_gate_auto_is_a_static_rule(monkeypatch):
    """auto = on a TPU, at the compiled body's static scope. Off on the
    CPU (the per-phase XLA path IS the CPU fast path, so auto never
    engages the twin outside tests); no lowering probe stands behind
    the rule, and the platform is asked in ONE module."""
    import lightgbm_tpu.learner.split_step as split_step
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    x, y = _data(n=400)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "verbosity": -1})
    ds = Dataset.from_numpy(x, cfg, label=y)
    serial = SerialTreeLearner(ds, cfg)
    part = PartitionedTreeLearner(ds, cfg)
    assert serial.split_plan().body == "per_phase"
    assert part.split_plan() == split_step.SplitStepPlan()
    monkeypatch.setattr(split_step, "on_tpu", lambda: True)
    assert part.split_plan().body == "megakernel"
    assert serial.split_plan() == split_step.SplitStepPlan(
        scan_kernel=True)


# ---- the decision as a table -----------------------------------------
# one row = what the function is given -> (body, scan_kernel,
# lut_partition, cat_scan). Defaults: a single-device partitioned
# learner on a TPU with compiled kernels, a numeric unbundled table of
# byte bins, 255 leaves, nothing between the phases.

def _plan(**over):
    from lightgbm_tpu.learner.split_step import plan_split_step
    from lightgbm_tpu.ops.split import SplitParams
    params = SplitParams(
        lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
        min_data_in_leaf=20.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0,
        has_categorical=over.pop("has_categorical", False),
        cegb_on=over.pop("cegb_on", False))
    kw = dict(mode="auto", params=params, bundled=False,
              num_bins_max=255, num_leaves=255, num_features=28,
              forced_plan=(),
              extra_trees=False, ff_bynode=1.0, cache_hists=True,
              mv_groups=0, serial_comm=True, interpret=False,
              has_megakernel=True, merged=True, tpu=True)
    kw.update(over)
    return plan_split_step(**kw)


MEGA, PHASE = "megakernel", "per_phase"

PLAN_ROWS = [
    # the three cells, as PERF.md section 4 says of them
    ("higgs-10m-train", dict(num_bins_max=255),
     (MEGA, True, False, False)),
    ("criteo-7m-train", dict(num_bins_max=255, num_features=67),
     (MEGA, True, False, False)),
    ("expo-10m-train", dict(has_categorical=True, num_bins_max=256,
                            num_features=40),
     (PHASE, False, True, True)),
    # 2,000 columns: more than the megakernel's unrolled body takes,
    # so the per-phase kernels, which cut their work by columns; the
    # fifth field says the width alone decided
    ("epsilon-400k-train", dict(num_features=2000),
     (PHASE, True, False, False, True)),
    ("widest-megakernel", dict(num_features=192),
     (MEGA, True, False, False)),
    ("narrowest-wide", dict(num_features=193),
     (PHASE, True, False, False, True)),
    # width is not the reason where something else refuses first, or
    # off a TPU, or where the megakernel is forced on
    ("wide-categorical", dict(num_features=2000, has_categorical=True,
                              num_bins_max=256),
     (PHASE, False, True, True)),
    ("wide-cpu", dict(num_features=2000, tpu=False, interpret=True),
     (PHASE, False, False, False)),
    ("wide-on-cpu-twin", dict(num_features=2000, mode="on", tpu=False,
                              interpret=True),
     (MEGA, False, False, False)),
    # 4,228 one-hot columns bundled into 47 byte columns whose widest
    # holds 256 values: the plan sees the physical width, so the
    # width does not refuse; the bundles do
    ("allstate-12m-train", dict(bundled=True, num_bins_max=256,
                                num_features=47),
     (PHASE, True, True, False)),
    ("bundled", dict(bundled=True), (PHASE, True, True, False)),
    # 137 dense numeric columns, 256-byte rows: the third width
    # through the megakernel, the first between 69 and 192 to train on
    # a chip (PR 37)
    ("msltr-2m-train", dict(num_features=137, num_bins_max=256),
     (MEGA, True, False, False)),
    ("257-bins", dict(num_bins_max=257), (PHASE, True, False, False)),
    ("forced-plan", dict(forced_plan=((0, 1, 3, False),)),
     (PHASE, True, False, False)),
    ("cegb", dict(cegb_on=True), (PHASE, False, False, False)),
    ("extra-trees", dict(extra_trees=True),
     (PHASE, True, False, False)),
    ("ff-bynode", dict(ff_bynode=0.5), (PHASE, True, False, False)),
    ("pool-bounded-hists", dict(cache_hists=False),
     (PHASE, True, False, False)),
    ("mesh-comm", dict(serial_comm=False, has_megakernel=False),
     (PHASE, True, False, False)),
    ("one-leaf", dict(num_leaves=1), (PHASE, True, False, False)),
    ("legacy-carry", dict(merged=False), (PHASE, True, False, False)),
    ("off", dict(mode="off"), (PHASE, True, False, False)),
    ("on-cpu-twin", dict(mode="on", tpu=False, interpret=True),
     (MEGA, False, False, False)),
    ("on-cpu-twin-categorical-forced",
     dict(mode="on", tpu=False, interpret=True, has_categorical=True,
          forced_plan=((0, 1, 3, False),)),
     (MEGA, False, True, True)),
    ("on-compiled-forced-plan",
     dict(mode="on", forced_plan=((0, 1, 3, False),)),
     (PHASE, True, False, False)),
    ("auto-cpu", dict(tpu=False, interpret=True),
     (PHASE, False, False, False)),
    ("auto-cpu-categorical",
     dict(tpu=False, interpret=True, has_categorical=True),
     (PHASE, False, True, True)),
    ("serial-learner-tpu", dict(has_megakernel=False),
     (PHASE, True, False, False)),
    ("tpu-interpret-kernels", dict(interpret=True),
     (MEGA, False, False, False)),
]


@pytest.mark.parametrize("given,want", [r[1:] for r in PLAN_ROWS],
                         ids=[r[0] for r in PLAN_ROWS])
def test_split_step_plan_table(given, want):
    from lightgbm_tpu.learner.split_step import SplitStepPlan
    assert _plan(**given) == SplitStepPlan(*want)


@pytest.mark.parametrize("given", [
    dict(has_megakernel=False),                       # serial, TPU
    dict(has_megakernel=False, tpu=False),            # serial, CPU
    dict(has_megakernel=False, serial_comm=False),    # mesh
], ids=["serial-tpu", "serial-cpu", "mesh"])
def test_split_step_plan_on_without_a_megakernel_raises(given):
    from lightgbm_tpu.utils import LightGBMError
    with pytest.raises(LightGBMError, match="no split-step megakernel"):
        _plan(mode="on", **given)


def test_cells_plans_from_real_learners(monkeypatch):
    """The rows that are cells, from learners built on tables of the
    cells' shapes (Higgs: 28 numeric; Expo: categorical columns), the
    platform standing in through the ONE module that asks it."""
    import lightgbm_tpu.learner.split_step as split_step
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    monkeypatch.setattr(split_step, "on_tpu", lambda: True)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 255,
                              "verbosity": -1})
    x, y = _data(n=600, f=28)
    higgs = PartitionedTreeLearner(Dataset.from_numpy(x, cfg, label=y),
                                   cfg, interpret=False)
    assert higgs.split_plan() == split_step.SplitStepPlan(
        MEGA, True, False, False)
    assert higgs.params.use_scan_kernel
    x, y = _data(n=600, categorical=True)
    expo = PartitionedTreeLearner(
        Dataset.from_numpy(x, cfg, label=y, categorical_features=[0]),
        cfg, interpret=False)
    assert expo.split_plan() == split_step.SplitStepPlan(
        PHASE, False, True, True)
    assert not expo.params.use_scan_kernel
    # Epsilon: 2,000 numeric columns (every column kept: the learner's
    # width is the table's)
    x, y = _data(n=600, f=2000)
    wide = PartitionedTreeLearner(Dataset.from_numpy(x, cfg, label=y),
                                  cfg, interpret=False)
    assert wide.num_groups == 2000
    assert wide.split_plan() == split_step.SplitStepPlan(
        PHASE, True, False, False, wide=True)
    assert wide.params.use_scan_kernel
    # Allstate: a one-hot CSR of 4,228 columns that the dataset
    # bundles into 47 (the benchmark's width probe: every column holds
    # a value); the learner's width is the physical one
    from benchmarks.generators.allstate_like import CARDS, NUMERIC
    from benchmarks.kinds.train_sparse import _probe_table
    x, y = _probe_table(NUMERIC + sum(CARDS), NUMERIC, list(CARDS))
    sparse_cfg = Config.from_params({
        "objective": "binary", "num_leaves": 255, "verbosity": -1,
        "min_data_in_bin": 1, "feature_pre_filter": False})
    onehot = PartitionedTreeLearner(
        Dataset.from_scipy(x, sparse_cfg, label=y), sparse_cfg,
        interpret=False)
    assert (onehot.num_features, onehot.num_groups) == (4228, 47)
    assert onehot.bundled and onehot.cache_hists
    assert onehot.split_plan() == split_step.SplitStepPlan(
        PHASE, True, True, False)
    assert onehot.params.use_scan_kernel
    # MS LTR: 137 numeric columns in query groups, lambdarank; the
    # plan knows no objective
    x, y = _data(n=600, f=137)
    rank_cfg = Config.from_params({"objective": "lambdarank",
                                   "num_leaves": 255, "verbosity": -1})
    msltr = PartitionedTreeLearner(
        Dataset.from_numpy(x, rank_cfg, label=y, group=[200, 1, 399]),
        rank_cfg, interpret=False)
    assert msltr.num_groups == 137 and msltr.mat.shape[1] == 256
    assert msltr.split_plan() == split_step.SplitStepPlan(
        MEGA, True, False, False)


def test_forced_splits_keep_foil_for_forced_steps(tmp_path):
    """A forcedsplits plan coexists with the fused while-loop body:
    forced pre-steps run the foil, the remaining splits the kernel —
    byte-identical models either way."""
    import json
    x, y = _data(n=900)
    fn = tmp_path / "forced.json"
    fn.write_text(json.dumps({"feature": 1, "threshold": 0.0}))
    params = {"forcedsplits_filename": str(fn)}
    t_foil = _model_text(False, params, x, y)
    t_fused = _model_text(True, params, x, y)
    assert t_fused == t_foil


def _package_heads(node):
    """First package under ``lightgbm_tpu`` that an import statement
    written in ``lightgbm_tpu/ops/`` reaches (``from ..learner.x import
    y``, ``from .. import learner``, ``import lightgbm_tpu.learner``)."""
    import ast
    if isinstance(node, ast.ImportFrom):
        mod = node.module.split(".") if node.module else []
        if node.level >= 2:
            return mod[:1] or [a.name for a in node.names]
        if node.level == 0 and mod[:1] == ["lightgbm_tpu"]:
            return mod[1:2] or [a.name for a in node.names]
    elif isinstance(node, ast.Import):
        return [a.name.split(".")[1] for a in node.names
                if a.name.startswith("lightgbm_tpu.")]
    return []


def test_ops_import_nothing_from_the_layers_above():
    """A kernel module does not know its caller: nothing under
    ``lightgbm_tpu/ops/`` imports ``learner``, ``models`` or
    ``parallel`` (what a kernel shares with the learners lives in
    ``ops/``; the carry and the comm arrive as arguments)."""
    import ast
    import pathlib

    import lightgbm_tpu.ops as ops
    above = {"learner", "models", "parallel"}
    # the walker sees each way of writing such an import
    for text in ("from ..learner.comm import SERIAL_COMM",
                 "from .. import models", "import lightgbm_tpu.parallel",
                 "from lightgbm_tpu.learner import serial",
                 "def f():\n    from ..learner import split_step"):
        assert above & {h for n in ast.walk(ast.parse(text))
                        for h in _package_heads(n)}, text
    assert not _package_heads(ast.parse("from .split import x").body[0])
    files = sorted(pathlib.Path(ops.__file__).parent.glob("*.py"))
    assert len(files) > 10
    found = [(path.name, node.lineno)
             for path in files
             for node in ast.walk(ast.parse(path.read_text()))
             if above & set(_package_heads(node))]
    assert not found, found
