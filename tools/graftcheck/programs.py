"""Example builders: one per registered program name.

Each builder returns a ``jax`` *Lowered* object for its program at the
FIXED tiny graftcheck config — the checker compiles it and runs the
contract checks over the compiled text. Builders live here (with the
checker), keyed by the names the hot modules register in
``lightgbm_tpu.utils.jit_registry`` — the package carries the
contract, the tool carries the harness.

Shapes are deliberately tiny: every check here is shape-independent
(op lists, alias maps, collective multisets and dtype sets do not
change with row count), so the whole registry compiles in CI time.
Shared fixtures (datasets, trained boosters, learners) are built
lazily ONCE per process in ``_env`` and reused across builders.

The mesh programs shard over every visible device — run with
``--xla_force_host_platform_device_count=8`` (the CLI arranges this
itself; tests inherit conftest's virtual 8-device CPU mesh).
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, List

# fixed tiny config (grow programs reuse the census tiny shape that
# tests already pin against the committed dispatch budget)
GROW_ROWS, GROW_FEATURES, GROW_LEAVES = 512, 8, 15
N, F, L, C = 256, 8, 16, 4

BUILDERS: Dict[str, Callable] = {}


def builder(name: str):
    def deco(fn):
        BUILDERS[name] = fn
        return fn
    return deco


# ---------------------------------------------------------------------
_ENV: Dict = {}


def _env(key: str, make: Callable):
    if key not in _ENV:
        _ENV[key] = make()
    return _ENV[key]


def _grow_fixture():
    from tools.hlo_census import _build_dataset
    return _build_dataset(GROW_ROWS, GROW_FEATURES, GROW_LEAVES)


def _train_booster(extra_params: Dict, rounds: int = 3, seed: int = 0):
    import numpy as np

    import lightgbm_tpu as lgb
    rng = np.random.RandomState(seed)
    x = rng.randn(N, F).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + 0.2 * rng.randn(N) > 0) \
        .astype(np.float32)
    params = {"objective": "binary", "num_leaves": L - 1,
              "min_data_in_leaf": 5, "verbosity": -1, "seed": seed}
    params.update(extra_params)
    ds = lgb.Dataset(x, label=y, free_raw_data=False)
    return lgb.train(params, ds, num_boost_round=rounds)


def _booster():
    return _env("booster", lambda: _train_booster({}))


def _booster_linear():
    return _env("booster_linear",
                lambda: _train_booster({"linear_tree": True}, rounds=2))


def _best_tree(bst):
    models = bst._gbdt.models
    return max(models, key=lambda t: t.num_leaves)


def _serial_learner():
    def make():
        from lightgbm_tpu.learner.serial import SerialTreeLearner
        ds, cfg = _env("grow_fixture", _grow_fixture)
        return SerialTreeLearner(ds, cfg)
    return _env("serial_learner", make)


def _partitioned_learner():
    def make():
        from lightgbm_tpu.learner.partitioned import \
            PartitionedTreeLearner
        ds, cfg = _env("grow_fixture", _grow_fixture)
        return PartitionedTreeLearner(ds, cfg)
    return _env("partitioned_learner", make)


def _spec_fn(name: str):
    from lightgbm_tpu.utils.jit_registry import get
    spec = get(name)
    if spec is None or spec.fn is None:
        raise RuntimeError(f"program {name!r} is not registered (or "
                           "its dynamic creation path did not run)")
    return spec.fn


# --- gbdt score updaters / bagging -----------------------------------
@builder("score_add_leaf")
def _b_score_add_leaf():
    import jax.numpy as jnp
    fn = _spec_fn("score_add_leaf")
    return fn.lower(jnp.zeros((N, 1), jnp.float32),
                    jnp.zeros((L,), jnp.float32),
                    jnp.zeros((N,), jnp.int32), tid=0)


@builder("score_add_col")
def _b_score_add_col():
    import jax.numpy as jnp
    fn = _spec_fn("score_add_col")
    return fn.lower(jnp.zeros((N, 1), jnp.float32),
                    jnp.zeros((N,), jnp.float32), tid=0)


@builder("score_add_leaf_linear")
def _b_score_add_leaf_linear():
    import jax.numpy as jnp
    fn = _spec_fn("score_add_leaf_linear")
    return fn.lower(jnp.zeros((N, 1), jnp.float32),
                    jnp.zeros((L,), jnp.float32),
                    jnp.zeros((L,), jnp.float32),
                    jnp.zeros((L, C), jnp.float32),
                    jnp.full((L, C), -1, jnp.int32),
                    jnp.zeros((N,), jnp.int32),
                    jnp.zeros((N, F), jnp.float32), tid=0)


@builder("refit_tree")
def _b_refit_tree():
    import jax.numpy as jnp
    fn = _spec_fn("refit_tree")
    return fn.lower(jnp.zeros((N, 1), jnp.float32),
                    jnp.zeros((N,), jnp.int32),
                    jnp.zeros((N,), jnp.float32),
                    jnp.ones((N,), jnp.float32),
                    jnp.zeros((L,), jnp.float32),
                    jnp.float32(0.1), jnp.float32(0.9),
                    nl=L, tid=0, l1=0.0, l2=0.0, mds=20.0)


@builder("refit_tree_linear")
def _b_refit_tree_linear():
    import jax.numpy as jnp
    fn = _spec_fn("refit_tree_linear")
    return fn.lower(jnp.zeros((N, 1), jnp.float32),
                    jnp.zeros((N,), jnp.int32),
                    jnp.zeros((N,), jnp.float32),
                    jnp.ones((N,), jnp.float32),
                    jnp.zeros((N, F), jnp.float32),
                    jnp.full((L, C), -1, jnp.int32),
                    jnp.zeros((L,), jnp.float32),
                    jnp.zeros((L,), jnp.float32),
                    jnp.zeros((L, C), jnp.float32),
                    jnp.float32(0.1), jnp.float32(0.9),
                    nl=L, tid=0, l1=0.0, l2=0.0, mds=20.0,
                    lam=0.01, l2lin=0.0)


@builder("bag_mask")
def _b_bag_mask():
    import jax
    import jax.numpy as jnp
    fn = _spec_fn("bag_mask")
    return fn.lower(jax.random.PRNGKey(0), jnp.int32(0), None,
                    freq=1, n=N, frac=0.8, pos_frac=1.0, neg_frac=1.0)


@builder("gbdt_grad")
def _b_gbdt_grad():
    import jax.numpy as jnp
    bst = _booster()          # registration happens at construction
    return _spec_fn("gbdt_grad").lower(jnp.zeros((N,), jnp.float32))


@builder("gbdt_grad_bag")
def _b_gbdt_grad_bag():
    import jax.numpy as jnp

    def make():
        bst = _train_booster({"bagging_fraction": 0.5,
                              "bagging_freq": 1}, rounds=1, seed=1)
        g = bst._gbdt
        g._grad_hess_bag(g.train_score[:, 0], 0)  # builds the program
        return bst
    _env("booster_bag", make)
    return _spec_fn("gbdt_grad_bag").lower(
        jnp.zeros((N,), jnp.float32), jnp.int32(0))


@builder("gbdt_fused_block")
def _b_gbdt_fused_block():
    import jax.numpy as jnp

    def make():
        import os
        os.environ["LGBM_TPU_FUSE_ITERS"] = "1"
        try:
            bst = _train_booster({"tree_learner": "partitioned"},
                                 rounds=1, seed=2)
            g = bst._gbdt
            assert g._fused_scan_supported(), \
                "fused-scan path not eligible at the fixture config"
            g._train_fused_blocks(0)   # builds _fused_jit, trains 0
            return bst
        finally:
            os.environ.pop("LGBM_TPU_FUSE_ITERS", None)
    bst = _env("booster_fused", make)
    g = bst._gbdt
    ln = g.learner
    return _spec_fn("gbdt_fused_block").lower(
        ln.mat, ln.ws, g.train_score, (), jnp.float32(0.1),
        jnp.int32(g.iter), m=2)


# --- tree traversal / prediction -------------------------------------
@builder("tree_traverse_binned")
def _b_tree_traverse():
    import jax.numpy as jnp
    bst = _booster()
    t = _best_tree(bst)
    binned = bst._gbdt.train_data.binned_device
    return _spec_fn("tree_traverse_binned").lower(
        binned, *t._padded_traversal_args(), mv_slots=None,
        mv_present=False)


@builder("tree_traverse_add")
def _b_tree_traverse_add():
    import jax.numpy as jnp
    bst = _booster()
    t = _best_tree(bst)
    binned = bst._gbdt.train_data.binned_device
    score = jnp.zeros((binned.shape[0], 1), jnp.float32)
    return _spec_fn("tree_traverse_add").lower(
        score, binned, *t._padded_traversal_args(), mv_slots=None,
        tid=0, mv_present=False)


@builder("tree_traverse_linear")
def _b_tree_traverse_linear():
    bst = _booster_linear()
    t = _best_tree(bst)
    ds = bst._gbdt.train_data
    return _spec_fn("tree_traverse_linear").lower(
        ds.binned_device, *t._padded_traversal_args(),
        *t._padded_linear_args(), ds.raw_numeric_device,
        mv_slots=None, mv_present=False)


@builder("tree_traverse_add_linear")
def _b_tree_traverse_add_linear():
    import jax.numpy as jnp
    bst = _booster_linear()
    t = _best_tree(bst)
    ds = bst._gbdt.train_data
    score = jnp.zeros((ds.binned_device.shape[0], 1), jnp.float32)
    return _spec_fn("tree_traverse_add_linear").lower(
        score, ds.binned_device, *t._padded_traversal_args(),
        *t._padded_linear_args(), ds.raw_numeric_device,
        mv_slots=None, tid=0, mv_present=False)


@builder("tree_traverse_arrays")
def _b_tree_traverse_arrays():
    import jax.numpy as jnp
    bst = _booster()
    t = _best_tree(bst)
    arr = t._padded_traversal_args()
    binned = bst._gbdt.train_data.binned_device
    return _spec_fn("tree_traverse_arrays").lower(
        binned, *arr, jnp.int32(t.num_leaves), mv_slots=None,
        mv_present=False)


@builder("predict_scan_trees")
def _b_predict_scan_trees():
    import jax.numpy as jnp
    from lightgbm_tpu.predictor import stack_tree_arrays
    bst = _booster()
    models = list(bst._gbdt.models)
    stacked = _env("stacked", lambda: stack_tree_arrays(models, 1))
    binned = bst._gbdt.train_data.binned_device
    return _spec_fn("predict_scan_trees").lower(
        binned, *stacked.device(), 1, None, False)


@builder("predict_scan_leaf_idx")
def _b_predict_scan_leaf_idx():
    import jax.numpy as jnp
    from lightgbm_tpu.predictor import stack_tree_arrays
    bst = _booster()
    models = list(bst._gbdt.models)
    stacked = _env("stacked", lambda: stack_tree_arrays(models, 1))
    binned = bst._gbdt.train_data.binned_device
    return _spec_fn("predict_scan_leaf_idx").lower(
        binned, *stacked.device(), None, False)


@builder("predict_scan_trees_linear")
def _b_predict_scan_trees_linear():
    import jax.numpy as jnp
    from lightgbm_tpu.predictor import stack_tree_arrays
    bst = _booster_linear()
    models = list(bst._gbdt.models)
    stacked = _env("stacked_linear",
                   lambda: stack_tree_arrays(models, 1))
    ds = bst._gbdt.train_data
    return _spec_fn("predict_scan_trees_linear").lower(
        ds.binned_device, *stacked.device(), *stacked.device_linear(),
        ds.raw_numeric_device, 1, None, False)


# --- objectives / sampling / guards / leaf models --------------------
@builder("xendcg_grad")
def _b_xendcg_grad():
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import Metadata
    from lightgbm_tpu.objective.rank import RankXENDCG
    # the program is the objective's own (it closes over the query
    # layout's static shapes; the layout's arrays are arguments)
    n = 32
    md = Metadata(n)
    md.set_label(np.zeros(n, np.float32))
    md.set_query([8, 8, 8, 8])
    obj = RankXENDCG(Config.from_params({"objective": "rank_xendcg",
                                         "verbosity": -1}))
    obj.init(md, n)
    return _spec_fn("xendcg_grad").lower(
        jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
        obj._operands)


@builder("goss_weights")
def _b_goss_weights():
    import jax
    import jax.numpy as jnp
    return _spec_fn("goss_weights").lower(
        jnp.zeros((N, 1), jnp.float32), jnp.ones((N, 1), jnp.float32),
        jax.random.PRNGKey(0), top_rate=0.2, other_rate=0.1)


@builder("finite_ok")
def _b_finite_ok():
    import jax.numpy as jnp
    return _spec_fn("finite_ok").lower(
        jnp.zeros((N,), jnp.float32), jnp.ones((N,), jnp.float32))


@builder("linear_leaf_fit")
def _b_linear_leaf_fit():
    import jax.numpy as jnp
    return _spec_fn("linear_leaf_fit").lower(
        jnp.zeros((N, F), jnp.float32), jnp.zeros((N,), jnp.int32),
        jnp.zeros((N,), jnp.float32), jnp.ones((N,), jnp.float32),
        jnp.ones((N,), jnp.float32), jnp.full((L, C), -1, jnp.int32),
        jnp.zeros((L,), jnp.float32), lam=0.1, l2=0.0)


# --- multiboost: B models' iteration as ONE program ------------------
def _multiboost_batch():
    def make():
        import numpy as np

        import lightgbm_tpu as lgb
        from lightgbm_tpu.multiboost.batch import (BoosterBatch,
                                                   ModelSpec)
        rng = np.random.RandomState(0)
        x = rng.randn(GROW_ROWS, GROW_FEATURES).astype(np.float32)
        y = (x[:, 0] - 0.5 * x[:, 1]
             + 0.2 * rng.randn(GROW_ROWS) > 0).astype(np.float32)
        specs = [ModelSpec(params={
            "objective": "binary", "num_leaves": GROW_LEAVES,
            "min_data_in_leaf": 5, "verbosity": -1,
            "learning_rate": 0.1 + 0.1 * i}) for i in range(3)]
        bb = BoosterBatch(lgb.Dataset(x, label=y), specs,
                          num_boost_round=3)
        return bb.setup()
    return _env("multiboost_batch", make)


@builder("multiboost_grow")
def _b_multiboost_grow():
    """The vmapped grow program at its hot (async) boundary: the
    [B, N] score is donated and the contract pins zero collectives —
    vmap widening a cross-device op along the model axis is exactly
    the regression GC401 catches here (see the bad_multiboost
    fixture)."""
    import jax.numpy as jnp
    bb = _multiboost_batch()
    fn = _spec_fn("multiboost_grow")
    score = jnp.zeros((bb.B, bb.N), jnp.float32)
    return fn.lower(score, jnp.int32(1), bb._attrs, bb._masks,
                    bb._hyp, sync0=False)


@builder("multiboost_score_add")
def _b_multiboost_score_add():
    import jax.numpy as jnp
    fn = _spec_fn("multiboost_score_add")
    B = 3
    return fn.lower(jnp.zeros((B, N), jnp.float32),
                    jnp.zeros((B, L), jnp.float32),
                    jnp.zeros((B, N), jnp.int32))


# --- grow programs (shared with the hlo_census front-end) ------------
@builder("serial_grow")
def _b_serial_grow():
    from tools.hlo_census import lower_serial
    ds, cfg = _env("grow_fixture", _grow_fixture)
    return lower_serial(ds, cfg)


@builder("serial_grow_cegb")
def _b_serial_grow_cegb():
    """The lazy-CEGB configuration of the serial grow program: its
    [N, F] charged matrix is the donated buffer the jit site declares
    — this is the config where GC101 proves the alias materializes."""
    import jax.numpy as jnp

    def make():
        import numpy as np

        from lightgbm_tpu.config import Config
        from lightgbm_tpu.data.dataset import Dataset
        from lightgbm_tpu.learner.serial import SerialTreeLearner
        rng = np.random.RandomState(0)
        x = rng.randn(GROW_ROWS, GROW_FEATURES).astype(np.float32)
        y = (rng.rand(GROW_ROWS) < 0.5).astype(np.float32)
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": GROW_LEAVES,
            "min_data_in_leaf": 20, "verbosity": -1,
            "cegb_penalty_feature_lazy":
                [0.1] * GROW_FEATURES})
        return SerialTreeLearner(Dataset.from_numpy(x, cfg, label=y),
                                 cfg)
    lrn = _env("serial_learner_cegb", make)
    assert lrn._cegb_charged is not None, \
        "fixture config did not enable lazy CEGB"
    n = lrn.dataset.num_data
    from lightgbm_tpu.learner.serial import _grow_jit
    from lightgbm_tpu.learner.split_step import split_fusion_default
    return _grow_jit.lower(
        lrn.binned, jnp.zeros((n,), jnp.float32),
        jnp.ones((n,), jnp.float32), lrn._ones_rows,
        lrn._all_features, lrn.meta, rand_key=None,
        cegb_used0=lrn._cegb_used, cegb_charged0=lrn._cegb_charged,
        params=lrn.params, num_leaves=lrn.num_leaves,
        max_depth=lrn.max_depth, num_bins_max=lrn.num_bins_max,
        hist_method=lrn.hist_method, bundled=lrn.bundled,
        extra_trees=False, ff_bynode=1.0, bynode_count=2,
        forced_plan=(), cache_hists=lrn.cache_hists,
        mv_slots=lrn.mv_slots, mv_groups=lrn.mv_groups,
        has_monotone=lrn.has_monotone,
        split_fusion=split_fusion_default())


@builder("partitioned_grow")
def _b_partitioned_grow():
    from tools.hlo_census import lower_partitioned
    ds, cfg = _env("grow_fixture", _grow_fixture)
    return lower_partitioned(ds, cfg)


# --- pallas kernel wrappers (interpret mode on CPU) ------------------
@builder("hist_child_stream")
def _b_hist_child_stream():
    """The whole-row one-hot stream (the root's and a leaf segment's
    histogram up to ``MAX_FUSED_F`` columns) on a learner's matrix."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_pallas import SLICE_BLK

    def make():
        import numpy as np

        from lightgbm_tpu.config import Config
        from lightgbm_tpu.data.dataset import Dataset
        from lightgbm_tpu.learner.partitioned import \
            PartitionedTreeLearner
        rng = np.random.RandomState(0)
        x = rng.randn(N, F).astype(np.float32)
        y = (rng.rand(N) < 0.5).astype(np.float32)
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": 7, "max_bin": 15,
            "min_data_in_leaf": 5, "verbosity": -1})
        return PartitionedTreeLearner(
            Dataset.from_numpy(x, cfg, label=y), cfg)
    lrn = _env("partitioned_learner_hist", make)
    return _spec_fn("hist_child_stream").lower(
        lrn.mat, jnp.int32(0), jnp.int32(lrn.num_data),
        num_features=lrn.num_groups, num_bins=lrn.num_bins_max,
        blk=SLICE_BLK, interpret=True)


@builder("hist_segment_slices")
def _b_hist_segment_slices():
    """The wide table's histogram (one call, the column slice a grid
    axis) on the same learner's matrix: one slice there."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_pallas import SLICE_BLK
    _b_hist_child_stream()          # builds the shared learner
    lrn = _ENV["partitioned_learner_hist"]
    return _spec_fn("hist_segment_slices").lower(
        lrn.mat, jnp.int32(0), jnp.int32(lrn.num_data),
        num_features=lrn.num_groups, num_bins=lrn.num_bins_max,
        blk=SLICE_BLK, interpret=True)


def _partition_args(blk: int):
    import jax.numpy as jnp
    lrn = _partitioned_learner()
    b = lrn.num_bins_max
    lut = jnp.zeros((1, 256), jnp.float32)
    return (lrn.mat, lrn.ws, jnp.int32(0), jnp.int32(lrn.num_data),
            jnp.int32(0), jnp.int32(b // 2), jnp.int32(0),
            jnp.int32(0), jnp.int32(0), jnp.int32(b), jnp.int32(0),
            lut), dict(blk=blk, interpret=True, use_lut_path=False)


@builder("partition_segment")
def _b_partition_segment():
    from lightgbm_tpu.learner.partitioned import PART_BLK
    args, kw = _partition_args(PART_BLK)
    return _spec_fn("partition_segment").lower(*args, **kw)


@builder("leaf_of_pos")
def _b_leaf_of_pos():
    import jax.numpy as jnp
    lrn = _partitioned_learner()
    table = jnp.zeros((lrn.num_leaves,), jnp.int32)
    # the fused driver's form: the pass paints the f32 leaf values
    return _spec_fn("leaf_of_pos").lower(
        table, table, jnp.int32(1),
        jnp.zeros((lrn.num_leaves,), jnp.float32), n=lrn.num_data,
        interpret=True)


def _fused_step_state(lrn):
    import jax.numpy as jnp

    from lightgbm_tpu.learner.partitioned import segment_grow_pack
    from lightgbm_tpu.ops.split_step_pallas import pack_meta_tables
    pack = segment_grow_pack(lrn.num_leaves,
                             has_cat=lrn.params.has_categorical,
                             has_monotone=lrn.has_monotone)
    ks = len(pack.sf_fields) + len(pack.si_fields)
    kt = len(pack.tf_fields) + len(pack.ti_fields)
    big_l = lrn.num_leaves
    imeta, fmeta = pack_meta_tables(
        lrn.meta, jnp.ones((lrn.meta.num_bins.shape[0],), bool))
    # the packed carriers are i32 (float rows bitcast; StatePack)
    return (pack, jnp.zeros((ks, big_l), jnp.int32),
            jnp.zeros((kt, big_l - 1), jnp.int32), imeta, fmeta)


@builder("fused_split_step_segment")
def _b_fused_split_step_segment():
    import jax.numpy as jnp
    from lightgbm_tpu.learner.comm import SERIAL_COMM
    from lightgbm_tpu.learner.partitioned import HIST_BLK
    lrn = _partitioned_learner()
    pack, S, T, imeta, fmeta = _fused_step_state(lrn)
    g = lrn.num_groups
    b = lrn.num_bins_max
    hist = jnp.zeros((lrn.num_leaves, g, b, 3), jnp.float32)
    return _spec_fn("fused_split_step_segment").lower(
        jnp.int32(1), S, T, lrn.mat, lrn.ws, hist, imeta, fmeta,
        params=lrn.params, pack=pack, comm=SERIAL_COMM,
        big_l=lrn.num_leaves, max_depth=lrn.max_depth, b=b, f=g,
        n=lrn.num_data, bundled=lrn.bundled,
        has_monotone=lrn.has_monotone, blk=HIST_BLK, interpret=True)


@builder("split_scan_kernel")
def _b_split_scan_kernel():
    import jax.numpy as jnp
    lrn = _serial_learner()
    meta = lrn.meta
    f = int(meta.num_bins.shape[0])
    b = lrn.num_bins_max
    scal = jnp.zeros((1, 5), jnp.float32)
    imeta = jnp.stack([meta.num_bins, meta.missing, meta.default_bin,
                       meta.monotone], axis=1).astype(jnp.int32)
    fmeta = jnp.stack([meta.penalty,
                       jnp.ones((f,), jnp.float32)], axis=1)
    hist = jnp.zeros((f, b), jnp.float32)
    return _spec_fn("split_scan_kernel").lower(
        scal, imeta, fmeta, hist, hist, hist, params=lrn.params,
        interpret=True)


# --- mesh learners (collective programs; 8-device virtual mesh) ------
def _mesh_einsum_lower(name: str, cls_name: str, env_key: str):
    import jax.numpy as jnp

    def make():
        import lightgbm_tpu.parallel.learners as learners
        ds, cfg = _env("grow_fixture", _grow_fixture)
        return getattr(learners, cls_name)(ds, cfg)
    lrn = _env(env_key, make)
    pf = lrn._fn                     # functools.partial(sharded, ...)
    n_pad = lrn._n_pad
    grad = jnp.zeros((n_pad,), jnp.float32)
    hess = jnp.ones((n_pad,), jnp.float32)
    bag = jnp.ones((n_pad,), jnp.float32)
    fmask = jnp.ones((lrn.dataset.num_features,), bool)
    rkey = jnp.zeros((2, 2), jnp.uint32)
    return pf.func.lower(*pf.args, grad, hess, bag, fmask, rkey,
                         lrn._cegb_arg())


@builder("mesh_data_grow")
def _b_mesh_data_grow():
    return _mesh_einsum_lower("mesh_data_grow",
                              "DataParallelTreeLearner", "mesh_data")


@builder("mesh_feature_grow")
def _b_mesh_feature_grow():
    return _mesh_einsum_lower("mesh_feature_grow",
                              "FeatureParallelTreeLearner",
                              "mesh_feature")


@builder("mesh_voting_grow")
def _b_mesh_voting_grow():
    return _mesh_einsum_lower("mesh_voting_grow",
                              "VotingParallelTreeLearner",
                              "mesh_voting")


@builder("mesh_partitioned_grow")
def _b_mesh_partitioned_grow():
    import jax.numpy as jnp

    def make():
        from lightgbm_tpu.parallel.learners import \
            MeshPartitionedTreeLearner
        ds, cfg = _env("grow_fixture", _grow_fixture)
        return MeshPartitionedTreeLearner(ds, cfg, mode="data")
    lrn = _env("mesh_partitioned", make)
    n_pad = lrn._n_pad
    grad = jnp.zeros((n_pad,), jnp.float32)
    hess = jnp.ones((n_pad,), jnp.float32)
    bag = jnp.ones((n_pad,), jnp.float32)
    fmask = jnp.ones((lrn.num_features,), bool)
    rkey = jnp.zeros((2, 2), jnp.uint32)
    cegb0 = jnp.zeros((lrn.num_features,), bool)
    return _spec_fn("mesh_partitioned_grow").lower(
        lrn.mat, lrn.ws, lrn.grow_operands(), grad, hess, bag, fmask,
        rkey, cegb0)


# ---------------------------------------------------------------------
def registered_names() -> List[str]:
    """Names of every registered/declared program graftcheck covers:
    the static registrations import-time discovery sees, plus the
    dynamic ones whose builders create them on demand."""
    return sorted(BUILDERS)


def build_program(name: str) -> str:
    """Lower + compile one program; returns the compiled HLO text."""
    if name not in BUILDERS:
        raise KeyError(f"no example builder for program {name!r}")
    with warnings.catch_warnings():
        # jax warns when a declared donation is unused at THIS example
        # config (e.g. serial_grow with CEGB off) — that is exactly
        # what the manifest's donation count records, not noise worth
        # failing a CI log grep over
        warnings.filterwarnings(
            "ignore", message=".*[Dd]onat.*", category=UserWarning)
        low = BUILDERS[name]()
        return low.compile().as_text()


def import_side_registrations() -> None:
    """Import every module that registers programs at import time, so
    the registry is fully populated before a check run (dynamic
    programs register inside their builders)."""
    # graftlint: allow[GL601]
    import lightgbm_tpu.models.gbdt      # noqa: F401
    import lightgbm_tpu.models.linear    # noqa: F401
    import lightgbm_tpu.models.tree      # noqa: F401
    import lightgbm_tpu.models.variants  # noqa: F401
    import lightgbm_tpu.multiboost.program       # noqa: F401
    import lightgbm_tpu.objective.rank   # noqa: F401
    import lightgbm_tpu.ops.hist_pallas  # noqa: F401
    import lightgbm_tpu.ops.partition_pallas     # noqa: F401
    import lightgbm_tpu.ops.split_scan_pallas    # noqa: F401
    import lightgbm_tpu.ops.split_step_pallas    # noqa: F401
    import lightgbm_tpu.predictor        # noqa: F401
    import lightgbm_tpu.robustness.guards        # noqa: F401
    # graftlint: allow[GL601]
    from lightgbm_tpu.learner import partitioned, serial  # noqa: F401
