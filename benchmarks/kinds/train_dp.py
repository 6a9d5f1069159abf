"""Training cells on the data-parallel deployment: the table's rows
sharded over the chips of one host, one shard a chip, and the mesh
learner's collectives between the phases of every split.

The step, the window, the fixed-work rate, the counters, the facts and
the checks (b) and (c) are those of ``kinds/train.py`` (its docstring
describes them): this runner calls its ``run``, as
``kinds/train_rank.py`` does. It adds two things:

* **a probe, before any data is made** (``_require_table_free_block``):
  two tables of ``probe_rows`` rows of the cell's width, over the
  cell's shards, whose bins differ (the second is shifted by one
  standard deviation, so every column's default and most frequent bin
  move). The mesh fused block of each is lowered, and the run raises
  unless the two texts are equal; then one tree is grown on each
  through that block, and the run raises if the second compiled
  anything the persistent cache did not hold. A program whose mesh
  block holds its table's metadata as constants compiles that block
  anew for every seed, which a run's time limit cannot hold at the
  cell's size: it fails here within seconds of its start;
* **check (a) at the timed size** (``_check_first_tree``): the warm-up
  step's first tree, grown on all the rows over all the chips outside
  the window, against ``benchmarks/reference/tree_check_numpy.py`` in
  float64 from the training scores fetched just before that step
  (every split's counts, sums and gain from the rows the tree sends
  each way; the best split of every node of the first
  ``check.search_levels`` levels by the reference's own histogram
  search), with the tolerances ``check.split_gain_rtol`` and
  ``check.split_gain_median_rtol`` of the configuration (the sums are
  printed as readings). The 100,000-row AUC
  check of ``kinds/train.py`` runs as well; both must pass.

``facts["max_bin"]`` carries the configuration's bin count for the
collectives' readers, and the ``info: setup_spans`` line is printed in
every run (no metric of the cell's lists asks for it).
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from .. import setup_spans
from ..spec import SpecError
from . import train

# what a chip holds a row beyond its four 128-byte matrix copies (the
# matrix, its twin, and the grow program's two outputs while the inputs
# are donated): gradients, hessians, row ids, scores, the packed
# gradient rows and the leaf parts; read off criteo-7m-train's
# memory_peak_bytes, 4.66 GB at 7 M rows less the copies and the cache
# (the ledger)
ROW_EXTRA_BYTES = 146


def reckoned_chip_bytes(rows_local: int, features: int, leaves: int,
                        bins: int = 256) -> int:
    """The fullest chip's device memory at the peak, reckoned from the
    one-chip cell of the same table: four copies of a 128-byte matrix
    row and ``ROW_EXTRA_BYTES`` a row, plus the per-leaf histogram cache
    ``[leaves, features, bins, 3]`` float32."""
    from lightgbm_tpu.ops.hist_pallas import matrix_cols
    per_row = 4 * matrix_cols(features) + ROW_EXTRA_BYTES
    return rows_local * per_row + leaves * features * bins * 3 * 4


def _probe_booster(lgb, params, features: int, seed: int, shift: float,
                   rows: int):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, features)).astype(np.float32) + shift
    y = (x[:, 0] - shift > 0).astype(np.float32)
    return lgb.Booster(dict(params), lgb.Dataset(x, label=y,
                                                 params=dict(params)))


def _require_table_free_block(lgb, tel, params, features: int,
                              expect: Dict[str, Any], rows: int) -> None:
    """Two tables of one shape lower the mesh fused block to one text,
    and the second grows its tree compiling nothing new."""
    import jax
    probes = [_probe_booster(lgb, params, features, seed, shift, rows)
              for seed, shift in ((0, 0.0), (1, 1.0))]
    texts = []
    for bst in probes:
        g = bst._gbdt
        ln = g.learner
        if type(ln).__name__ != expect["learner"] \
                or int(getattr(ln, "num_shards", 1)) != expect["num_shards"]:
            raise SpecError(
                f"the probe's tables train on {type(ln).__name__} over "
                f"{getattr(ln, 'num_shards', 1)} shards, not on "
                f"{expect['learner']} over {expect['num_shards']}")
        texts.append(g._fused_block().trace(
            *g._fused_block_args(), m=1).lower().as_text())
    if texts[0] != texts[1]:
        raise SpecError(
            "two tables of one shape lower this program's mesh fused "
            "block to different texts: it holds something of its table "
            "as a constant, so every seed of the cell would compile it "
            "anew")
    for i, bst in enumerate(probes):
        g = bst._gbdt
        seen = {id(r) for r in tel.records}
        out = g._fused_block()(*g._fused_block_args(), m=1)
        jax.block_until_ready(out)
        trees = out[4]
        if int(np.asarray(trees.num_leaves).ravel()[0]) <= 1:
            raise SpecError("the probe grew no split through the mesh "
                            "fused block")
        missed = [r.get("program") for r in list(tel.records)
                  if id(r) not in seen and r.get("kind") == "compile"
                  and r.get("stage") == "backend"
                  and r.get("cache") == "miss"]
        if i == 1 and missed:
            raise SpecError(f"the second probe table compiled {missed} "
                            "anew: its programs are not the first's")


def _grown_tree(tree):
    """The program's host tree as ``tree_check_numpy.GrownTree``."""
    from ..reference.tree_check_numpy import GrownTree
    return GrownTree(
        feature=np.asarray(tree.split_feature_inner, np.int64),
        threshold=np.asarray(tree.threshold_bin, np.int64),
        left=np.asarray(tree.left_child, np.int64),
        right=np.asarray(tree.right_child, np.int64),
        gain=np.asarray(tree.split_gain, np.float64),
        internal_count=np.asarray(tree.internal_count),
        internal_weight=np.asarray(tree.internal_weight, np.float64),
        internal_value=np.asarray(tree.internal_value, np.float64),
        leaf_count=np.asarray(tree.leaf_count),
        leaf_weight=np.asarray(tree.leaf_weight, np.float64),
        leaf_value=np.asarray(tree.leaf_value, np.float64),
        shrinkage=float(tree.shrinkage))


def _check_first_tree(ds, params, check, first: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """Check (a) at the timed size on the tree ``first`` names."""
    import ml_dtypes

    from ..reference import tree_check_numpy
    t0 = time.perf_counter()
    inner = ds._inner
    if inner.feature_group is not None:
        raise SpecError("check (a) reads one column a feature; this "
                        "table was bundled")
    tree = first["gbdt"].models[first["index"]]
    grown = _grown_tree(tree)
    grad, hess = tree_check_numpy.gradients(first["scores"],
                                            inner.metadata.label)
    kw = dict(levels=int(check["search_levels"]),
              gain_rtol=float(check["split_gain_rtol"]),
              gain_median_rtol=float(check["split_gain_median_rtol"]))
    out = tree_check_numpy.check_tree(
        inner.binned, inner.num_bins_array(), grad, hess, grown, params,
        **kw)
    # the reading in the nearest precision below the configuration's:
    # the same splits, every gradient and hessian rounded to bfloat16
    # (routing and sums alone, no search)
    low = tree_check_numpy.check_tree(
        inner.binned, inner.num_bins_array(), grad, hess, grown, params,
        quantize=lambda a: a.astype(ml_dtypes.bfloat16).astype(
            np.float64), **dict(kw, levels=-1))
    out.update(tree_index=first["index"],
               bfloat16_gain_err_median=low["gain_err_median"],
               bfloat16_gain_err_max=low["gain_err_max"],
               bfloat16_grad_err_max=low["grad_err_max"],
               bfloat16_hess_err_max=low["hess_err_max"],
               seconds=round(time.perf_counter() - t0, 2))
    return out


def run(ctx) -> Dict[str, Any]:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability.telemetry import get_telemetry

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    params = dict(cfg["params"], **mix.get("params", {}))
    rows = int(mix["rows"])
    tel = get_telemetry()
    tel.ensure_ring()               # counters and records, no sink
    t0 = time.perf_counter()
    _require_table_free_block(lgb, tel, params, int(cfg["features"]),
                              mix["expect"], int(mix["probe_rows"]))
    ctx.info("probe", rows=int(mix["probe_rows"]),
             seconds=round(time.perf_counter() - t0, 2))
    first: Dict[str, Any] = {}

    def booster(*args, **kwargs):
        bst = plain["Booster"](*args, **kwargs)
        g = bst._gbdt
        if g.num_data == rows:
            # the cell's booster: keep the scores the warm-up step's
            # first tree is grown from, fetched just before that step
            g_train = g.train

            def train_first(num_iterations=None):
                if not first and g.iter >= 1:
                    first.update(gbdt=g, index=len(g.models),
                                 scores=np.asarray(g.train_score[:, 0]))
                return g_train(num_iterations)
            g.train = train_first
        return bst

    def checked(lgb, ds, params, check):
        tree = _check_first_tree(ds, params, check, first)
        ctx.info("check_tree", **tree)
        out = plain["_check_against_reference"](lgb, ds, params, check)
        out["ok"] = bool(out["ok"] and tree["ok"])
        return out

    # kinds/train.py's run, whole, with this module's check (a) where it
    # looks its own up and the cell's booster watched: that file is the
    # accepted benchmark's and has no argument for either
    plain = {"Booster": lgb.Booster,
             "_check_against_reference": train._check_against_reference}
    lgb.Booster = booster
    train._check_against_reference = checked
    try:
        obs = train.run(ctx)
    finally:
        lgb.Booster = plain["Booster"]
        train._check_against_reference = plain["_check_against_reference"]
    obs["facts"]["max_bin"] = int(cfg["max_bin"])
    # set-up by layer on its info line (``setup_spans``), which no
    # metric of this cell's lists prints: whether every compile of a
    # new seed came from the cache
    setup_spans.by_layer(dict(obs["facts"], setup_s=ctx.setup_s))
    return obs
