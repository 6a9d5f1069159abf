"""Communication recipes for the leaf-wise grow loop.

Reference analog: the parallel tree learners
(``src/treelearner/{feature,data,voting}_parallel_tree_learner.cpp``)
layered over the hand-rolled ``Network`` collectives (``src/network/``).
On TPU the whole Network layer is replaced by XLA mesh collectives
inside ``shard_map``; what remains of each parallel algorithm is a
RECIPE of hooks injected into ONE shared grow loop
(``learner/serial.py:grow_tree`` / ``learner/partitioned.py``), with
the array placement owned by the partition-rule layer
(``parallel/partition_rules.py``).

The collective budget is a CONTRACT: graftcheck GC401 pins the exact
per-program multiset (``tools/graftcheck/contracts.json``), so every
recipe below states its count. The collapse levers:

* **packed winner gather** — a shard's best-split candidate is ONE
  f32 buffer (ints/bitsets bitcast, bit patterns preserved), so the
  winner exchange is ONE ``all_gather`` instead of a tree-map gather
  per SplitResult field (the old feature-parallel cost: ~10 gathers
  per select, 30 per split).
* **pair batching** — both fresh children's selects run under
  ``jax.vmap`` (``vmap_safe=True``); XLA batches the collective, so a
  split pays ONE gather (and, for voting, one psum) for both children.
* **reduce-scatter histograms (data-parallel)** — the per-split child
  histogram is ``psum_scatter``'d over the (permuted) group axis and
  each shard scans ITS slice of the globally-reduced histogram — the
  reference's ReduceScatter + SyncUpGlobalBestSplit shape
  (data_parallel_tree_learner.cpp:149-164) instead of a full-histogram
  all-reduce followed by a redundant replicated scan.
* **packed root reduce** — the root histogram and the root (g, h, c)
  sums ride ONE psum (concatenated), not two.

Per-mode collective multisets (whole compiled grow program):

  data     {all-reduce: 1, reduce-scatter: 1, all-gather: 1}  (was 3ar)
  feature  {all-gather: 2}                                    (was 30ag)
  voting   {all-gather: 2, all-reduce: 3}                     (was 6ag+4ar)

Every hook returns values REPLICATED across mesh devices so the grow
loop's control flow stays identical everywhere; only row partitioning
and histogram work are sharded.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..observability import scopes
from ..ops.split import (MAX_CAT_WORDS, FeatureMeta, SplitParams,
                         SplitResult, _argmax_first, assemble_split,
                         best_split, per_feature_splits)


# what one chip sends of a collective's payload, as a multiple of it,
# in a ring over d chips: a reduce-scatter passes on (d-1)/d of its
# input, an all-reduce is a reduce-scatter and an all-gather of the
# result, an all-gather passes its own piece d-1 times
_RING_SENT = {"psum_scatter": lambda d: (d - 1) / d,
              "psum": lambda d: 2 * (d - 1) / d,
              "all_gather": lambda d: d - 1}


def _count_collective(name: str, tree, axis: Optional[str] = None):
    """Telemetry seam: add the payload bytes of a collective to counter
    ``comm.<name>_bytes``, what a chip sends of it in a ring over the
    mesh ``axis`` to ``comm.<name>_sent_bytes`` (+ ``comm.<name>_calls``)
    and return the payload unchanged. The comm hooks run inside jitted
    grow programs, so this executes at TRACE time over abstract values
    — the counters record bytes per compiled-program invocation
    (grow-loop collectives execute once per while-loop step at
    runtime), with zero cost inside the program.
    ``tools/run_report.py`` renders the counters as the per-op comms
    table."""
    from ..observability.telemetry import get_telemetry, traced_bytes
    tel = get_telemetry()
    if tel.enabled:
        nbytes = traced_bytes(tree)
        tel.count(f"comm.{name}_bytes", nbytes)
        if axis is not None:
            tel.count(f"comm.{name}_sent_bytes",
                      nbytes * _RING_SENT[name](jax.lax.axis_size(axis)))
        tel.count(f"comm.{name}_calls", 1)
    return tree


def _psum(x, axis: str, scope: str):
    with jax.named_scope(scope):
        return jax.lax.psum(_count_collective("psum", x, axis), axis)


def _bitcast_f32(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _bitcast_i32(x):
    return jax.lax.bitcast_convert_type(
        jnp.asarray(x, jnp.float32), jnp.int32)


class Comm(NamedTuple):
    """Static strategy object (functions close over mesh axis names).

    ``reduce_hist``/``select_split`` define the PER-SPLIT path: the
    child histogram reduce (which may change layout — data-parallel
    returns the shard's reduce-scattered slice) and the best-split
    scan over that layout. ``reduce_root``/``select_root``/``to_scan``
    define the ROOT path where it differs: data-parallel reduces the
    full root histogram once (packed with the root sums), scans it
    replicated, and ``to_scan`` slices it into the per-split cache
    layout. ``None`` fields fall back to the per-split hooks."""
    reduce_hist: Callable
    reduce_sums: Callable
    select_split: Callable
    # True when select_split may run under jax.vmap over both fresh
    # children: XLA batches any inner collective into ONE op, so the
    # pair costs one gather. Set on every recipe whose select is
    # batching-safe (all of the below).
    vmap_safe: bool = False
    # True when the histogram handed to select_split is shard-LOCAL
    # (voting keeps hists local until the winners' psum). The grow
    # loop's EFB debundle must then reconstruct most-freq-bin counts
    # from LOCAL leaf totals, not the globally reduced g/h/c
    local_hist: bool = False
    # root-path overrides (None -> derive from the per-split hooks)
    reduce_root: Optional[Callable] = None   # (hist, sums) -> (hist, sums)
    select_root: Optional[Callable] = None
    to_scan: Optional[Callable] = None       # root hist -> cache layout


def _serial_select(hist, g, h, c, meta, params, cmin, cmax, fmask,
                   rand_bins=None):
    return best_split(hist, g, h, c, meta, params,
                      constraint_min=cmin, constraint_max=cmax,
                      feature_mask=fmask, rand_bins=rand_bins)


SERIAL_COMM = Comm(reduce_hist=lambda x: x, reduce_sums=lambda x: x,
                   select_split=_serial_select, vmap_safe=True)


# ---------------------------------------------------------------------
# packed SplitResult exchange: ONE i32 buffer per candidate. The
# carrier is the integer type (floats bitcast), like the grow-loop
# StatePack (learner/split_step.py): integer moves keep every bit
# pattern, while an f32 carrier loses small and negative ints on the
# TPU (denormal flush, NaN canonicalization).
_PACK_WORDS = 10 + MAX_CAT_WORDS


def pack_split(res: SplitResult) -> jnp.ndarray:
    """SplitResult -> i32[10 + MAX_CAT_WORDS]. Floats and the bitset
    are bitcast (value bits preserved exactly); bools ride as 0/1."""
    scal = jnp.stack([
        _bitcast_i32(res.gain),
        res.feature.astype(jnp.int32),
        res.threshold.astype(jnp.int32),
        res.default_left.astype(jnp.int32),
        _bitcast_i32(res.left_g), _bitcast_i32(res.left_h),
        _bitcast_i32(res.left_c), _bitcast_i32(res.left_output),
        _bitcast_i32(res.right_output),
        res.is_cat.astype(jnp.int32)])
    bits = jax.lax.bitcast_convert_type(res.cat_bitset, jnp.int32)
    return jnp.concatenate([scal, bits])


def unpack_split(row: jnp.ndarray) -> SplitResult:
    return SplitResult(
        gain=_bitcast_f32(row[0]),
        feature=row[1],
        threshold=row[2],
        default_left=row[3] > 0,
        left_g=_bitcast_f32(row[4]), left_h=_bitcast_f32(row[5]),
        left_c=_bitcast_f32(row[6]),
        left_output=_bitcast_f32(row[7]),
        right_output=_bitcast_f32(row[8]),
        is_cat=row[9] > 0,
        cat_bitset=jax.lax.bitcast_convert_type(row[10:], jnp.uint32))


def gather_best_split(res: SplitResult, axis: str,
                      scope: str = scopes.SPLITS_COLLECTIVE
                      ) -> SplitResult:
    """The SyncUpGlobalBestSplit exchange
    (parallel_tree_learner.h:190-213) as ONE packed all_gather:
    max gain wins, ties broken by LOWER global feature id so
    equal-gain splits match serial's first-index rule even when
    bundled group blocks scramble the shard<->feature-id order."""
    packed = pack_split(res)
    with jax.named_scope(scope):
        rows = jax.lax.all_gather(
            _count_collective("all_gather", packed, axis), axis)
    gains = _bitcast_f32(rows[:, 0])
    feats = rows[:, 1]
    best = jnp.max(gains)
    tied = jnp.where(gains >= best, feats, jnp.iinfo(jnp.int32).max)
    return unpack_split(rows[jnp.argmin(tied)])


def make_sharded_select(axis: str, scope: str = scopes.SPLITS_COLLECTIVE):
    """Best-split select over a column-sharded scan axis: local scan
    of the shard's slice (``meta_local.global_id`` maps the local slot
    back to the global feature) + the packed winner gather (under
    ``scope``). Shared by the feature-parallel learner (locally-built
    sharded histograms) and the data-parallel reduce-scatter recipe
    (slices of the globally-reduced histogram)."""

    def select(hist, g, h, c, meta_local, params, cmin, cmax, fmask,
               rand_bins=None):
        pf = per_feature_splits(hist, g, h, c, meta_local, params,
                                cmin, cmax, fmask, rand_bins)
        lb = _argmax_first(pf.score).astype(jnp.int32)
        res = assemble_split(pf, lb,
                             feature_id=meta_local.global_id[lb])
        return gather_best_split(res, axis, scope)

    return select


# ---------------------------------------------------------------------
def make_data_parallel_comm(axis: str, plan=None) -> Comm:
    """Data-parallel (data_parallel_tree_learner.cpp semantics).

    With ``plan`` (a ``partition_rules.FeatureShardPlan``): the
    reduce-scatter recipe — per-split child histograms are permuted to
    shard-slice order and ``psum_scatter``'d (each shard receives the
    globally-reduced histograms of ITS groups), scanned locally
    against ``plan.meta_local``, and the winner is exchanged via the
    packed gather. The root histogram is psum'ed ONCE (packed with the
    root sums), scanned replicated, and ``to_scan`` slices it into the
    cache layout. 3 collectives per program: {ar:1, rs:1, ag:1}.

    Without ``plan``: the legacy replicated recipe — full-histogram
    psum + redundant replicated select. Kept for the configs whose
    bookkeeping needs a replicated global-feature histogram (CEGB's
    candidate cache, forced splits reading the leaf histogram cache).
    """
    if plan is None:
        return Comm(
            reduce_hist=lambda x: _psum(x, axis,
                                        scopes.SPLITS_COLLECTIVE),
            reduce_sums=lambda x: _psum(x, axis, scopes.ROOT_COLLECTIVE),
            select_split=_serial_select, vmap_safe=True)

    g_local = plan.g_local

    def reduce_hist(hist):
        hp = plan.permute_hist(hist)
        with jax.named_scope(scopes.SPLITS_COLLECTIVE):
            return jax.lax.psum_scatter(
                _count_collective("psum_scatter", hp, axis), axis,
                scatter_dimension=0, tiled=True)

    def reduce_root(hist, sums):
        flat = jnp.concatenate([hist.reshape(-1), sums])
        flat = _psum(flat, axis, scopes.ROOT_COLLECTIVE)
        return flat[:-3].reshape(hist.shape), flat[-3:]

    def to_scan(hist_full):
        hp = plan.permute_hist(hist_full)
        idx = jax.lax.axis_index(axis)
        return jax.lax.dynamic_slice_in_dim(
            hp, idx * g_local, g_local, axis=0)

    return Comm(
        reduce_hist=reduce_hist,
        reduce_sums=lambda x: _psum(x, axis, scopes.ROOT_COLLECTIVE),
        select_split=make_sharded_select(axis), vmap_safe=True,
        reduce_root=reduce_root, select_root=_serial_select,
        to_scan=to_scan)


def make_feature_parallel_comm(axis: str) -> Comm:
    """Every device holds all rows but scans only its feature shard
    (whole EFB bundle groups; ``meta_local.global_id`` maps the local
    scan slot back to the global feature); winners are compared via
    the packed single-buffer gather (the Allreduce of SplitInfo,
    parallel_tree_learner.h:190-213). 2 collectives per program: the
    root select's gather + the vmapped pair's batched gather."""
    return Comm(reduce_hist=lambda x: x, reduce_sums=lambda x: x,
                select_split=make_sharded_select(axis), vmap_safe=True,
                select_root=make_sharded_select(
                    axis, scopes.ROOT_COLLECTIVE))


def make_voting_parallel_comm(axis: str, num_machines: int, top_k: int,
                              params_local: SplitParams) -> Comm:
    """PV-Tree (arxiv 1611.01276; voting_parallel_tree_learner.cpp).
    Per leaf: local per-feature scan (with min_data / min_hessian
    divided by num_machines, :57-59) -> local top-k -> ONE packed
    all_gather of (weighted gain, feature id) pairs (the 2*top_k
    LightSplitInfo exchange) -> GlobalVoting by gain weighted with
    local leaf count / mean count (:152-183) -> psum of ONLY the
    winning features' histogram columns (CopyLocalHistogram +
    ReduceScatter, :186-242,344 — O(top_k) not O(F)) -> full-parameter
    scan on the aggregated columns -> replicated winner.

    5 collectives per program: root sums psum + (gather, psum) at the
    root select + ONE batched (gather, psum) for the vmapped child
    pair."""

    def select(hist_local, g, h, c, meta, params, cmin, cmax, fmask,
               rand_bins=None, scope=scopes.SPLITS_COLLECTIVE):
        f = hist_local.shape[0]
        k = min(top_k, f)
        # local leaf totals (every feature's bins sum to the leaf)
        loc = hist_local[0].sum(axis=0)
        pf = per_feature_splits(hist_local, loc[0], loc[1], loc[2],
                                meta, params_local, cmin, cmax, fmask,
                                rand_bins)
        top_gain, top_ids = jax.lax.top_k(pf.score, k)
        # weighted gain: local leaf count relative to the mean shard count
        mean_cnt = c / num_machines
        w_gain = jnp.where(jnp.isfinite(top_gain),
                           top_gain * loc[2] / jnp.maximum(mean_cnt, 1.0),
                           -jnp.inf)
        # ONE packed gather for the whole vote: [2k] = gains ++ ids
        buf = jnp.concatenate([_bitcast_i32(w_gain),
                               top_ids.astype(jnp.int32)])
        with jax.named_scope(scope):
            rows = jax.lax.all_gather(
                _count_collective("all_gather", buf, axis), axis)
        all_gain = _bitcast_f32(rows[:, :k]).reshape(-1)
        all_ids = rows[:, k:].reshape(-1)
        # per-feature max weighted gain over all candidates, then top-k
        feat_gain = jnp.full((f,), -jnp.inf).at[all_ids].max(
            jnp.where(jnp.isfinite(all_gain), all_gain, -jnp.inf))
        _, win_ids = jax.lax.top_k(feat_gain, k)
        # aggregate only the winning columns across the data shards
        hist_sel = _psum(hist_local[win_ids], axis, scope)
        meta_sel = FeatureMeta(*[m[win_ids] for m in meta])
        fmask_sel = None if fmask is None else fmask[win_ids]
        rb_sel = None if rand_bins is None else rand_bins[win_ids]
        pf_glob = per_feature_splits(hist_sel, g, h, c, meta_sel,
                                     params, cmin, cmax, fmask_sel,
                                     rb_sel)
        b = _argmax_first(pf_glob.score).astype(jnp.int32)
        return assemble_split(pf_glob, b, feature_id=win_ids[b])

    return Comm(reduce_hist=lambda x: x,
                reduce_sums=lambda x: _psum(x, axis,
                                            scopes.ROOT_COLLECTIVE),
                select_split=select, vmap_safe=True, local_hist=True,
                select_root=functools.partial(
                    select, scope=scopes.ROOT_COLLECTIVE))


# ---------------------------------------------------------------------
class ShardScanCtx(NamedTuple):
    """Per-shard scan context the grow loops use for the PER-SPLIT
    scans when the scan axis is column-sharded but the histogram build
    is not (the data-parallel reduce-scatter recipe): the permuted
    local meta, the shard's slice of the feature mask, the
    shard-folded RNG key pair and the shard's slice of the by-node
    feature budget. ``None`` ctx -> per-split scans reuse the root
    scan's (global) context."""
    meta: FeatureMeta
    fmask: jnp.ndarray
    rand_key: Optional[jnp.ndarray]
    bynode_count: object        # traced int (uneven budget split)
    bynode_cap: int             # static cap for the top_k draw


def comm_root_hooks(comm: Comm):
    """(reduce_root, select_root, to_scan) with the per-split hooks as
    fallbacks — one definition for both grow loops."""
    reduce_root = comm.reduce_root or (
        lambda hh, ss: (comm.reduce_hist(hh), comm.reduce_sums(ss)))
    select_root = comm.select_root or comm.select_split
    to_scan = comm.to_scan or (lambda hh: hh)
    return reduce_root, select_root, to_scan
