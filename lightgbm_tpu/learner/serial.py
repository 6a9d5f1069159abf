"""Serial (single-device) leaf-wise tree learner.

Reference analog: ``SerialTreeLearner``
(``src/treelearner/serial_tree_learner.cpp:29-782``). The whole
``num_leaves-1`` grow loop compiles to ONE XLA program
(``lax.while_loop``): per step it
  * picks the open leaf with the best cached split gain
    (``Train`` serial_tree_learner.cpp:145-192),
  * applies the split to the ``leaf_id[N]`` vector (index-free partition,
    replacing DataPartition::Split),
  * builds the histogram of the SMALLER child only and derives the larger
    sibling by subtraction (the smaller/larger-leaf trick,
    serial_tree_learner.cpp:434-436),
  * runs the vectorized best-split scan for both children and caches the
    results per leaf.

All state (leaf_id, histogram cache, per-leaf sums and split candidates,
tree arrays) stays on device; the host only launches one fused program per
tree. The histogram cache holds every open leaf (the reference's
HistogramPool LRU exists to bound host RAM; HBM capacity makes a full
cache the right TPU default).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.binning import (BIN_TYPE_CATEGORICAL, MISSING_NAN, MISSING_NONE,
                            MISSING_ZERO)
from ..data.dataset import Dataset
from ..models.linear import LinearLeafFitMixin
from ..models.tree import Tree, TreeArrays
from ..observability import scopes
from ..observability.telemetry import get_telemetry
from ..utils.jit_registry import register_jit
from ..ops.histogram import build_histogram, make_ghc
from ..ops.partition import split_leaf
from ..ops.split import (MAX_CAT_WORDS, MISSING_NAN_CODE, MISSING_NONE_CODE,
                         MISSING_ZERO_CODE, FeatureMeta, SplitParams,
                         _argmax_first, assemble_split, child_columns,
                         child_constraints, make_scan_leaf,
                         order_child_pair, per_feature_splits,
                         scan_split_pair, set_bitsets,
                         split_node_updates)
from .split_step import (SplitStepPlan, StatePack, make_grow_pack,
                         plan_split_step, split_fusion_default)

_MISSING_CODE = {MISSING_NONE: MISSING_NONE_CODE,
                 MISSING_ZERO: MISSING_ZERO_CODE,
                 MISSING_NAN: MISSING_NAN_CODE}

kEps = 1e-15


def dataset_any_missing(dataset: Dataset) -> bool:
    """Static gate for SplitParams.any_missing: True when any feature's
    bin mapper recorded a missing-value convention (two-scan split
    search needed)."""
    return any(dataset.feature_mapper(i).missing_type != MISSING_NONE
               for i in range(dataset.num_features))


def dataset_has_monotone(dataset: Dataset) -> bool:
    """Static gate for the grow loops' monotone-bound carry: when no
    feature carries a monotone constraint the per-leaf cmin/cmax stay
    ±inf forever, so the fused split step drops them from the carry and
    compiles the propagation out."""
    return bool(dataset.monotone_types) \
        and any(int(t) != 0 for t in dataset.monotone_types)


def feature_meta_from_dataset(dataset: Dataset,
                              config: Config) -> FeatureMeta:
    """Build the static per-feature metadata arrays."""
    f = dataset.num_features
    num_bins = dataset.num_bins_array()
    missing = np.asarray(
        [_MISSING_CODE[dataset.feature_mapper(i).missing_type]
         for i in range(f)], np.int32)
    default_bin = np.asarray(
        [dataset.feature_mapper(i).default_bin for i in range(f)], np.int32)
    most_freq = np.asarray(
        [dataset.feature_mapper(i).most_freq_bin for i in range(f)],
        np.int32)
    is_cat = np.asarray(
        [dataset.feature_mapper(i).bin_type == BIN_TYPE_CATEGORICAL
         for i in range(f)], bool)
    monotone = np.asarray(dataset.monotone_types, np.int32) \
        if dataset.monotone_types else np.zeros(f, np.int32)
    penalty = np.asarray(dataset.feature_penalty, np.float32) \
        if dataset.feature_penalty else np.ones(f, np.float32)
    group, offset, _ = dataset.bundle_maps()
    coupled_cfg = list(config.cegb_penalty_feature_coupled)
    if coupled_cfg and len(coupled_cfg) != dataset.num_total_features:
        from ..utils.log import log_fatal
        log_fatal("cegb_penalty_feature_coupled should be the same size "
                  f"as feature number ({len(coupled_cfg)} vs "
                  f"{dataset.num_total_features})")
    lazy_cfg = list(config.cegb_penalty_feature_lazy)
    if lazy_cfg and len(lazy_cfg) != dataset.num_total_features:
        from ..utils.log import log_fatal
        log_fatal("cegb_penalty_feature_lazy should be the same size "
                  f"as feature number ({len(lazy_cfg)} vs "
                  f"{dataset.num_total_features})")
    cegb_coupled = np.zeros(f, np.float32)
    cegb_lazy = np.zeros(f, np.float32)
    for inner, orig in enumerate(dataset.real_feature_idx):
        if orig < len(coupled_cfg):
            cegb_coupled[inner] = float(coupled_cfg[orig])
        if orig < len(lazy_cfg):
            cegb_lazy[inner] = float(lazy_cfg[orig])
    return FeatureMeta(
        num_bins=jnp.asarray(num_bins), missing=jnp.asarray(missing),
        default_bin=jnp.asarray(default_bin),
        most_freq_bin=jnp.asarray(most_freq),
        monotone=jnp.asarray(monotone), penalty=jnp.asarray(penalty),
        is_categorical=jnp.asarray(is_cat),
        group=jnp.asarray(np.asarray(group, np.int32)),
        offset=jnp.asarray(np.asarray(offset, np.int32)),
        cegb_coupled_penalty=jnp.asarray(cegb_coupled),
        cegb_lazy_penalty=jnp.asarray(cegb_lazy),
        global_id=jnp.arange(f, dtype=jnp.int32))


def build_forced_plan(dataset: Dataset, config: Config) -> tuple:
    """Parse forcedsplits_filename into a STATIC unrollable plan.

    Reference analog: ``SerialTreeLearner::ForceSplits``
    (serial_tree_learner.cpp:465-634). The reference walks the JSON in
    BFS order at runtime; since leaf ids are assigned deterministically
    (the i-th split creates leaf i+1), the whole traversal is resolved
    here at trace time: each entry is
    ``(leaf, feature_inner, threshold_bin, default_left, missing_code,
    default_bin, num_bin)`` — all static ints — with ``threshold_bin``
    chosen so that ``bin <= threshold_bin`` goes left exactly when
    ``bin < ValueToBin(threshold)``, matching
    GatherInfoForThresholdNumerical's right-accumulates-``>=`` loop.
    NaN-missing features send missing left there (the NaN bin is
    excluded from the right sweep), hence default_left; the missing
    metadata lets forced_left_sums route the NaN / zero-default bins
    the same way the partition does. A threshold below all data
    (ValueToBin == 0: empty left side) aborts the rest of the plan like
    the reference's empty-gather abort.
    """
    fn = config.forcedsplits_filename
    if not fn:
        return ()
    import json as _json
    from collections import deque

    from ..data.binning import BIN_TYPE_CATEGORICAL, MISSING_NAN
    from ..utils.log import log_warning
    with open(fn) as f:
        root = _json.load(f)
    num_leaves = int(config.num_leaves)
    plan = []
    q = deque([(root, 0)])
    k = 1
    while q and k < num_leaves:
        node, leaf = q.popleft()
        if not node:
            continue
        feat_real = int(node["feature"])
        thr = float(node["threshold"])
        try:
            inner = dataset.inner_feature_index(feat_real)
        except IndexError:
            inner = -1
        if inner is None or inner < 0:
            log_warning(f"forced split on unused feature {feat_real} "
                        "ignored; aborting remaining forced splits")
            break
        mapper = dataset.feature_mapper(inner)
        if mapper.bin_type == BIN_TYPE_CATEGORICAL:
            log_warning("forced splits on categorical features are not "
                        "supported; aborting remaining forced splits")
            break
        tbin = int(np.asarray(
            mapper.values_to_bins(np.asarray([thr], np.float64)))[0])
        if tbin == 0:
            log_warning(
                f"forced split threshold {thr} on feature {feat_real} "
                "is below all data (empty left side); aborting "
                "remaining forced splits")
            break
        tbin -= 1  # left = bin < ValueToBin(threshold)
        plan.append((leaf, int(inner), tbin,
                     mapper.missing_type == MISSING_NAN,
                     _MISSING_CODE[mapper.missing_type],
                     int(mapper.default_bin), int(mapper.num_bin)))
        if node.get("left"):
            q.append((node["left"], leaf))
        if node.get("right"):
            q.append((node["right"], k))
        k += 1
    return tuple(plan)


def forced_left_sums(hist_leaf, st, forced, meta_scan, bundled: bool):
    """Left sums of a STATIC forced split read off the leaf's
    histogram (``hist_leaf`` — cached or rebuilt on demand in pool-
    bounded mode) — the GatherInfoForThreshold analog. Missing bins are
    routed exactly like the partition routes the rows: NaN bin
    (num_bin-1) by default_left, zero-missing default bin right."""
    fleaf, ffeat, fthr, fdleft, fmiss, fdbin, fnbin = forced
    if bundled:
        from ..ops.histogram import debundle_hist
        pg0, ph0, pc0 = (st["leaf_g"][fleaf], st["leaf_h"][fleaf],
                         st["leaf_c"][fleaf])
        hist_leaf = debundle_hist(hist_leaf, meta_scan.group,
                                  meta_scan.offset, meta_scan.num_bins,
                                  pg0, ph0, pc0)
    cum = hist_leaf[ffeat, :fthr + 1].sum(axis=0)
    if fmiss == MISSING_NAN_CODE and fdleft and fnbin - 1 > fthr:
        cum = cum + hist_leaf[ffeat, fnbin - 1]  # NaN rows go left
    if fmiss == MISSING_ZERO_CODE and not fdleft and fdbin <= fthr:
        cum = cum - hist_leaf[ffeat, fdbin]  # default bin goes right
    return cum[0], cum[1], cum[2]


def forced_split_override(hist_leaf, st, forced, params: SplitParams,
                          meta_scan, bundled: bool):
    """All split-site quantities of a static forced split, shared by
    the serial and partitioned grow bodies: returns
    (leaf, feat, thr, dleft, gain, is_cat, bitset,
     lg, lh, lc, pg, ph, pc, rg, rh, rc, lout, rout)."""
    from ..ops.split import (gain_given_output, leaf_output,
                             leaf_split_gain)
    fleaf, ffeat, fthr, fdleft = forced[:4]
    leaf = jnp.int32(fleaf)
    feat = jnp.int32(ffeat)
    thr = jnp.int32(fthr)
    dleft = jnp.bool_(fdleft)
    is_cat = jnp.bool_(False)
    bitset = jnp.zeros((MAX_CAT_WORDS,), jnp.uint32)
    lg, lh, lc = forced_left_sums(hist_leaf, st, forced, meta_scan,
                                  bundled)
    pg, ph, pc = (st["leaf_g"][leaf], st["leaf_h"][leaf],
                  st["leaf_c"][leaf])
    rg, rh, rc = pg - lg, ph - lh, pc - lc
    cmin0 = st["leaf_cmin"][leaf]
    cmax0 = st["leaf_cmax"][leaf]
    lh_e = lh + kEps
    rh_e = ph + 2 * kEps - lh_e
    lout = leaf_output(lg, lh_e, params.lambda_l1, params.lambda_l2,
                       params.max_delta_step, cmin0, cmax0)
    rout = leaf_output(rg, rh_e, params.lambda_l1, params.lambda_l2,
                       params.max_delta_step, cmin0, cmax0)
    shift = leaf_split_gain(pg, ph + 2 * kEps, params.lambda_l1,
                            params.lambda_l2, params.max_delta_step)
    gain = (gain_given_output(lg, lh_e, lout, params.lambda_l1,
                              params.lambda_l2)
            + gain_given_output(rg, rh_e, rout, params.lambda_l1,
                                params.lambda_l2)
            - shift - params.min_gain_to_split)
    return (leaf, feat, thr, dleft, gain, is_cat, bitset,
            lg, lh, lc, pg, ph, pc, rg, rh, rc, lout, rout)


def use_hist_cache(config: Config, num_leaves: int, f: int,
                   b: int) -> bool:
    """histogram_pool_size (MB) semantics (config.h:244, HistogramPool
    serial_tree_learner.cpp:313-353): cache per-leaf histograms only if
    the full [num_leaves, F, B, 3] f32 cache fits the budget; otherwise
    the grow loops run pool-bounded. <= 0 means unlimited, like the
    reference default. (One source of truth: hist_pool_slots.)"""
    return hist_pool_slots(config, num_leaves, f, b) >= num_leaves


def hist_pool_slots(config: Config, num_leaves: int, f: int,
                    b: int) -> int:
    """Slot count for the partitioned learner's bounded LRU histogram
    pool (HistogramPool, serial_tree_learner.cpp:313-353): the full
    [num_leaves, F, B, 3] cache when it fits histogram_pool_size MB
    (<= 0 = unlimited, the reference default), else as many whole
    slots as fit (>= 2 needed for parent+sibling), else 0 =
    rebuild-both-children-on-demand."""
    pool = float(config.histogram_pool_size)
    if pool <= 0:
        return num_leaves
    slots = int(pool * 1024 * 1024 // (f * b * 3 * 4))
    if slots >= num_leaves:
        return num_leaves
    return slots if slots >= 2 else 0


def split_params_from_config(config: Config) -> SplitParams:
    coupled = list(config.cegb_penalty_feature_coupled)
    lazy = list(config.cegb_penalty_feature_lazy)
    lazy_on = float(config.cegb_tradeoff) > 0.0 \
        and any(float(c) > 0.0 for c in lazy)
    cegb_on = lazy_on or (float(config.cegb_tradeoff) > 0.0 and (
        float(config.cegb_penalty_split) > 0.0
        or any(float(c) > 0.0 for c in coupled)))
    return SplitParams(
        cegb_on=cegb_on,
        cegb_lazy_on=lazy_on,
        cegb_tradeoff=float(config.cegb_tradeoff),
        cegb_penalty_split=float(config.cegb_penalty_split),
        lambda_l1=float(config.lambda_l1),
        lambda_l2=float(config.lambda_l2),
        max_delta_step=float(config.max_delta_step),
        min_data_in_leaf=float(config.min_data_in_leaf),
        min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
        min_gain_to_split=float(config.min_gain_to_split),
        max_cat_threshold=int(config.max_cat_threshold),
        cat_l2=float(config.cat_l2),
        cat_smooth=float(config.cat_smooth),
        max_cat_to_onehot=int(config.max_cat_to_onehot),
        min_data_per_group=float(config.min_data_per_group))


class GrowResult(NamedTuple):
    tree: TreeArrays
    leaf_id: object  # i32 [N]
    # CEGB lazy-penalty charged state [N, F] bool (None unless
    # cegb_penalty_feature_lazy is active; persists on the learner)
    cegb_charged: object = None


def bynode_feature_count(num_features: int, feature_fraction: float,
                         ff_bynode: float) -> int:
    """Features sampled per node, matching GetUsedFeatures
    (serial_tree_learner.cpp:226-275): ``round(used * ff_bynode)`` where
    ``used`` is the per-TREE subset size, floored at min(2, valid)."""
    used = num_features if feature_fraction >= 1.0 \
        else max(1, int(round(num_features * feature_fraction)))
    min_used = min(2, used)
    return max(min_used, int(round(used * ff_bynode)))


class NodeRandMixin:
    """Shared per-tree RNG state for extra-trees / by-node sampling —
    one definition so the serial, partitioned and mesh learners derive
    identical key streams."""

    def _init_node_rand(self, dataset: Dataset, config: Config) -> None:
        self.extra_trees = bool(config.extra_trees)
        self.ff_bynode = float(config.feature_fraction_bynode)
        self._extra_rng = np.random.RandomState(config.extra_seed)
        self._bynode_rng = np.random.RandomState(
            config.feature_fraction_seed)
        self.bynode_count = bynode_feature_count(
            dataset.num_features, float(config.feature_fraction),
            self.ff_bynode)
        self.forced_plan = build_forced_plan(dataset, config)

    def next_tree_key(self):
        """Fresh per-tree PRNG key pair for extra-trees (extra_seed
        stream) and by-node feature sampling (feature_fraction_seed
        stream); None when neither feature is on, keeping the no-RNG
        compile."""
        if not (self.extra_trees or self.ff_bynode < 1.0):
            return None
        return jnp.stack([
            jax.random.PRNGKey(self._extra_rng.randint(0, 2**31 - 1)),
            jax.random.PRNGKey(self._bynode_rng.randint(0, 2**31 - 1))])


def make_node_rand(rand_keys, feature_mask, bynode_count, num_bins,
                   extra_trees: bool, ff_bynode: float,
                   bynode_cap: int | None = None):
    """Per-node randomness for the grow loop, shared by the serial and
    partitioned learners.

    ``rand_keys`` is a stacked pair of PRNG keys — [0] drives the
    extra-trees thresholds (seeded from Config.extra_seed), [1] the
    by-node column sample (seeded from Config.feature_fraction_seed) —
    two independent streams exactly like the reference's ``rand_`` in
    FeatureHistogram vs ``random_`` in SerialTreeLearner.

    Returns ``node_rand(salt) -> (rand_bins, node_mask)``:
      * ``rand_bins`` [F] — extra-trees random candidate threshold per
        feature, uniform on [0, num_bin-3] (feature_histogram.hpp:98-101
        NextInt(0, num_bin-2) is half-open), or None;
      * ``node_mask`` [F] bool — ``bynode_count`` features drawn from
        WITHIN the per-tree ``feature_mask`` subset (already ANDed), or
        None when by-node sampling is off.
    ``bynode_count`` may be a TRACED int (feature-parallel shards split
    the global budget unevenly); ``bynode_cap`` must then be the static
    maximum (top_k needs a static k). ``salt`` must be a distinct
    traced int per scan call so every node draws fresh randomness
    inside one compiled program.
    """
    use = (extra_trees or ff_bynode < 1.0) and rand_keys is not None
    if not use:
        return lambda salt: (None, None)
    f = num_bins.shape[0]
    cap = bynode_cap if bynode_cap is not None else int(bynode_count)
    cap = min(max(cap, 1), f)

    def node_rand(salt):
        rb = None
        if extra_trees:
            kk = jax.random.fold_in(rand_keys[0], salt)
            u = jax.random.uniform(kk, (f,))
            span = jnp.maximum(num_bins - 2, 1).astype(jnp.float32)
            rb = jnp.floor(u * span).astype(jnp.int32)
        nm = None
        if ff_bynode < 1.0:
            kk2 = jax.random.fold_in(rand_keys[1], salt)
            u2 = jax.random.uniform(kk2, (f,))
            u2 = jnp.where(feature_mask, u2, -1.0)  # only tree subset
            vals = jax.lax.top_k(u2, cap)[0]
            cnt = jnp.clip(jnp.asarray(bynode_count, jnp.int32), 0, cap)
            kth = jnp.where(cnt > 0, vals[jnp.maximum(cnt - 1, 0)],
                            jnp.float32(2.0))  # cnt=0 -> empty mask
            nm = (u2 >= kth) & feature_mask
        return rb, nm

    return node_rand


_PF_FIELDS = (("pf_score", "score"), ("pf_thr", "threshold"),
              ("pf_lg", "left_g"), ("pf_lh", "left_h"),
              ("pf_lc", "left_c"), ("pf_dleft", "default_left"),
              ("pf_lout", "left_output"), ("pf_rout", "right_output"),
              ("pf_iscat", "is_cat"), ("pf_bitset", "cat_bitset"))


def cegb_pf_state(big_l: int, f: int) -> dict:
    """Per-(leaf, feature) RAW candidate cache — the reference's
    ``splits_per_leaf_`` (cost_effective_gradient_boosting.hpp:35,114).
    The cached gains are UNpenalized (DetlaGain receives split_info by
    value before the caller subtracts the delta,
    serial_tree_learner.cpp:767-776), so a coupled-penalty refund can
    upgrade OTHER leaves' cached best splits with raw+coupled gains
    (UpdateLeafBestSplits, :63-80).

    Divergence from the reference: rows reset to -inf at every tree
    start; the reference never clears ``splits_per_leaf_``, letting
    stale candidates from earlier trees leak into refund upgrades."""
    return dict(
        pf_score=jnp.full((big_l, f), -jnp.inf, jnp.float32),
        pf_thr=jnp.zeros((big_l, f), jnp.int32),
        pf_lg=jnp.zeros((big_l, f), jnp.float32),
        pf_lh=jnp.zeros((big_l, f), jnp.float32),
        pf_lc=jnp.zeros((big_l, f), jnp.float32),
        pf_dleft=jnp.zeros((big_l, f), bool),
        pf_lout=jnp.zeros((big_l, f), jnp.float32),
        pf_rout=jnp.zeros((big_l, f), jnp.float32),
        pf_iscat=jnp.zeros((big_l, f), bool),
        pf_bitset=jnp.zeros((big_l, f, MAX_CAT_WORDS), jnp.uint32),
        leaf_blocked=jnp.zeros((big_l,), bool),
    )


def cegb_store_row(st: dict, row, pf, blocked) -> None:
    for key, attr in _PF_FIELDS:
        st[key] = st[key].at[row].set(getattr(pf, attr))
    st["leaf_blocked"] = st["leaf_blocked"].at[row].set(blocked)


def cegb_refund(st: dict, feat, was_used, meta, params) -> None:
    """On FIRST acquisition of ``feat``, add the coupled penalty back
    to every leaf's cached candidate on that feature
    (UpdateLeafBestSplits, cost_effective_gradient_boosting.hpp:63-80).
    Must run BEFORE the fresh children's rows are stored — their scans
    already saw the feature as acquired."""
    refund = jnp.where(was_used, 0.0,
                       jnp.float32(params.cegb_tradeoff)
                       * meta.cegb_coupled_penalty[feat])
    col = st["pf_score"][:, feat]
    st["pf_score"] = st["pf_score"].at[:, feat].set(
        jnp.where(jnp.isfinite(col), col + refund, col))


def cegb_upgrade_best(st: dict, feat, was_used, leaf, new,
                      big_l: int) -> None:
    """On FIRST acquisition of ``feat``, replace another leaf's cached
    best with its (refunded) raw+coupled candidate on ``feat`` where
    that candidate wins (UpdateLeafBestSplits,
    cost_effective_gradient_boosting.hpp:67-78). Upgrade-only — the
    reference compares the single refunded candidate against the
    current best and never downgrades; the two fresh children are
    excluded (``i == best_leaf`` skip + the new leaf's reset gain)."""
    rows = jnp.arange(big_l)
    cand = st["pf_score"][:, feat]
    # SplitInfo::operator> (split_info.hpp:126-152): higher gain wins,
    # exact ties go to the SMALLER feature id
    beats = (cand > st["bs_gain"]) | (
        (cand == st["bs_gain"]) & (feat < st["bs_feat"]))
    do = (~was_used) & (rows != leaf) & (rows != new) \
        & ~st["leaf_blocked"] & jnp.isfinite(st["bs_gain"]) \
        & jnp.isfinite(cand) & beats
    pick2 = (("bs_thr", "pf_thr"), ("bs_dleft", "pf_dleft"),
             ("bs_lg", "pf_lg"), ("bs_lh", "pf_lh"),
             ("bs_lc", "pf_lc"), ("bs_lout", "pf_lout"),
             ("bs_rout", "pf_rout"), ("bs_iscat", "pf_iscat"))
    st["bs_gain"] = jnp.where(do, cand, st["bs_gain"])
    st["bs_feat"] = jnp.where(do, feat, st["bs_feat"])
    for bs_key, pf_key in pick2:
        st[bs_key] = jnp.where(do, st[pf_key][:, feat], st[bs_key])
    st["bs_bitset"] = jnp.where(do[:, None], st["pf_bitset"][:, feat],
                                st["bs_bitset"])


class CegbStateMixin:
    """Cross-tree CEGB feature-acquisition state: the coupled penalty
    applies until a feature's FIRST use anywhere in the model
    (CostEfficientGradientBoosting::UpdateUsedFeature); the used set
    persists across iterations on the learner."""

    def _init_cegb(self) -> None:
        self._cegb_used = (
            jnp.zeros((self.dataset.num_features,), bool)
            if self.params.cegb_on else None)
        self._cegb_charged = (
            jnp.zeros((self.dataset.num_data,
                       self.dataset.num_features), bool)
            if self.params.cegb_lazy_on else None)

    def _drop_cegb_lazy(self, why: str) -> None:
        if self.params.cegb_lazy_on:
            from ..utils.log import log_warning
            log_warning("cegb_penalty_feature_lazy is only supported by "
                        f"the serial tree learner ({why}); ignoring the "
                        "lazy penalty")
            # recompute the master gate: lazy may have been the ONLY
            # penalty — don't run zero-delta CEGB machinery
            coupled = list(self.config.cegb_penalty_feature_coupled)
            still_on = float(self.config.cegb_tradeoff) > 0.0 and (
                float(self.config.cegb_penalty_split) > 0.0
                or any(float(c) > 0.0 for c in coupled))
            self.params = self.params._replace(cegb_lazy_on=False,
                                               cegb_on=still_on)
            self._cegb_charged = None
            if not still_on:
                self._cegb_used = None

    def _drop_cegb(self) -> None:
        """CEGB's cross-split feature-used state is indexed by global
        feature id; the feature-sharded mesh learners scan local
        shards, so penalties are not supported on the mesh learners
        (the reference ties CEGB to the serial learner too)."""
        if self.params.cegb_on:
            from ..utils.log import log_warning
            log_warning("cegb_* penalties are not supported by parallel "
                        "tree learners; ignoring them")
            self.params = self.params._replace(cegb_on=False,
                                               cegb_lazy_on=False)
            self._cegb_used = None
            self._cegb_charged = None

    def _cegb_after_tree(self, result: "GrowResult") -> None:
        if getattr(self, "_cegb_used", None) is None:
            return
        ta = result.tree
        valid = jnp.arange(ta.split_feature.shape[0]) \
            < (ta.num_leaves - 1)
        upd = jnp.zeros_like(self._cegb_used) \
            .at[ta.split_feature].max(valid)
        self._cegb_used = self._cegb_used | upd


def count_tree_telemetry(learner) -> None:
    """Per-tree learner counters (observability/telemetry.py): tree
    and row totals plus the PLANNED histogram-build count — the grow
    loop is one fused device program, so the build count is derived
    from its static shape (1 root + 1 per split with the sibling
    subtraction, 2 per split in pool-bounded mode; an early stop can
    only make the true count lower). Shared by every learner's
    ``train`` entry point; free when telemetry is disabled."""
    tel = get_telemetry()
    if not tel.enabled:
        return
    n = learner.dataset.num_data
    big_l = learner.num_leaves
    cache = getattr(learner, "cache_hists", True)
    # the grow call is ONE fused device program = one dispatch
    tel.count_iter("host.dispatches")
    tel.count("learner.trees", 1)
    tel.count("learner.rows_scanned", n)
    tel.count("learner.hist_builds_planned",
              1 + (big_l - 1) * (1 if cache else 2))
    tel.count("learner.splits_planned", big_l - 1)
    shards = getattr(learner, "num_shards", 1)
    if shards > 1:
        tel.gauge("mesh.num_shards", shards)


class SerialTreeLearner(NodeRandMixin, CegbStateMixin,
                        LinearLeafFitMixin):
    """Owns the device copy of the dataset and the compiled grow
    program. ``LinearLeafFitMixin`` adds the post-grow leaf-linear
    ridge fit over the grow loop's device-resident ``leaf_id`` (the
    ``linear_tree`` subsystem, models/linear.py)."""

    _count_tree_telemetry = count_tree_telemetry
    # mesh subclasses flip this off and place the matrix through the
    # sharded ingest layer instead (parallel/ingest.py)
    _stage_binned_on_device = True

    def __init__(self, dataset: Dataset, config: Config,
                 hist_method: str = "auto"):
        self.dataset = dataset
        self.config = config
        self._init_node_rand(dataset, config)
        self.meta = feature_meta_from_dataset(dataset, config)
        has_cat = any(
            dataset.feature_mapper(i).bin_type == BIN_TYPE_CATEGORICAL
            for i in range(dataset.num_features))
        self.params = split_params_from_config(config)._replace(
            has_categorical=has_cat,
            any_missing=dataset_any_missing(dataset))
        # the mesh learners defer device placement to the sharded
        # ingest path (parallel/ingest.py): a plain jnp.asarray here
        # would stage the FULL matrix on the default device before the
        # re-shard — exactly the replicated host-0 copy the ingest
        # layer exists to avoid
        if self._stage_binned_on_device:
            with get_telemetry().setup_span(
                    scopes.SETUP_DEVICE_TABLE,
                    bytes=dataset.binned.nbytes):
                self.binned = jnp.asarray(dataset.binned)
        else:
            self.binned = dataset.binned
        # multi-val pseudo-groups (no physical column; bundling.py)
        self.mv_slots = dataset.mv_slots_device
        self.mv_groups = dataset.num_groups - dataset.num_dense_groups
        _, _, group_bins = dataset.bundle_maps()
        self.num_bins_max = max(
            int(dataset.num_bins_array().max(initial=2)),
            int(np.asarray(group_bins).max(initial=2)))
        self.bundled = dataset.feature_offset is not None
        self.num_leaves = int(config.num_leaves)
        self.max_depth = int(config.max_depth)
        self.hist_method = hist_method
        self.has_monotone = dataset_has_monotone(dataset)
        self.cache_hists = use_hist_cache(
            config, self.num_leaves, dataset.num_groups,
            self.num_bins_max)
        # Pallas split scan on compiled backends (see
        # learner/partitioned.py for the rationale; scans are
        # collective-free in every comm, so the mesh learners built on
        # this base get it too)
        self.params = self.params._replace(
            use_scan_kernel=self.split_plan().scan_kernel)
        self._init_cegb()
        # no-sampling defaults, built ONCE (see PartitionedTreeLearner)
        self._ones_rows = jnp.ones((dataset.num_data,), jnp.float32)
        self._all_features = jnp.ones((dataset.num_features,), bool)

    def split_plan(self) -> SplitStepPlan:
        """Which split step this learner's grow program runs
        (learner/split_step.py ``plan_split_step``). The leaf_id layout
        has no megakernel, so the body is always the per-phase one and
        ``grow_tree`` takes no plan; ``fused_split_kernel=on`` raises
        here, when the learner is built."""
        return plan_split_step(
            mode=self.config.fused_split_kernel, params=self.params,
            bundled=self.bundled, num_bins_max=self.num_bins_max,
            num_leaves=self.num_leaves,
            num_features=self.dataset.num_groups,
            forced_plan=self.forced_plan,
            extra_trees=self.extra_trees, ff_bynode=self.ff_bynode,
            cache_hists=self.cache_hists, mv_groups=self.mv_groups,
            has_megakernel=False)

    def train(self, grad: jnp.ndarray, hess: jnp.ndarray,
              bag_weight: Optional[jnp.ndarray] = None,
              feature_mask: Optional[jnp.ndarray] = None) -> GrowResult:
        if bag_weight is None:
            bag_weight = self._ones_rows
        if feature_mask is None:
            feature_mask = self._all_features
        self._count_tree_telemetry()
        # module-level jit: learners with equal shapes/params share the
        # compiled executable (tests and per-class trainers hit the cache)
        res = _grow_jit(self.binned, grad, hess, bag_weight, feature_mask,
                        self.meta, rand_key=self.next_tree_key(),
                        cegb_used0=getattr(self, "_cegb_used", None),
                        cegb_charged0=getattr(self, "_cegb_charged",
                                              None),
                        params=self.params,
                        num_leaves=self.num_leaves,
                        max_depth=self.max_depth,
                        num_bins_max=self.num_bins_max,
                        hist_method=self.hist_method,
                        bundled=self.bundled,
                        extra_trees=self.extra_trees,
                        ff_bynode=self.ff_bynode,
                        bynode_count=self.bynode_count,
                        forced_plan=self.forced_plan,
                        cache_hists=self.cache_hists,
                        mv_slots=self.mv_slots,
                        mv_groups=self.mv_groups,
                        has_monotone=self.has_monotone,
                        split_fusion=split_fusion_default())
        self._cegb_after_tree(res)
        if res.cegb_charged is not None:
            self._cegb_charged = res.cegb_charged
        return res

    def to_host_tree(self, result: GrowResult,
                     shrinkage: float = 1.0) -> Tree:
        tree = Tree(jax.device_get(result.tree), dataset=self.dataset)
        if shrinkage != 1.0:
            tree.shrink(shrinkage)
        return tree


# registered under TWO contract names: the default config (CEGB off —
# no donation can materialize) and the lazy-CEGB config whose charged
# matrix the jit site donates (graftcheck proves the alias holds)
@register_jit("serial_grow_cegb", donate=("cegb_charged0",))
@register_jit("serial_grow")
@functools.partial(
    jax.jit, static_argnames=("params", "num_leaves", "max_depth",
                              "num_bins_max", "hist_method", "bundled",
                              "extra_trees", "ff_bynode", "bynode_count",
                              "forced_plan", "cache_hists", "mv_groups",
                              "has_monotone", "split_fusion"),
    # the CEGB lazy charged matrix [N, F] is replaced by the grow
    # result every tree — the input buffer is dead the moment the
    # program launches, so donate it (the largest state array a CEGB
    # config carries)
    donate_argnames=("cegb_charged0",))
def _grow_jit(binned, grad, hess, bag_weight, feature_mask, meta,
              rand_key=None, cegb_used0=None, cegb_charged0=None,
              mv_slots=None, *,
              params, num_leaves, max_depth, num_bins_max, hist_method,
              bundled=False, extra_trees=False, ff_bynode=1.0,
              bynode_count=2, forced_plan=(), cache_hists=True,
              mv_groups=0, has_monotone=True, split_fusion=True):
    return grow_tree(binned, grad, hess, bag_weight, feature_mask,
                     meta=meta, params=params, num_leaves=num_leaves,
                     max_depth=max_depth, num_bins_max=num_bins_max,
                     hist_method=hist_method, bundled=bundled,
                     rand_key=rand_key, extra_trees=extra_trees,
                     ff_bynode=ff_bynode, bynode_count=bynode_count,
                     forced_plan=forced_plan, cache_hists=cache_hists,
                     cegb_used0=cegb_used0, cegb_charged0=cegb_charged0,
                     mv_slots=mv_slots, mv_groups=mv_groups,
                     has_monotone=has_monotone,
                     split_fusion=split_fusion)


def grow_tree(binned, grad, hess, bag_weight, feature_mask, *,
              meta: FeatureMeta, params: SplitParams, num_leaves: int,
              max_depth: int, num_bins_max: int, hist_method: str,
              comm=None, binned_hist=None, meta_hist=None,
              bundled: bool = False, rand_key=None,
              extra_trees: bool = False, ff_bynode: float = 1.0,
              bynode_count=2, bynode_cap: int | None = None,
              forced_plan: tuple = (), cache_hists: bool = True,
              cegb_used0=None, cegb_charged0=None,
              mv_slots=None, mv_groups: int = 0,
              has_monotone: bool = True,
              split_fusion: bool | None = None,
              body_scan=None) -> GrowResult:
    """One full leaf-wise tree; jit-compiled once per shape.

    ``comm`` injects the parallel-learner collectives (learner/comm.py);
    ``binned_hist``/``meta_hist`` override the histogram-build inputs for
    feature-parallel mode (feature-sharded) while ``binned``/``meta``
    stay global for row partitioning and the tree arrays.
    ``body_scan`` (a ``learner/comm.py:ShardScanCtx``) switches the
    PER-SPLIT scans onto a column-sharded local context (permuted
    meta, local feature mask, shard-folded RNG) while the root scan
    keeps the global one — the data-parallel reduce-scatter recipe,
    where the root histogram is reduced replicated but every per-split
    histogram arrives as the shard's reduce-scattered slice.

    ``cache_hists=False`` is the pool-bounded mode (the reference's
    ``histogram_pool_size`` LRU, serial_tree_learner.cpp:313-353,
    taken to its TPU-shaped limit): no [num_leaves, F, B, 3] HBM cache
    — each split rebuilds BOTH children's histograms directly instead
    of deriving the sibling by subtraction. Costs one extra histogram
    pass per split, bounds grow-loop HBM by O(F*B) regardless of
    num_leaves.

    ``split_fusion`` selects the per-split state packing
    (learner/split_step.py): fused (merged single-scatter state, slim
    carry) or the r05 legacy layout — bit-identical models either way.
    """
    if comm is None:
        from .comm import SERIAL_COMM
        comm = SERIAL_COMM
    if split_fusion is None:
        split_fusion = split_fusion_default()
    if binned_hist is None:
        binned_hist = binned
    if meta_hist is None:
        meta_hist = meta
    n = binned.shape[0]
    big_l = num_leaves
    b = num_bins_max

    def full_hist(ghc_arr):
        """Dense-group histograms + multi-val pseudo-group histograms
        concatenated on the group axis (one [G_total, B, 3] tensor —
        the cache/subtraction/debundle machinery is layout-blind)."""
        h = build_histogram(binned_hist, ghc_arr, b, method=hist_method)
        if mv_groups:
            from ..ops.histogram import multival_hist
            h = jnp.concatenate(
                [h, multival_hist(mv_slots, ghc_arr, mv_groups, b)],
                axis=0)
        return h

    from .comm import comm_root_hooks
    reduce_root, select_root, to_scan = comm_root_hooks(comm)
    ghc = make_ghc(grad, hess, bag_weight)
    # ONE packed collective where the recipe supports it (the root
    # histogram and the root sums ride the same psum — learner/comm.py)
    root_hist, root_sums = reduce_root(full_hist(ghc),
                                       ghc.sum(axis=0))
    root_g, root_h, root_c = root_sums[0], root_sums[1], root_sums[2]
    # per-split scan/cache layout of the root histogram (identity for
    # every recipe except data-parallel's reduce-scatter slice)
    hist0 = to_scan(root_hist)

    inf = jnp.float32(jnp.inf)
    # static per-trace packing of the grow-loop carry
    # (learner/split_step.py): fused = merged single-scatter state +
    # slim carry; legacy = the r05 split-matrix layout
    pack = make_grow_pack(merged=split_fusion,
                          has_cat=params.has_categorical,
                          has_monotone=has_monotone, big_l=big_l)
    # the scan's feature axis is LOGICAL features (EFB hists debundle
    # before select_split), so draws span meta_hist's length, not the
    # physical group count
    node_rand = make_node_rand(rand_key, feature_mask, bynode_count,
                               meta_hist.num_bins, extra_trees, ff_bynode,
                               bynode_cap=bynode_cap)

    f_logical = meta_hist.num_bins.shape[0]
    if params.cegb_on and cegb_used0 is None:
        cegb_used0 = jnp.zeros((f_logical,), bool)
    used_rows = jnp.ones((n,), bool) if bag_weight is None \
        else bag_weight > 0
    if params.cegb_lazy_on and cegb_charged0 is None:
        cegb_charged0 = jnp.zeros((n, f_logical), bool)

    def lazy_uncharged(charged, mask):
        """Per-feature count of leaf rows not yet charged for the
        feature (CalculateOndemandCosts loop)."""
        m = mask.astype(jnp.float32)
        return m.sum() - (charged.astype(jnp.float32)
                          * m[:, None]).sum(axis=0)

    # shared scan-leaf composition (ops/split.py — the partitioned
    # learner and its megakernel's interpret twin call the SAME maker,
    # which keeps the paths bit-identical). The root and per-split scans
    # may differ in layout: the root scans ``root_hist`` with the
    # global meta (and the recipe's select_root), per-split scans use
    # the ``body_scan`` shard context when the comm reduces child
    # histograms into a column-sharded slice.
    scan_root = make_scan_leaf(comm, meta_hist, params, feature_mask,
                               node_rand, bundled, max_depth,
                               select=select_root)
    if body_scan is None:
        scan_body = make_scan_leaf(comm, meta_hist, params,
                                   feature_mask, node_rand, bundled,
                                   max_depth)
    else:
        node_rand_body = make_node_rand(
            body_scan.rand_key, body_scan.fmask,
            body_scan.bynode_count, body_scan.meta.num_bins,
            extra_trees, ff_bynode, bynode_cap=body_scan.bynode_cap)
        scan_body = make_scan_leaf(comm, body_scan.meta, params,
                                   body_scan.fmask, node_rand_body,
                                   bundled, max_depth)

    def scan_leaf_pf(hist, g, h, c, depth, cmin, cmax, salt, cegb_used,
                     uncharged=None):
        """CEGB path: the full per-feature candidate row is kept for
        the refund bookkeeping (splits_per_leaf_). The leaf's own best
        is picked from PENALIZED scores, but the cached row keeps the
        RAW gains (DetlaGain stores split_info pre-subtraction). Only
        the serial / data-parallel comms reach here (their select IS
        the local argmax over the reduced histogram)."""
        if bundled:
            from ..ops.histogram import debundle_leaf_hist
            hist = debundle_leaf_hist(hist, meta_hist, g, h, c,
                                      comm.local_hist)
        rb, nm = node_rand(salt)
        fm = feature_mask if nm is None else nm
        pf, raw = per_feature_splits(hist, g, h, c, meta_hist, params,
                                     cmin, cmax, fm, rb,
                                     cegb_used=cegb_used,
                                     cegb_uncharged=uncharged,
                                     return_raw=True)
        res = assemble_split(pf, _argmax_first(pf.score).astype(
            jnp.int32))
        blocked = (max_depth > 0) & (depth >= max_depth)
        return (res._replace(gain=jnp.where(blocked, -jnp.inf,
                                            res.gain)),
                pf._replace(score=raw), blocked)

    if params.cegb_on:
        unch_root = lazy_uncharged(cegb_charged0, used_rows) \
            if params.cegb_lazy_on else None
        root_split, root_pf, root_blocked = scan_leaf_pf(
            root_hist, root_g, root_h, root_c, jnp.int32(0), -inf, inf,
            jnp.int32(0), cegb_used0, unch_root)
    else:
        root_split = scan_root(root_hist, root_g, root_h, root_c,
                               jnp.int32(0), -inf, inf, jnp.int32(0))

    def at0(arr, val):
        return arr.at[0].set(val)

    from ..ops.split import leaf_output_no_constraint
    root_out = leaf_output_no_constraint(
        root_g, root_h + 2e-15, params.lambda_l1, params.lambda_l2,
        params.max_delta_step)

    fields = dict(
        leaf_g=at0(jnp.zeros((big_l,), jnp.float32), root_g),
        leaf_h=at0(jnp.zeros((big_l,), jnp.float32), root_h),
        leaf_c=at0(jnp.zeros((big_l,), jnp.float32), root_c),
        # cached best split per open leaf
        bs_gain=at0(jnp.full((big_l,), -jnp.inf), root_split.gain),
        bs_feat=at0(jnp.zeros((big_l,), jnp.int32), root_split.feature),
        bs_thr=at0(jnp.zeros((big_l,), jnp.int32), root_split.threshold),
        bs_dleft=at0(jnp.zeros((big_l,), bool), root_split.default_left),
        bs_lg=at0(jnp.zeros((big_l,), jnp.float32), root_split.left_g),
        bs_lh=at0(jnp.zeros((big_l,), jnp.float32), root_split.left_h),
        bs_lc=at0(jnp.zeros((big_l,), jnp.float32), root_split.left_c),
        bs_lout=at0(jnp.zeros((big_l,), jnp.float32),
                    root_split.left_output),
        bs_rout=at0(jnp.zeros((big_l,), jnp.float32),
                    root_split.right_output),
        bs_iscat=at0(jnp.zeros((big_l,), bool), root_split.is_cat),
        # pointer-fixing bookkeeping: which node references each leaf
        ref_node=jnp.full((big_l,), -1, jnp.int32),
        ref_side=jnp.zeros((big_l,), jnp.int32),
        # per-leaf monotone output bounds (LeafConstraints,
        # monotone_constraints.hpp:32-66)
        leaf_cmin=jnp.full((big_l,), -jnp.inf, jnp.float32),
        leaf_cmax=jnp.full((big_l,), jnp.inf, jnp.float32),
        # tree arrays
        split_feature=jnp.zeros((big_l - 1,), jnp.int32),
        threshold_bin=jnp.zeros((big_l - 1,), jnp.int32),
        decision_type=jnp.zeros((big_l - 1,), jnp.int32),
        left_child=jnp.zeros((big_l - 1,), jnp.int32),
        right_child=jnp.zeros((big_l - 1,), jnp.int32),
        split_gain_arr=jnp.zeros((big_l - 1,), jnp.float32),
        internal_value=jnp.zeros((big_l - 1,), jnp.float32),
        internal_weight=jnp.zeros((big_l - 1,), jnp.float32),
        internal_count=jnp.zeros((big_l - 1,), jnp.float32),
        leaf_value=at0(jnp.zeros((big_l,), jnp.float32), root_out),
        leaf_weight=at0(jnp.zeros((big_l,), jnp.float32), root_h),
        leaf_count=at0(jnp.zeros((big_l,), jnp.float32), root_c),
        leaf_parent=jnp.full((big_l,), -1, jnp.int32),
        leaf_depth=jnp.zeros((big_l,), jnp.int32),
    )
    fields.update(
        k=jnp.int32(1),
        leaf_id=jnp.zeros((n,), jnp.int32),
        bs_bitset=at0(jnp.zeros((big_l, MAX_CAT_WORDS), jnp.uint32),
                      root_split.cat_bitset),
        cat_bitsets=jnp.zeros((big_l - 1, MAX_CAT_WORDS), jnp.uint32))
    if cache_hists:
        fields["hist"] = at0(
            jnp.zeros((big_l,) + hist0.shape, jnp.float32), hist0)
    if params.cegb_on:
        fields["cegb_used"] = cegb_used0
        fields.update(cegb_pf_state(big_l, f_logical))
        cegb_store_row(fields, 0, root_pf, root_blocked)
        if params.cegb_lazy_on:
            fields["cegb_charged"] = cegb_charged0
    state = pack.pack(fields)

    leaf_range = jnp.arange(big_l)

    def leaf_hist_masked(v, leaf):
        """Pool-bounded mode: rebuild one leaf's histogram on demand."""
        ghc_leaf = ghc * (v["leaf_id"] == leaf).astype(
            jnp.float32)[:, None]
        return comm.reduce_hist(full_hist(ghc_leaf))

    def cond(st):
        bs_gain = pack.row_f(st, "bs_gain")
        open_gain = jnp.where(leaf_range < st["k"], bs_gain, -jnp.inf)
        # best gain <= 0 stops training (serial_tree_learner.cpp Train;
        # equivalent to the old isfinite check for unpenalized gains,
        # which are strictly positive when valid)
        return (st["k"] < big_l) & (open_gain.max() > 0.0)

    def body(st_packed, forced=None, forced_hist=None):
        st = pack.view(st_packed)  # row views, folded by XLA
        k = st["k"]
        new = k
        s = k - 1  # internal node index for this split

        if forced is None:
            open_gain = jnp.where(leaf_range < k, st["bs_gain"],
                                  -jnp.inf)
            leaf = jnp.argmax(open_gain).astype(jnp.int32)
            # ONE column slice replaces ~22 per-field scalar reads
            site = pack.read_site(st_packed, leaf)
            feat = site["bs_feat"]
            thr = site["bs_thr"]
            dleft = site["bs_dleft"]
            gain = site["bs_gain"]
            is_cat = site["bs_iscat"]
            bitset = st["bs_bitset"][leaf]
            lg, lh, lc = site["bs_lg"], site["bs_lh"], site["bs_lc"]
            pg, ph, pc = site["leaf_g"], site["leaf_h"], site["leaf_c"]
            rg, rh, rc = pg - lg, ph - lh, pc - lc
            lout, rout = site["bs_lout"], site["bs_rout"]
        else:
            fh = forced_hist if forced_hist is not None \
                else st["hist"][forced[0]] if cache_hists \
                else leaf_hist_masked(st, forced[0])
            (leaf, feat, thr, dleft, gain, is_cat, bitset,
             lg, lh, lc, pg, ph, pc, rg, rh, rc, lout, rout) = \
                forced_split_override(fh, st, forced, params, meta_hist,
                                      bundled)
            site = pack.read_site(st_packed, leaf)
        # monotone bounds drop out of the carry (and the site read)
        # when no feature has a monotone constraint
        pcmin = site.get("leaf_cmin", -inf)
        pcmax = site.get("leaf_cmax", inf)

        # ---- partition rows of `leaf` ---------------------------------
        grp = meta.group[feat]
        if mv_groups:
            g_dense = binned.shape[1]

            def _mv_bins(_):
                from ..data.bundling import MV_SLOT_STRIDE
                from ..ops.histogram import multival_feature_bins
                base = (grp - g_dense) * MV_SLOT_STRIDE \
                    + meta.offset[feat]
                return multival_feature_bins(
                    mv_slots, base, meta.num_bins[feat]).astype(jnp.int32)

            def _dense_bins(_):
                from ..data.bundling import decode_feature_bin
                col = jnp.take(binned, jnp.clip(grp, 0, g_dense - 1),
                               axis=1).astype(jnp.int32)
                return decode_feature_bin(col, meta.offset[feat],
                                          meta.num_bins[feat]) \
                    .astype(jnp.int32)

            bin_col = jax.lax.cond(grp >= g_dense, _mv_bins,
                                   _dense_bins, None)
        else:
            bin_col = jnp.take(binned, meta.group[feat], axis=1)
            if bundled:
                from ..data.bundling import decode_feature_bin
                bin_col = decode_feature_bin(
                    bin_col.astype(jnp.int32), meta.offset[feat],
                    meta.num_bins[feat]).astype(bin_col.dtype)
        leaf_id = split_leaf(
            st["leaf_id"], bin_col, leaf, new, thr, dleft,
            meta.missing[feat], meta.default_bin[feat],
            meta.num_bins[feat], is_cat, bitset)

        # ---- tree arrays (split_node_updates, shared with the
        # partitioned learner and its megakernel) ----------------------
        pside = site["ref_side"]
        depth = site["leaf_depth"] + 1
        treef, treei, pnode, upd = split_node_updates(
            params, gain, feat, thr, dleft, is_cat, pg, ph, pc,
            site["ref_node"], leaf, new)

        # ---- histograms: smaller child built, sibling by subtraction
        # (pool-bounded mode: no parent cache -> build both directly).
        # The fused path carries the pair in (smaller, other) order —
        # the state/hist writes key on the child's leaf index, so the
        # two [F, B, 3] left/right reorder selects vanish ------------
        if cache_hists:
            parent_hist = st["hist"][leaf]
            small_is_left = lc <= rc
            sm = jnp.where(small_is_left, leaf, new)
            ghc_small = ghc * (leaf_id == sm).astype(
                jnp.float32)[:, None]
            hist_small = comm.reduce_hist(full_hist(ghc_small))
            hist_other = parent_hist - hist_small
            if params.cegb_on:
                hist_left = jnp.where(small_is_left, hist_small,
                                      hist_other)
                hist_right = jnp.where(small_is_left, hist_other,
                                       hist_small)
        else:
            st_after = dict(st, leaf_id=leaf_id)
            hist_left = leaf_hist_masked(st_after, leaf)
            hist_right = leaf_hist_masked(st_after, new)

        # ---- monotone constraint propagation -------------------------
        # (LeafConstraints::UpdateConstraints monotone_constraints.hpp:44;
        # compiled out when no feature has a monotone constraint)
        cmin_l, cmax_l, cmin_r, cmax_r = child_constraints(
            meta, feat, is_cat, lout, rout, pcmin, pcmax, has_monotone)

        # ---- child best splits ---------------------------------------
        # CEGB: the feature just split is "acquired" for the children's
        # scans and every later split (OnSplit marking)
        if params.cegb_on:
            cu = st["cegb_used"].at[feat].set(True)
            unch_l = unch_r = None
            if params.cegb_lazy_on:
                # charge the PARENT leaf's rows for the split feature
                # (UpdateLeafBestSplits runs before the partition)
                m_parent = (st["leaf_id"] == leaf) & used_rows
                charged2 = st["cegb_charged"].at[:, feat].set(
                    st["cegb_charged"][:, feat] | m_parent)
                unch_l = lazy_uncharged(
                    charged2, (leaf_id == leaf) & used_rows)
                unch_r = lazy_uncharged(
                    charged2, (leaf_id == new) & used_rows)
            split_a, pf_l, blk_l = scan_leaf_pf(
                hist_left, lg, lh, lc, depth, cmin_l, cmax_l,
                2 * k + 1, cu, unch_l)
            split_b, pf_r, blk_r = scan_leaf_pf(
                hist_right, rg, rh, rc, depth, cmin_r, cmax_r,
                2 * k + 2, cu, unch_r)
            idx_a, idx_b = leaf, new
            hist_a, hist_b = hist_left, hist_right
            o = order_child_pair(
                jnp.bool_(True), k, lg, lh, lc, rg, rh, rc, lout, rout,
                cmin_l, cmax_l, cmin_r, cmax_r)
        else:
            if cache_hists:
                a_is_left = small_is_left
                idx_a = sm
                idx_b = jnp.where(small_is_left, new, leaf)
                hist_a, hist_b = hist_small, hist_other
            else:
                a_is_left = jnp.bool_(True)
                idx_a, idx_b = leaf, new
                hist_a, hist_b = hist_left, hist_right
            o, split_a, split_b = scan_split_pair(
                comm, scan_body, a_is_left, k, depth, hist_a, hist_b,
                lg, lh, lc, rg, rh, rc, lout, rout,
                cmin_l, cmax_l, cmin_r, cmax_r)

        # ---- packed column writes (learner/split_step.py): fused =
        # one scatter per state/tree matrix; legacy = the r05 writes --
        fa, ia = child_columns(split_a, o["ga"], o["ha"], o["ca"],
                               o["out_a"], o["cmin_a"], o["cmax_a"],
                               s, o["side_a"], depth)
        fb, ib = child_columns(split_b, o["gb"], o["hb"], o["cb"],
                               o["out_b"], o["cmin_b"], o["cmax_b"],
                               s, o["side_b"], depth)
        st2 = {kk: vv for kk, vv in st_packed.items()
               if kk not in StatePack._MATS}
        st2.update(pack.set_state_cols(st_packed, idx_a, idx_b,
                                       fa, fb, ia, ib))
        st2.update(pack.set_tree_col(st_packed, s, treef, treei,
                                     pnode, upd, pside))
        st2.update(k=k + 1, leaf_id=leaf_id)
        st2.update(set_bitsets(pack, st, idx_a, idx_b,
                               split_a.cat_bitset, split_b.cat_bitset,
                               s, bitset))
        if cache_hists:
            st2["hist"] = st["hist"].at[
                jnp.stack([idx_a, idx_b])].set(
                jnp.stack([hist_a, hist_b]))
        if params.cegb_on:
            # shared CEGB helpers mutate whole rows on a view dict;
            # repacking writes them back (refund BEFORE the children's
            # rows land — their scans already saw `feat` acquired)
            vv = pack.view(st2)
            vv["cegb_used"] = cu
            if params.cegb_lazy_on:
                vv["cegb_charged"] = charged2
            cegb_refund(vv, feat, st["cegb_used"][feat], meta_hist,
                        params)
            cegb_store_row(vv, leaf, pf_l, blk_l)
            cegb_store_row(vv, new, pf_r, blk_r)
            cegb_upgrade_best(vv, feat, st["cegb_used"][feat], leaf,
                              new, big_l)
            st2 = pack.pack(vv)
        return st2

    # ---- forced splits: unrolled static pre-pass (ForceSplits,
    # serial_tree_learner.cpp:465-634). Any invalid forced split aborts
    # the REST of the plan (aborted_last_force_split semantics).
    st = state
    force_ok = jnp.bool_(True)
    for step in forced_plan:
        v0 = pack.view(st)
        fh0 = v0["hist"][step[0]] if cache_hists \
            else leaf_hist_masked(v0, step[0])
        lg_f, lh_f, _ = forced_left_sums(fh0, v0, step, meta_hist,
                                         bundled)
        ph_f = v0["leaf_h"][step[0]]
        force_ok = force_ok & (lh_f > kEps) & (ph_f - lh_f > kEps) \
            & (st["k"] < big_l)
        st = jax.lax.cond(
            force_ok,
            functools.partial(body, forced=step, forced_hist=fh0),
            lambda s: s, st)

    st = jax.lax.while_loop(cond, body, st)
    vf = pack.view(st)

    tree = TreeArrays(
        num_leaves=st["k"],
        split_feature=vf["split_feature"],
        threshold_bin=vf["threshold_bin"],
        decision_type=vf["decision_type"],
        left_child=vf["left_child"],
        right_child=vf["right_child"],
        split_gain=vf["split_gain_arr"],
        internal_value=vf["internal_value"],
        internal_weight=vf["internal_weight"],
        internal_count=vf["internal_count"],
        leaf_value=vf["leaf_value"],
        leaf_weight=vf["leaf_weight"],
        leaf_count=vf["leaf_count"],
        leaf_parent=vf["leaf_parent"],
        leaf_depth=vf["leaf_depth"],
        cat_bitsets=vf["cat_bitsets"],
    )
    return GrowResult(tree=tree, leaf_id=st["leaf_id"],
                      cegb_charged=st.get("cegb_charged"))
