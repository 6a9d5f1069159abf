"""Vectorized best-split search over feature histograms.

Reference analog: ``FeatureHistogram::FindBestThreshold*``
(``src/treelearner/feature_histogram.hpp:84-709``). The reference scans
each feature's bins serially in two directions; here both directions for
ALL features are evaluated at once as cumulative-sum tensor ops on
``[F, B]`` grids — a VPU-friendly formulation with no data-dependent
control flow.

Semantics preserved:
  * gain math with L1/L2/max_delta_step (feature_histogram.hpp:492-553);
  * missing handling: two scans when num_bin > 2 and missing != None;
    Zero-missing skips the default bin from partial sums and thresholds;
    NaN-missing excludes the NaN bin from the default-left scan
    (feature_histogram.hpp:103-131, 555-709);
  * min_data_in_leaf / min_sum_hessian_in_leaf validity, kEpsilon seeding;
  * monotone-constraint gain zeroing + output clamping
    (feature_histogram.hpp:507-537);
  * tie-breaking: default-left scan wins ties; within a scan the
    reference's iteration order is reproduced (largest threshold for the
    right-to-left scan, smallest for left-to-right);
  * per-feature gain penalty (feature_contri, feature_histogram.hpp:89).

Categorical split search lives in ``split_categorical.py`` and is merged
by the learner.
"""

from __future__ import annotations

import contextlib

from typing import NamedTuple

import jax
import jax.numpy as jnp

kEpsilon = 1e-15
NEG_INF = -jnp.inf

# missing-type codes (device-side encoding of bin.h:26 MissingType)
MISSING_NONE_CODE = 0
MISSING_ZERO_CODE = 1
MISSING_NAN_CODE = 2


class FeatureMeta(NamedTuple):
    """Static per-feature metadata, all arrays of shape [F]."""
    num_bins: jnp.ndarray      # int32
    missing: jnp.ndarray       # int32 code
    default_bin: jnp.ndarray   # int32
    most_freq_bin: jnp.ndarray  # int32
    monotone: jnp.ndarray      # int32 in {-1, 0, +1}
    penalty: jnp.ndarray       # float32
    is_categorical: jnp.ndarray  # bool
    # EFB bundling maps (data/bundling.py): physical matrix column of
    # each feature and its value offset inside it (0 = raw bins)
    group: jnp.ndarray = None    # int32
    offset: jnp.ndarray = None   # int32
    # CEGB per-feature coupled acquisition penalty (zeros when off)
    cegb_coupled_penalty: jnp.ndarray = None  # float32
    # CEGB per-datum lazy penalty (zeros when off)
    cegb_lazy_penalty: jnp.ndarray = None     # float32
    # global logical feature id of each scan slot (arange(F) except in
    # the feature-parallel shard metas, where the scan axis is a
    # permuted/padded slice of the global features; padding slots hold
    # F — an out-of-range id — and are masked off the scan)
    global_id: jnp.ndarray = None             # int32


class SplitParams(NamedTuple):
    """Static (python-scalar) split hyperparameters."""
    lambda_l1: float
    lambda_l2: float
    max_delta_step: float
    min_data_in_leaf: float
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    # categorical (M3)
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    min_data_per_group: float = 100.0
    # static gate: compile the categorical scan only when the dataset
    # has categorical features (set by the learner)
    has_categorical: bool = False
    # static gate: when NO feature has missing values the dir=+1 scan
    # can never win (two_scan is all-False), so skip compiling it —
    # halves the per-split scan op count in the common dense case
    # (mirrors the reference's one-scan path for MissingType::None,
    # feature_histogram.hpp:555-709)
    any_missing: bool = True
    # static gate: route eligible numerical scans through the fused
    # Pallas kernel (ops/split_scan_pallas.py) — set by learners whose
    # scan runs collective-free (see scan_kernel_ok for the per-call
    # eligibility: no categorical, no CEGB, no rand_bins)
    use_scan_kernel: bool = False
    # CEGB (cost_effective_gradient_boosting.hpp:50-61): static gate +
    # scalar penalties; the per-feature coupled penalty rides FeatureMeta
    cegb_on: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_lazy_on: bool = False


class SplitResult(NamedTuple):
    """Best split of one leaf; all scalars (device)."""
    gain: jnp.ndarray          # f32, -inf when no valid split
    feature: jnp.ndarray       # i32 inner feature index
    threshold: jnp.ndarray     # i32 bin threshold (left = bin <= threshold)
    default_left: jnp.ndarray  # bool
    left_g: jnp.ndarray
    left_h: jnp.ndarray
    left_c: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray
    # categorical support: when is_cat, the split is "bin in bitset"
    is_cat: jnp.ndarray        # bool
    cat_bitset: jnp.ndarray    # uint32 [MAX_CAT_WORDS] bin-bitset, left side


MAX_CAT_WORDS = 8  # supports categorical features up to 256 bins


def threshold_l1(s, l1):
    reg = jnp.maximum(jnp.abs(s) - l1, 0.0)
    return jnp.sign(s) * reg


def leaf_output_no_constraint(g, h, l1, l2, max_delta_step):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:497-504).

    ``max_delta_step`` is a python float on the serial path (the clip
    is compiled in or out statically) but a traced per-model scalar
    under multiboost's vmap — there the cap widens to +inf when the
    step is 0, which is a bitwise no-op (clip(x, -inf, inf) == x,
    NaNs propagate through max/min unchanged)."""
    out = -threshold_l1(g, l1) / (h + l2)
    if isinstance(max_delta_step, jnp.ndarray):
        cap = jnp.where(max_delta_step > 0.0, max_delta_step, jnp.inf)
        out = jnp.clip(out, -cap, cap)
    elif max_delta_step > 0.0:
        out = jnp.clip(out, -max_delta_step, max_delta_step)
    return out


def leaf_output(g, h, l1, l2, max_delta_step, cmin, cmax):
    """Constrained variant (feature_histogram.hpp:527-537)."""
    return jnp.clip(
        leaf_output_no_constraint(g, h, l1, l2, max_delta_step), cmin, cmax)


def gain_given_output(g, h, w, l1, l2):
    """GetLeafSplitGainGivenOutput (feature_histogram.hpp:550-553)."""
    sg_l1 = threshold_l1(g, l1)
    return -(2.0 * sg_l1 * w + (h + l2) * w * w)


def leaf_split_gain(g, h, l1, l2, max_delta_step):
    """GetLeafSplitGain (feature_histogram.hpp:545-548)."""
    w = leaf_output_no_constraint(g, h, l1, l2, max_delta_step)
    return gain_given_output(g, h, w, l1, l2)


def _split_gains(gl, hl, gr, hr, p: SplitParams, monotone, cmin, cmax):
    """GetSplitGains (feature_histogram.hpp:507-519)."""
    wl = leaf_output(gl, hl, p.lambda_l1, p.lambda_l2, p.max_delta_step,
                     cmin, cmax)
    wr = leaf_output(gr, hr, p.lambda_l1, p.lambda_l2, p.max_delta_step,
                     cmin, cmax)
    gain = gain_given_output(gl, hl, wl, p.lambda_l1, p.lambda_l2) \
        + gain_given_output(gr, hr, wr, p.lambda_l1, p.lambda_l2)
    violates = ((monotone > 0) & (wl > wr)) | ((monotone < 0) & (wl < wr))
    return jnp.where(violates, 0.0, gain)


def _argmax_first(x):
    return jnp.argmax(x)


def _argmax_last(x, axis):
    n = x.shape[axis]
    rev = jnp.flip(x, axis=axis)
    return n - 1 - jnp.argmax(rev, axis=axis)


class PerFeatureSplits(NamedTuple):
    """Best split per feature (arrays of shape [F]) — the intermediate
    the parallel learners exchange (voting: top-k of ``score``;
    feature-parallel: local argmax then cross-device compare)."""
    score: jnp.ndarray       # f32 penalized gain above shift, -inf invalid
    threshold: jnp.ndarray   # i32
    left_g: jnp.ndarray      # f32
    left_h: jnp.ndarray      # f32 (eps-free)
    left_c: jnp.ndarray      # f32
    default_left: jnp.ndarray  # bool
    left_output: jnp.ndarray   # f32, constrained
    right_output: jnp.ndarray  # f32, constrained
    is_cat: jnp.ndarray        # bool
    cat_bitset: jnp.ndarray    # uint32 [F, MAX_CAT_WORDS]


def per_feature_numerical(hist: jnp.ndarray, parent_g, parent_h, parent_c,
                          meta: FeatureMeta, params: SplitParams,
                          constraint_min=None, constraint_max=None,
                          feature_mask: jnp.ndarray | None = None,
                          rand_bins: jnp.ndarray | None = None
                          ) -> PerFeatureSplits:
    """Per-feature best numerical split of one leaf.

    hist: [F, B, 3] (sum_grad, sum_hess, count) per bin.
    parent_*: scalar totals of the leaf.
    rand_bins: extra-trees mode (Config.extra_trees; the reference's
    IS_RAND template paths, feature_histogram.hpp:555-709 rand_threshold_):
    [F] i32 of one uniformly-drawn candidate threshold per feature —
    both scan directions consider ONLY that bin.

    The cumulative machinery runs CHANNEL-STACKED on a [3, F, B]
    channels-FIRST tensor — one cumsum / one reduce / one
    winning-threshold gather per scan direction instead of three — so
    the compiled while-loop body carries ~3x fewer per-split ops. The
    bin axis stays MINOR exactly as in the per-channel [F, B]
    formulation, so each channel's reduction runs over the same
    contiguous layout with the same vectorized accumulation order and
    every value is bit-identical to the unstacked scan (a
    channels-last [F, B, 3] stack is NOT: reducing the then-strided
    bin axis changes the accumulation order under vectorization —
    observed at AVX2 — and flips last-ulp rounding).
    """
    f, b, _ = hist.shape
    p = params
    if constraint_min is None:
        constraint_min = jnp.float32(-jnp.inf)
    if constraint_max is None:
        constraint_max = jnp.float32(jnp.inf)

    bins = jnp.arange(b, dtype=jnp.int32)[None, :]          # [1,B]
    nb = meta.num_bins[:, None]                              # [F,1]
    missing = meta.missing[:, None]
    default_bin = meta.default_bin[:, None]
    monotone = meta.monotone[:, None]

    parent_h_eps = parent_h + 2.0 * kEpsilon
    # (parent_g, parent_h + 2eps, parent_c) as a [3, 1, 1] channel
    # vector; the kEpsilon seed lands on the hessian channel ONLY via
    # a channel select (an unconditional `+ [0, eps, 0]` would rewrite
    # -0.0 bins to +0.0 on the grad/count channels — a bit-level
    # divergence)
    parents = jnp.stack([jnp.asarray(parent_g, jnp.float32),
                         jnp.asarray(parent_h_eps, jnp.float32),
                         jnp.asarray(parent_c, jnp.float32)]
                        )[:, None, None]
    # iota-compare instead of a materialized [3] constant: the fused
    # split-step megakernel traces this scan INSIDE a Pallas kernel
    # body, which rejects captured non-scalar constants
    ch_is_h = jax.lax.broadcasted_iota(jnp.int32, (3, 1, 1), 0) == 1

    def seed_h(x):
        return jnp.where(ch_is_h, x + kEpsilon, x)

    hist_cf = jnp.moveaxis(hist, -1, 0)                      # [3,F,B]
    gain_shift = leaf_split_gain(parent_g, parent_h_eps, p.lambda_l1,
                                 p.lambda_l2, p.max_delta_step)
    min_gain_shift = gain_shift + p.min_gain_to_split

    def masked(x, m):
        return jnp.where(m[None, :, :], 0.0, x)

    if p.any_missing:
        # reference runs the two-scan path only when num_bin > 2 and
        # missing
        two_scan = (missing != MISSING_NONE_CODE) & (nb > 2)
        skip_default = two_scan & (missing == MISSING_ZERO_CODE) \
            & (bins == default_bin)
        na_excl = two_scan & (missing == MISSING_NAN_CODE)
        is_na_bin = na_excl & (bins == nb - 1)

        # ---- dir=+1: left-to-right; default/NaN implicitly go right ----
        # left sums at threshold t = cumsum of masked bins <= t, with
        # the kEpsilon seed on the hessian channel
        left_p = seed_h(jnp.cumsum(masked(hist_cf, skip_default),
                                   axis=2))
        lg_p, hl_p, lc_p = left_p[0], left_p[1], left_p[2]
        hr_p = parent_h_eps - hl_p
        gr_p = parent_g - lg_p
        cr_p = parent_c - lc_p
        valid_p = two_scan & (bins <= nb - 2) & ~skip_default
        if rand_bins is not None:
            valid_p &= bins == rand_bins[:, None]
        valid_p &= (lc_p >= p.min_data_in_leaf) \
            & (cr_p >= p.min_data_in_leaf)
        valid_p &= (hl_p >= p.min_sum_hessian_in_leaf) \
            & (hr_p >= p.min_sum_hessian_in_leaf)
        gains_p = _split_gains(lg_p, hl_p, gr_p, hr_p, p, monotone,
                               constraint_min, constraint_max)
        score_p = jnp.where(valid_p & (gains_p > min_gain_shift),
                            gains_p, NEG_INF)
        hist_m = masked(hist_cf, skip_default | is_na_bin)
    else:
        # static no-missing fast path (set by the learner from the bin
        # mappers): two_scan would be all-False, so the dir=+1 scan can
        # never record a split and every missing mask vanishes — only
        # the dir=-1 scan below compiles (the reference's one-scan path
        # for MissingType::None, feature_histogram.hpp:555-709)
        hist_m = hist_cf

    # ---- dir=-1: right-to-left; default/NaN implicitly go left ---------
    # right side at threshold t = sum of masked bins > t (hessian
    # channel seeded with kEpsilon); left side = parents - right
    right_m = seed_h(hist_m.sum(axis=2, keepdims=True)
                     - jnp.cumsum(hist_m, axis=2))
    left_m = parents - right_m
    rg_m, hr_m, rc_m = right_m[0], right_m[1], right_m[2]
    gl_m, hl_m, cl_m = left_m[0], left_m[1], left_m[2]
    if p.any_missing:
        valid_m = bins <= nb - 2 - na_excl.astype(jnp.int32)
    else:
        valid_m = bins <= nb - 2
    if rand_bins is not None:
        valid_m &= bins == rand_bins[:, None]
    if p.any_missing:
        # zero-missing skips threshold default_bin-1 (the `continue`
        # skips the iteration that would have recorded it,
        # feature_histogram.hpp:577)
        valid_m &= ~(two_scan & (missing == MISSING_ZERO_CODE)
                     & (bins == default_bin - 1))
    valid_m &= (cl_m >= p.min_data_in_leaf) & (rc_m >= p.min_data_in_leaf)
    valid_m &= (hl_m >= p.min_sum_hessian_in_leaf) \
        & (hr_m >= p.min_sum_hessian_in_leaf)
    gains_m = _split_gains(gl_m, hl_m, rg_m, hr_m, p, monotone,
                           constraint_min, constraint_max)
    score_m = jnp.where(valid_m & (gains_m > min_gain_shift), gains_m,
                        NEG_INF)

    # ---- per-feature best with reference iteration-order tie-breaks ----
    t_m = _argmax_last(score_m, axis=1)                      # [F]
    v_m = jnp.take_along_axis(score_m, t_m[:, None], axis=1)[:, 0]
    if p.any_missing:
        t_p = jnp.argmax(score_p, axis=1)
        v_p = jnp.take_along_axis(score_p, t_p[:, None], axis=1)[:, 0]
        use_m = v_m >= v_p                                   # -1 scan first
        feat_gain = jnp.where(use_m, v_m, v_p)
        feat_t = jnp.where(use_m, t_m, t_p).astype(jnp.int32)
    else:
        use_m = jnp.ones((f,), bool)
        feat_gain = v_m
        feat_t = t_m.astype(jnp.int32)

    feat_valid = jnp.isfinite(feat_gain) & ~meta.is_categorical
    if feature_mask is not None:
        feat_valid &= feature_mask
    feat_score = jnp.where(
        feat_valid, (feat_gain - min_gain_shift) * meta.penalty, NEG_INF)

    # left-side sums at each feature's winning threshold: ONE stacked
    # [3, F] gather per direction instead of three scalar-channel
    # gathers (the seeded left tensors already exist channel-stacked)
    lf_m = jnp.take_along_axis(left_m, t_m[None, :, None],
                               axis=2)[:, :, 0]              # [3, F]
    if p.any_missing:
        lf_p = jnp.take_along_axis(left_p, t_p[None, :, None],
                                   axis=2)[:, :, 0]
        lf = jnp.where(use_m[None, :], lf_m, lf_p)
    else:
        lf = lf_m
    lg_f, lh_f, lc_f = lf[0], lf[1], lf[2]

    # default direction: -1 scan => left; 2-bin NaN fix goes right
    # (feature_histogram.hpp:127-130)
    dleft_f = use_m & ~((meta.num_bins <= 2)
                        & (meta.missing == MISSING_NAN_CODE))

    # constrained outputs at the winning threshold (vectorized over [F])
    wl_f = leaf_output(lg_f, lh_f, p.lambda_l1, p.lambda_l2,
                       p.max_delta_step, constraint_min, constraint_max)
    wr_f = leaf_output(parent_g - lg_f, parent_h_eps - lh_f, p.lambda_l1,
                       p.lambda_l2, p.max_delta_step, constraint_min,
                       constraint_max)

    return PerFeatureSplits(
        score=feat_score, threshold=feat_t,
        left_g=lg_f, left_h=lh_f - kEpsilon,
        left_c=lc_f, default_left=dleft_f,
        left_output=wl_f, right_output=wr_f,
        is_cat=jnp.zeros((f,), bool),
        cat_bitset=jnp.zeros((f, MAX_CAT_WORDS), jnp.uint32))


def per_feature_splits(hist: jnp.ndarray, parent_g, parent_h, parent_c,
                       meta: FeatureMeta, params: SplitParams,
                       constraint_min=None, constraint_max=None,
                       feature_mask: jnp.ndarray | None = None,
                       rand_bins: jnp.ndarray | None = None,
                       cegb_used: jnp.ndarray | None = None,
                       cegb_uncharged: jnp.ndarray | None = None,
                       return_raw: bool = False):
    """Numerical + categorical per-feature scan, merged per feature.

    The categorical scan compiles only when ``params.has_categorical``
    (a static flag) — pure-numerical datasets pay nothing.
    ``rand_bins`` (extra-trees) restricts NUMERICAL features to one
    random threshold each; categorical features keep the full scan
    (documented divergence: the reference also randomizes categorical
    candidates in IS_RAND mode).

    ``return_raw=True`` also returns the pre-CEGB-penalty scores as a
    second value: the reference caches the UNpenalized SplitInfo
    (``new_split`` is passed by value to DetlaGain BEFORE the caller
    subtracts the delta, serial_tree_learner.cpp:767-776), so the
    coupled-penalty refund later lands on top of raw gains.
    """
    if constraint_min is None:
        constraint_min = jnp.float32(-jnp.inf)
    if constraint_max is None:
        constraint_max = jnp.float32(jnp.inf)
    if params.use_scan_kernel:
        from .split_scan_pallas import (per_feature_numerical_pallas,
                                        scan_kernel_ok)
        if scan_kernel_ok(params, rand_bins, cegb_uncharged):
            pf = per_feature_numerical_pallas(
                hist, parent_g, parent_h, parent_c, meta, params,
                constraint_min, constraint_max, feature_mask)
            # no CEGB on this path, so raw == penalized score
            return (pf, pf.score) if return_raw else pf
    pf = per_feature_numerical(hist, parent_g, parent_h, parent_c, meta,
                               params, constraint_min, constraint_max,
                               feature_mask, rand_bins)
    if params.has_categorical:
        from ..observability.scopes import CAT_SCAN
        from .split_categorical import per_feature_categorical
        with jax.named_scope(CAT_SCAN):
            cat = per_feature_categorical(
                hist, parent_g, parent_h, parent_c, meta, params,
                constraint_min, constraint_max, feature_mask)
            use = meta.is_categorical

            def sel(a, b):
                return jnp.where(use, a, b) if a.ndim == 1 \
                    else jnp.where(use[:, None], a, b)

            pf = PerFeatureSplits(
                score=sel(cat["score"], pf.score),
                threshold=pf.threshold,
                left_g=sel(cat["left_g"], pf.left_g),
                left_h=sel(cat["left_h"], pf.left_h),
                left_c=sel(cat["left_c"], pf.left_c),
                default_left=jnp.where(use, False, pf.default_left),
                left_output=sel(cat["left_output"], pf.left_output),
                right_output=sel(cat["right_output"], pf.right_output),
                is_cat=use & jnp.isfinite(cat["score"]),
                cat_bitset=sel(cat["bitset"], pf.cat_bitset))
    raw_score = pf.score
    if params.cegb_on:
        # CEGB DetlaGain (cost_effective_gradient_boosting.hpp:50-61):
        # gain -= tradeoff * (penalty_split * leaf rows
        #                     + coupled penalty if feature unused).
        # Penalized gains stay FINITE (possibly negative): the grow
        # loop stops on best gain <= 0, and a later coupled-penalty
        # refund (UpdateLeafBestSplits) can resurrect a leaf.
        delta = jnp.float32(params.cegb_tradeoff
                            * params.cegb_penalty_split) * parent_c
        cp = meta.cegb_coupled_penalty
        if cp is not None:
            unused = jnp.ones(pf.score.shape[0], bool) \
                if cegb_used is None else ~cegb_used
            delta = delta + params.cegb_tradeoff * cp * unused
        if params.cegb_lazy_on and cegb_uncharged is not None:
            # lazy: charge each (row, feature) pair once
            # (CalculateOndemandCosts: penalty * uncharged rows in leaf)
            delta = delta + params.cegb_tradeoff \
                * meta.cegb_lazy_penalty * cegb_uncharged
        pf = pf._replace(score=jnp.where(
            jnp.isfinite(pf.score), pf.score - delta, pf.score))
    if return_raw:
        return pf, raw_score
    return pf


def assemble_split(pf: PerFeatureSplits, best_f,
                   feature_id=None) -> SplitResult:
    """Gather one feature's per-feature result into a SplitResult.

    ``best_f`` indexes into ``pf``; ``feature_id`` (defaults to best_f)
    is the feature index recorded in the tree — parallel learners pass
    the GLOBAL id while indexing their local shard.
    """
    fid = best_f if feature_id is None else feature_id
    # two packed column gathers (f32 fields / int-ish fields) + the
    # bitset row replace ten scalar gathers — the per-split dispatch
    # economy the fused grow loop counts on (tools/hlo_census.py)
    fpack = jnp.stack([pf.score, pf.left_g, pf.left_h, pf.left_c,
                       pf.left_output, pf.right_output])      # [6, F]
    ipack = jnp.stack([pf.threshold,
                       pf.default_left.astype(jnp.int32),
                       pf.is_cat.astype(jnp.int32)])          # [3, F]
    fv = fpack[:, best_f]
    iv = ipack[:, best_f]
    return SplitResult(
        gain=fv[0], feature=jnp.asarray(fid, jnp.int32),
        threshold=iv[0],
        default_left=iv[1].astype(bool),
        left_g=fv[1], left_h=fv[2], left_c=fv[3],
        left_output=fv[4],
        right_output=fv[5],
        is_cat=iv[2].astype(bool),
        cat_bitset=pf.cat_bitset[best_f])


def best_split_numerical(hist: jnp.ndarray, parent_g, parent_h, parent_c,
                         meta: FeatureMeta, params: SplitParams,
                         constraint_min=None, constraint_max=None,
                         feature_mask: jnp.ndarray | None = None
                         ) -> SplitResult:
    """Best numerical split over all features of one leaf
    (per-feature scan + first-index argmax, the serial composition)."""
    if constraint_min is None:
        constraint_min = jnp.float32(-jnp.inf)
    if constraint_max is None:
        constraint_max = jnp.float32(jnp.inf)
    pf = per_feature_numerical(hist, parent_g, parent_h, parent_c, meta,
                               params, constraint_min, constraint_max,
                               feature_mask)
    best_f = _argmax_first(pf.score).astype(jnp.int32)
    return assemble_split(pf, best_f)


def best_split(hist: jnp.ndarray, parent_g, parent_h, parent_c,
               meta: FeatureMeta, params: SplitParams,
               constraint_min=None, constraint_max=None,
               feature_mask: jnp.ndarray | None = None,
               rand_bins: jnp.ndarray | None = None,
               cegb_used: jnp.ndarray | None = None,
               cegb_uncharged: jnp.ndarray | None = None) -> SplitResult:
    """Best split (numerical + categorical) over all features of one
    leaf — the full FindBestThreshold dispatch
    (feature_histogram.hpp:84-148)."""
    if constraint_min is None:
        constraint_min = jnp.float32(-jnp.inf)
    if constraint_max is None:
        constraint_max = jnp.float32(jnp.inf)
    pf = per_feature_splits(hist, parent_g, parent_h, parent_c, meta,
                            params, constraint_min, constraint_max,
                            feature_mask, rand_bins,
                            cegb_used=cegb_used,
                            cegb_uncharged=cegb_uncharged)
    best_f = _argmax_first(pf.score).astype(jnp.int32)
    return assemble_split(pf, best_f)


# =====================================================================
# one split's step: what a chosen split scans and writes. ONE
# definition of each, shared by the grow bodies (learner/serial.py,
# learner/partitioned.py) and the split-step megakernel
# (ops/split_step_pallas.py) — bit-exactness-critical, so they live
# below both. ``pack`` is the grow loop's ``StatePack``
# (learner/split_step.py), ``comm`` its ``Comm`` (learner/comm.py);
# both arrive as arguments.
# =====================================================================

def set_bitsets(pack, view: dict, idx_a, idx_b,
                bits_a, bits_b, s, site_bitset) -> dict:
    """Bitset carry updates for one split — compiled out entirely when
    the pack derives the bitsets (numerical-only datasets)."""
    if "bs_bitset" in pack.derived:
        return {}
    idx2 = jnp.stack([jnp.asarray(idx_a, jnp.int32),
                      jnp.asarray(idx_b, jnp.int32)])
    return {
        "bs_bitset": view["bs_bitset"].at[idx2].set(
            jnp.stack([bits_a, bits_b])),
        "cat_bitsets": view["cat_bitsets"].at[s].set(site_bitset)}


def child_constraints(meta, feat, is_cat, lout, rout, pcmin, pcmax,
                      has_monotone: bool):
    """Monotone constraint propagation to both children
    (LeafConstraints::UpdateConstraints, monotone_constraints.hpp:44).
    STATICALLY compiled out (inherited parent bounds, which stay ±inf
    forever) when no feature has a monotone constraint."""
    if not has_monotone:
        return pcmin, pcmax, pcmin, pcmax
    return child_constraints_mono(meta.monotone[feat], is_cat, lout,
                                  rout, pcmin, pcmax)


def child_constraints_mono(mono, is_cat, lout, rout, pcmin, pcmax):
    """``child_constraints`` on a pre-gathered per-feature monotone
    direction — the fused megakernel's Mosaic body extracts ``mono``
    with a select-sum (dynamic gathers do not lower) and shares the
    rest of the math here."""
    mid = (lout + rout) * 0.5
    numerical = ~is_cat
    cmin_l = jnp.where(numerical & (mono < 0),
                       jnp.maximum(pcmin, mid), pcmin)
    cmax_l = jnp.where(numerical & (mono > 0),
                       jnp.minimum(pcmax, mid), pcmax)
    cmin_r = jnp.where(numerical & (mono > 0),
                       jnp.maximum(pcmin, mid), pcmin)
    cmax_r = jnp.where(numerical & (mono < 0),
                       jnp.minimum(pcmax, mid), pcmax)
    return cmin_l, cmax_l, cmin_r, cmax_r


def order_child_pair(a_is_left, k, lg, lh, lc, rg, rh, rc, lout, rout,
                     cmin_l, cmax_l, cmin_r, cmax_r) -> dict:
    """(left, right) child scalars -> (a, b) storage order for one
    split step. ``a_is_left`` is True on the (leaf, new) paths and
    ``small_is_left`` on the (smaller, other) fused path; the salts
    carry the child identity (left = 2k+1, right = 2k+2) so per-node
    RNG streams are order-invariant, and ``side_a/b`` keep the
    ref_side encoding (0 = left child). One definition shared by the
    serial and partitioned grow bodies — this mapping is
    bit-exactness-critical and must never diverge between them."""
    def w(x, y):
        return jnp.where(a_is_left, x, y)

    side_a = w(jnp.int32(0), jnp.int32(1))
    return dict(
        ga=w(lg, rg), ha=w(lh, rh), ca=w(lc, rc),
        gb=w(rg, lg), hb=w(rh, lh), cb=w(rc, lc),
        out_a=w(lout, rout), out_b=w(rout, lout),
        cmin_a=w(cmin_l, cmin_r), cmax_a=w(cmax_l, cmax_r),
        cmin_b=w(cmin_r, cmin_l), cmax_b=w(cmax_r, cmax_l),
        salt_a=w(2 * k + 1, 2 * k + 2),
        salt_b=w(2 * k + 2, 2 * k + 1),
        side_a=side_a, side_b=jnp.int32(1) - side_a)


def child_columns(split, g, h, c, out, cmin, cmax, s, side, depth,
                  extra_i=None):
    """One fresh child's state-column field dicts (float, int) for
    ``StatePack.set_state_cols`` — the single definition of what each
    split writes per child (the partitioned learner prepends its
    segment bounds via ``extra_i``)."""
    f = dict(leaf_g=g, leaf_h=h, leaf_c=c, bs_gain=split.gain,
             bs_lg=split.left_g, bs_lh=split.left_h,
             bs_lc=split.left_c, bs_lout=split.left_output,
             bs_rout=split.right_output, leaf_cmin=cmin,
             leaf_cmax=cmax, leaf_value=out, leaf_weight=h,
             leaf_count=c)
    i = dict(bs_feat=split.feature, bs_thr=split.threshold,
             bs_dleft=split.default_left, bs_iscat=split.is_cat,
             ref_node=s, ref_side=side, leaf_parent=s,
             leaf_depth=depth)
    if extra_i:
        i.update(extra_i)
    return f, i


def make_scan_leaf(comm, meta_scan, params, feature_mask, node_rand,
                   bundled: bool, max_depth: int, select=None,
                   debundle_scope: str | None = None):
    """One leaf's best-split scan (debundle -> per-node randomness ->
    comm.select_split -> max_depth blocking) — ONE definition shared by
    the serial and partitioned grow bodies AND the fused megakernel's
    interpret twin (ops/split_step_pallas.py). The twin's byte-exact
    parity with the foil rests on this being the same function.
    ``select`` overrides ``comm.select_split`` where the root and
    per-split scan layouts differ (the data-parallel reduce-scatter
    recipe scans the root replicated, learner/comm.py).
    ``debundle_scope`` names the EFB debundle's device scope where the
    caller's program has one (observability/scopes.py BUNDLE_SCOPES)."""
    if select is None:
        select = comm.select_split

    def scan_leaf(hist, g, h, c, depth, cmin, cmax, salt):
        if bundled:
            from .histogram import debundle_leaf_hist
            with jax.named_scope(debundle_scope) if debundle_scope \
                    else contextlib.nullcontext():
                hist = debundle_leaf_hist(hist, meta_scan, g, h, c,
                                          comm.local_hist)
        rb, nm = node_rand(salt)
        fm = feature_mask if nm is None else nm  # nm already in-subset
        res = select(hist, g, h, c, meta_scan, params,
                     cmin, cmax, fm, rand_bins=rb)
        blocked = (max_depth > 0) & (depth >= max_depth)
        return res._replace(gain=jnp.where(blocked, -jnp.inf, res.gain))
    return scan_leaf


def scan_split_pair(comm, scan_leaf, a_is_left, k, depth,
                    hist_a, hist_b, lg, lh, lc, rg, rh, rc, lout, rout,
                    cmin_l, cmax_l, cmin_r, cmax_r):
    """Order the (a, b) child pair and scan both fresh children — the
    shared non-CEGB composition of ``order_child_pair`` +
    ``scan_children`` used by both grow bodies and the megakernel
    twin."""
    o = order_child_pair(a_is_left, k, lg, lh, lc, rg, rh, rc, lout,
                         rout, cmin_l, cmax_l, cmin_r, cmax_r)
    split_a, split_b = scan_children(
        comm, scan_leaf, hist_a, hist_b, o["ga"], o["ha"], o["ca"],
        o["gb"], o["hb"], o["cb"], depth, o["cmin_a"], o["cmax_a"],
        o["cmin_b"], o["cmax_b"], o["salt_a"], o["salt_b"])
    return o, split_a, split_b


def split_node_updates(params, gain, feat, thr, dleft, is_cat,
                       pg, ph, pc, ref_node, leaf, new):
    """Tree-array column dicts + parent-pointer fixup scalars of one
    split — one definition shared by the grow bodies and the fused
    megakernel twin (``set_tree_col`` consumes the result)."""
    dec = jnp.where(is_cat, 1, 0) + jnp.where(dleft, 2, 0)
    upd = ref_node >= 0
    pnode = jnp.where(upd, ref_node, 0)
    parent_out = leaf_output_no_constraint(
        pg, ph + 2e-15, params.lambda_l1, params.lambda_l2,
        params.max_delta_step)
    treef = dict(split_gain_arr=gain, internal_value=parent_out,
                 internal_weight=ph, internal_count=pc)
    treei = dict(split_feature=feat, threshold_bin=thr,
                 decision_type=dec, left_child=~leaf, right_child=~new)
    return treef, treei, pnode, upd


def scan_children(comm, scan_leaf, hist_a, hist_b, ga, ha, ca,
                  gb, hb, cb, depth, cmin_a, cmax_a, cmin_b, cmax_b,
                  salt_a, salt_b):
    """Best splits of both fresh children (order-agnostic pair — the
    fused bodies pass (smaller, larger), the legacy CEGB path passes
    (left, right); the salts carry the child identity so node-rand
    streams stay exact). For vmap_safe comms this is ONE vmapped scan:
    same math, half the op count inside the while_loop body (each
    [F, B] scan op is tiny; per-op overhead dominates at bench
    shapes). Collective-bearing selects stay unbatched. Shared by the
    serial and partitioned grow loops."""
    if not comm.vmap_safe:
        return (scan_leaf(hist_a, ga, ha, ca, depth, cmin_a, cmax_a,
                          salt_a),
                scan_leaf(hist_b, gb, hb, cb, depth, cmin_b, cmax_b,
                          salt_b))
    res2 = jax.vmap(
        lambda hh, g_, h_, c_, cm, cx, s_: scan_leaf(
            hh, g_, h_, c_, depth, cm, cx, s_))(
        jnp.stack([hist_a, hist_b]),
        jnp.stack([ga, gb]), jnp.stack([ha, hb]),
        jnp.stack([ca, cb]),
        jnp.stack([cmin_a, cmin_b]),
        jnp.stack([cmax_a, cmax_b]),
        jnp.stack([salt_a, salt_b]))
    return (jax.tree.map(lambda x: x[0], res2),
            jax.tree.map(lambda x: x[1], res2))
