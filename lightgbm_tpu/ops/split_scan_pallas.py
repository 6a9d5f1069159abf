"""Pallas TPU kernel: fused numerical best-split scan of one leaf.

Reference analog: ``FeatureHistogram::FindBestThresholdSequentially``
(feature_histogram.hpp:555-709) — the same math as
``ops/split.py:per_feature_numerical`` but compiled as ONE kernel.

Why: inside the grow ``while_loop`` the XLA formulation of the scan
lowers to ~100 small ops over [F, B] grids (cumsums, masks, gain
algebra, argmax, gathers); at bench shapes each op is ~2-8 us of fixed
issue overhead, so one scan costs ~0.7 ms — the single largest slice of
the ~1.4 ms/split budget when it was written. Fusing the
whole scan into one Pallas program removes the per-op overhead: all
intermediates live in VMEM/registers and the cumulative sums are 8
Hillis-Steele lane-shift adds.

Scope (the common fast path; ``per_feature_splits`` falls back to the
XLA scan otherwise): numerical features only (categorical features must
be masked off by the caller), no CEGB, no extra-trees rand_bins. The
missing-value two-scan path compiles only when ``params.any_missing``.

Layout: histograms arrive as separate [F, B] g/h/c planes (slices of
the learner's [F, B, 3] histogram); per-feature metadata rides in
[F, 4] i32 / [F, 2] f32 tables so each column broadcasts as an [F, 1]
tile against the [F, B] grids; per-leaf scalars (parent sums,
constraints) ride in SMEM. Output is one [F, 8] f32 table (score,
threshold, left_g, left_h(+eps), left_c, default_left, left_output,
right_output) unpacked by the wrapper.

``jax.vmap`` over the wrapper batches the kernel across children (the
grow loop scans both fresh children in one call, learner/serial.py
``scan_children``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu
from ..utils.jit_registry import register_jit
from .split import (MISSING_NAN_CODE, MISSING_NONE_CODE,
                    MISSING_ZERO_CODE, MAX_CAT_WORDS, PerFeatureSplits,
                    SplitParams, _split_gains, gain_given_output,
                    kEpsilon, leaf_output, leaf_output_no_constraint)

NEG_INF = float("-inf")  # python scalar: kernels fold it as a constant

# output column slots of the [F, 8] result table
O_SCORE, O_THR, O_LG, O_LH, O_LC, O_DLEFT, O_WL, O_WR = range(8)


def _scan_kernel(scal_ref, imeta_ref, fmeta_ref, hg_ref, hh_ref, hc_ref,
                 out_ref, *, f: int, b: int, p: SplitParams):
    # scal is [1, 5]: a 1-D SMEM operand would batch to an illegal
    # (1, 5)-block-over-(K, 5) spec under vmap (Mosaic requires the
    # trailing two block dims to equal the array dims); with the
    # explicit leading 1 the vmapped block (1, 1, 5) stays legal
    out_ref[...] = scan_core(
        scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2],
        scal_ref[0, 3], scal_ref[0, 4],
        imeta_ref[:, 0:1], imeta_ref[:, 1:2], imeta_ref[:, 2:3],
        imeta_ref[:, 3:4], fmeta_ref[:, 0:1], fmeta_ref[:, 1:2],
        hg_ref[...], hh_ref[...], hc_ref[...], f=f, b=b, p=p)


def scan_core(pg, ph, pc, cmin, cmax, nb, missing, defbin, mono,
              penalty, fmask, g, h, c, *, f: int, b: int,
              p: SplitParams):
    """The fused numerical best-split scan on VALUES: per-leaf scalars,
    [F, 1] metadata columns and [F, B] g/h/c planes in, the packed
    [F, 8] result table out. Factored from ``_scan_kernel`` so the
    split-step megakernel (ops/split_step_pallas.py) runs the SAME
    Mosaic-proven scan for both fresh children inside one kernel."""
    bins = jax.lax.broadcasted_iota(jnp.int32, (f, b), 1)

    # gain algebra: the SHARED split.py helpers (pure jnp, static-param
    # closures trace fine inside a Pallas kernel) so the fused kernel
    # can never drift from the XLA scan's formulas
    def out_con(gv, hv):
        return leaf_output(gv, hv, p.lambda_l1, p.lambda_l2,
                           p.max_delta_step, cmin, cmax)

    def split_gains(glv, hlv, grv, hrv):
        return _split_gains(glv, hlv, grv, hrv, p, mono, cmin, cmax)

    def cumsum_lanes(x):
        # inclusive prefix sum along lanes: Hillis-Steele doubling
        # (the shifted-add ladder XLA's cumsum also lowers to)
        sh = 1
        while sh < b:
            x = x + jnp.concatenate(
                [jnp.zeros((f, sh), x.dtype), x[:, :b - sh]], axis=1)
            sh *= 2
        return x

    parent_h_eps = ph + jnp.float32(2.0 * kEpsilon)
    w_p = leaf_output_no_constraint(pg, parent_h_eps, p.lambda_l1,
                                    p.lambda_l2, p.max_delta_step)
    gain_shift = gain_given_output(pg, parent_h_eps, w_p, p.lambda_l1,
                                   p.lambda_l2)
    min_gain_shift = gain_shift + jnp.float32(p.min_gain_to_split)

    if p.any_missing:
        two_scan = (missing != MISSING_NONE_CODE) & (nb > 2)   # [F, 1]
        skip_default = two_scan & (missing == MISSING_ZERO_CODE) \
            & (bins == defbin)                                 # [F, B]
        na_excl = two_scan & (missing == MISSING_NAN_CODE)
        is_na_bin = na_excl & (bins == nb - 1)

        # ---- dir=+1: left-to-right; default/NaN implicitly go right ----
        lg_p = cumsum_lanes(jnp.where(skip_default, 0.0, g))
        lh_p = cumsum_lanes(jnp.where(skip_default, 0.0, h))
        lc_p = cumsum_lanes(jnp.where(skip_default, 0.0, c))
        hl_p = lh_p + jnp.float32(kEpsilon)
        hr_p = parent_h_eps - hl_p
        gr_p = pg - lg_p
        cr_p = pc - lc_p
        gains_p = split_gains(lg_p, hl_p, gr_p, hr_p)
        ok_p = (two_scan & (bins <= nb - 2) & ~skip_default
                & (lc_p >= p.min_data_in_leaf)
                & (cr_p >= p.min_data_in_leaf)
                & (hl_p >= p.min_sum_hessian_in_leaf)
                & (hr_p >= p.min_sum_hessian_in_leaf)
                & (gains_p > min_gain_shift))
        score_p = jnp.where(ok_p, gains_p, NEG_INF)

        mask_m = skip_default | is_na_bin
        g_m = jnp.where(mask_m, 0.0, g)
        h_m = jnp.where(mask_m, 0.0, h)
        c_m = jnp.where(mask_m, 0.0, c)
    else:
        g_m, h_m, c_m = g, h, c

    # ---- dir=-1: right-to-left; default/NaN implicitly go left ---------
    cs_g = cumsum_lanes(g_m)
    cs_h = cumsum_lanes(h_m)
    cs_c = cumsum_lanes(c_m)
    rg_m = cs_g[:, b - 1:b] - cs_g
    rh_m = cs_h[:, b - 1:b] - cs_h
    rc_m = cs_c[:, b - 1:b] - cs_c
    hr_m = rh_m + jnp.float32(kEpsilon)
    hl_m = parent_h_eps - hr_m
    gl_m = pg - rg_m
    cl_m = pc - rc_m
    gains_m = split_gains(gl_m, hl_m, rg_m, hr_m)
    if p.any_missing:
        ok_m = bins <= nb - 2 - na_excl.astype(jnp.int32)
        # zero-missing skips threshold default_bin-1
        # (feature_histogram.hpp:577)
        ok_m &= ~(two_scan & (missing == MISSING_ZERO_CODE)
                  & (bins == defbin - 1))
    else:
        ok_m = bins <= nb - 2
    ok_m = (ok_m & (cl_m >= p.min_data_in_leaf)
            & (rc_m >= p.min_data_in_leaf)
            & (hl_m >= p.min_sum_hessian_in_leaf)
            & (hr_m >= p.min_sum_hessian_in_leaf)
            & (gains_m > min_gain_shift))
    score_m = jnp.where(ok_m, gains_m, NEG_INF)

    # ---- per-feature best with reference iteration-order tie-breaks ----
    # threshold arg-extrema run in f32 (bins <= 65535 are exact): this
    # jax's Mosaic cannot lower integer reductions, and the split-step
    # megakernel reuses this core compiled
    bins_f = bins.astype(jnp.float32)
    best_m = jnp.max(score_m, axis=1, keepdims=True)           # [F, 1]
    # _argmax_last: the -1 scan records the LARGEST winning threshold
    t_m = jnp.max(jnp.where(score_m == best_m, bins_f, -1.0), axis=1,
                  keepdims=True)                               # [F, 1]
    sel_m = (bins_f == t_m).astype(jnp.float32)                # [F, B]
    lg_m_t = jnp.sum(gl_m * sel_m, axis=1, keepdims=True)
    lh_m_t = jnp.sum(hl_m * sel_m, axis=1, keepdims=True)
    lc_m_t = jnp.sum(cl_m * sel_m, axis=1, keepdims=True)

    if p.any_missing:
        best_p = jnp.max(score_p, axis=1, keepdims=True)
        # +1 scan records the SMALLEST winning threshold
        t_p = jnp.min(jnp.where(score_p == best_p, bins_f,
                                jnp.float32(b)), axis=1,
                      keepdims=True)
        sel_p = (bins_f == t_p).astype(jnp.float32)
        lg_p_t = jnp.sum(lg_p * sel_p, axis=1, keepdims=True)
        lh_p_t = jnp.sum(hl_p * sel_p, axis=1, keepdims=True)
        lc_p_t = jnp.sum(lc_p * sel_p, axis=1, keepdims=True)

        use_m = best_m >= best_p                               # [F, 1]
        feat_gain = jnp.where(use_m, best_m, best_p)
        feat_t = jnp.where(use_m, t_m, t_p)
        lg_f = jnp.where(use_m, lg_m_t, lg_p_t)
        lh_f = jnp.where(use_m, lh_m_t, lh_p_t)
        lc_f = jnp.where(use_m, lc_m_t, lc_p_t)
        # 2-bin NaN features send missing right (hpp:127-130)
        dleft = jnp.where(
            use_m & ~((nb <= 2) & (missing == MISSING_NAN_CODE)),
            jnp.float32(1), jnp.float32(0))
    else:
        feat_gain = best_m
        feat_t = t_m
        lg_f, lh_f, lc_f = lg_m_t, lh_m_t, lc_m_t
        dleft = jnp.ones((f, 1), jnp.float32)

    valid = (feat_gain > NEG_INF) & (fmask > 0)
    feat_score = jnp.where(
        valid, (feat_gain - min_gain_shift) * penalty, NEG_INF)
    wl_f = out_con(lg_f, lh_f)
    wr_f = out_con(pg - lg_f, parent_h_eps - lh_f)

    return jnp.concatenate(
        [feat_score, feat_t.astype(jnp.float32), lg_f, lh_f, lc_f,
         dleft, wl_f, wr_f], axis=1)                           # [F, 8]


# features a grid step of the scan holds: every row of the [F, B]
# planes scans on its own, so a wide table is a grid over blocks of
# rows; the kernel keeps dozens of [rows, B] f32 intermediates in VMEM
# (2,000 x 256 would be 2 MB each)
SCAN_BLOCK_F = 128


@register_jit("split_scan_kernel")
@functools.partial(
    jax.jit, static_argnames=("params", "interpret"))
def _scan_call(scal, imeta, fmeta, hg, hh, hc, *, params: SplitParams,
               interpret: bool):
    f, b = hg.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if f <= SCAN_BLOCK_F:
        # one block holds the table
        whole = pl.BlockSpec(memory_space=pltpu.VMEM)
        return pl.pallas_call(
            functools.partial(_scan_kernel, f=f, b=b, p=params),
            out_shape=jax.ShapeDtypeStruct((f, 8), jnp.float32),
            in_specs=[smem, whole, whole, whole, whole, whole],
            out_specs=whole,
            interpret=interpret,
        )(scal, imeta, fmeta, hg, hh, hc)
    # a grid over blocks of features, the table padded to whole blocks
    # (a padded row has no bin and is cut off below)
    fb = SCAN_BLOCK_F
    pad = -f % fb

    def rows(x):
        return jnp.pad(x, ((0, pad), (0, 0)))

    def block(width):
        return pl.BlockSpec((fb, width), lambda i: (i, 0))

    return pl.pallas_call(
        functools.partial(_scan_kernel, f=fb, b=b, p=params),
        grid=((f + pad) // fb,),
        out_shape=jax.ShapeDtypeStruct((f + pad, 8), jnp.float32),
        in_specs=[smem, block(imeta.shape[1]), block(fmeta.shape[1]),
                  block(b), block(b), block(b)],
        out_specs=block(8),
        interpret=interpret,
    )(scal, rows(imeta), rows(fmeta), rows(hg), rows(hh), rows(hc))[:f]


def scan_kernel_ok(params: SplitParams, rand_bins, cegb_uncharged) -> bool:
    """Static eligibility of the fused kernel for one scan call.
    ``params.use_scan_kernel`` is the learner's choice
    (learner/split_step.py ``plan_split_step``: a TPU, compiled
    kernels, a numeric table without CEGB); a kernel it selects and
    Mosaic then refuses is a compile error at the first grow call,
    never a quiet switch to the XLA scan."""
    return (params.use_scan_kernel and rand_bins is None
            and not params.has_categorical and not params.cegb_on
            and cegb_uncharged is None)


def per_feature_numerical_pallas(hist, parent_g, parent_h, parent_c,
                                 meta, params: SplitParams,
                                 constraint_min, constraint_max,
                                 feature_mask,
                                 interpret: bool | None = None
                                 ) -> PerFeatureSplits:
    """Fused-kernel drop-in for ``per_feature_numerical`` (same output
    contract; categorical features come back masked with score=-inf and
    must be merged by the caller exactly as with the XLA scan).
    ``interpret=None`` resolves per backend; the Mosaic-lowering tests
    pass False explicitly (a backend-resolved default on a CPU host
    would silently lower the interpret path instead of Mosaic)."""
    f, b, _ = hist.shape
    if interpret is None:
        interpret = not on_tpu()
    scal = jnp.stack([
        jnp.asarray(parent_g, jnp.float32),
        jnp.asarray(parent_h, jnp.float32),
        jnp.asarray(parent_c, jnp.float32),
        jnp.asarray(constraint_min, jnp.float32),
        jnp.asarray(constraint_max, jnp.float32)])[None, :]
    imeta = jnp.stack([meta.num_bins, meta.missing, meta.default_bin,
                       meta.monotone], axis=1).astype(jnp.int32)
    fmask = ~meta.is_categorical
    if feature_mask is not None:
        fmask &= feature_mask
    fmeta = jnp.stack([meta.penalty, fmask.astype(jnp.float32)], axis=1)
    out = _scan_call(scal, imeta, fmeta,
                     hist[..., 0], hist[..., 1], hist[..., 2],
                     params=params, interpret=interpret)
    return PerFeatureSplits(
        score=out[:, O_SCORE],
        threshold=out[:, O_THR].astype(jnp.int32),
        left_g=out[:, O_LG],
        left_h=out[:, O_LH] - kEpsilon,
        left_c=out[:, O_LC],
        default_left=out[:, O_DLEFT] > 0.5,
        left_output=out[:, O_WL],
        right_output=out[:, O_WR],
        is_cat=jnp.zeros((f,), bool),
        cat_bitset=jnp.zeros((f, MAX_CAT_WORDS), jnp.uint32))
