"""One fused Pallas split-step megakernel (ROADMAP item 1).

The reference wins its grow loop by doing almost nothing per split
beyond one smaller-child histogram plus a subtraction
(``serial_tree_learner.cpp:434-436``). PR 8 collapsed the XLA analog
to 44 compiled ops/split; this module collapses it to ONE: an entire
split — best-leaf pick, leaf partition / row movement, smaller-child
histogram build, sibling histogram subtraction, and the
channel-stacked best-split scan of both fresh children — executes as a
single ``pallas_call`` whose carry (per-leaf state ``S``, tree arrays
``T``, the chosen leaf's histograms and every scan intermediate) never
leaves VMEM between phases. The grow ``while_loop`` body shrinks to
the kernel call plus the loop counter, measured by
``tools/hlo_census.py`` (committed budget ``partitioned_grow_fused``:
<= 10 dispatches/split vs the per-phase body's 78).

One layout: the partitioned learner's single row-major u8 training
matrix (``ops/hist_pallas.py``). ``fused_split_step_segment``
physically moves the chosen leaf's rows (stable partition,
``ops/partition_pallas.py`` semantics) and streams the smaller child's
contiguous segment. Two bodies sit behind the one wrapper:

* the **Mosaic TPU body** — real streamed DMA phases grounded in the
  per-phase kernels: ``partition_stream`` over the parent's rows, then
  ``hist_child_stream`` over the smaller child's compact segment alone
  (one-hot matmuls with exact bf16 hi/lo payload pairs; the kernels
  are VPU-bound, not HBM-bound, so a second read of the child's rows
  is cheap and a one-hot over the rows of the other child is not),
  f32 one-hot lane selects instead of the i32 reductions this jax's
  Mosaic cannot lower, the split-scan core from
  ``ops/split_scan_pallas.py``. Numerical, unbundled, byte-bin scope;
  anything else raises here.
* the **interpret-mode CPU twin** — the SAME pallas_call contract, but
  the body replicates the per-phase body bit-for-bit by calling the
  exact shared helpers it calls (``histogram_segment``,
  ``partition_decision_lut``, ``make_scan_leaf``, ``scan_split_pair``,
  ``StatePack.set_state_cols``/``set_tree_col``) on ref-loaded values.
  Models trained through the twin are therefore byte-identical to the
  per-phase body by construction — the contract
  ``tests/test_split_megakernel.py`` pins across bagging, categorical,
  linear_tree and monotone configs. The twin covers the FULL
  ``ops/split.py`` semantics (categorical + monotone paths).

This module holds kernels, their wrappers and the limits that are
facts about the kernel (``FUSED_BLK``, ``SEG_BLK``; the width it takes
is ``hist_pallas.MAX_FUSED_F``, the unrolled histogram stream's).
It does not know its caller: the packed carry (``StatePack``) and the
comm arrive as arguments, and whether the kernel runs at all is
``learner/split_step.py`` ``plan_split_step``'s decision.
``lower_for_tpu`` runs the real Mosaic lowering pass host-side
(``.trace().lower(lowering_platforms=("tpu",))``) for the CPU tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jit_registry import register_jit
from .hist_pallas import MAX_FUSED_F, hist_child_stream
from .split import (MISSING_NAN_CODE, MISSING_ZERO_CODE, FeatureMeta,
                    child_columns, child_constraints,
                    child_constraints_mono, kEpsilon, make_scan_leaf,
                    order_child_pair, scan_split_pair, set_bitsets,
                    split_node_updates)

NEG_INF = float("-inf")  # python scalar: kernels fold it as a constant

# the megakernel runs a static 2-step grid (phase 0: the partition
# stream over the parent's rows, then the histogram stream over the
# smaller child's segment; phase 1: sibling subtraction + both
# children's scans + state/tree writes). Two steps also keep the
# interpret twin's grid loop a real ``while`` in the compiled CPU HLO
# (a 1-trip loop is inlined by XLA's simplifier), so the whole split
# censuses as ONE dispatch — exactly what it is on TPU.
FUSED_PHASES = 2

FUSED_BLK = 2048          # row block of the compiled streaming phases
SEG_BLK = 512             # compiled segment-partition block (the tri
#                           permutation matmuls scale O(blk^2))
ALIGN = 8                 # Mosaic u8/row DMA offset granule
VMEM_LIMIT = 100 * 1024 * 1024

_COMPILER_PARAMS = pltpu.CompilerParams(
    has_side_effects=True, vmem_limit_bytes=VMEM_LIMIT)

# imeta table columns (one [F, 8] i32 operand instead of eight [F]
# gathers per split)
IM_NBINS, IM_MISS, IM_DEFBIN, IM_MOSTFREQ, IM_MONO, IM_GROUP, \
    IM_OFFSET, IM_ISCAT = range(8)


def pack_meta_tables(meta: FeatureMeta, feature_mask):
    """FeatureMeta + per-tree feature mask -> (imeta [F, 8] i32,
    fmeta [F, 2] f32) kernel operands. Built once per grow trace
    (loop-invariant; XLA hoists them out of the while body)."""
    f = meta.num_bins.shape[0]
    zeros = jnp.zeros((f,), jnp.int32)
    group = meta.group if meta.group is not None else jnp.arange(f)
    offset = meta.offset if meta.offset is not None else zeros
    imeta = jnp.stack(
        [meta.num_bins, meta.missing, meta.default_bin,
         meta.most_freq_bin, meta.monotone, group, offset,
         meta.is_categorical.astype(jnp.int32)], axis=1).astype(
        jnp.int32)
    fmeta = jnp.stack([meta.penalty,
                       feature_mask.astype(jnp.float32)], axis=1)
    return imeta, fmeta


def compiled_hist_cache(root_hist, big_l: int):
    """Per-leaf histogram cache of the COMPILED bodies: channels-major
    ``[L, 3, F8, B128]``, root in row 0. Every plane the kernel
    touches is a static-leading-index slab, and F and B are padded
    (pad rows/lanes stay zero) because Mosaic moves a leaf's slab by
    DMA and a slab's last two dims must fill whole (8, 128) tiles
    ("Slice shape along dimension 2 must be aligned to tiling (8),
    but is 28"). The interpret twin keeps the foil's
    ``[L, F, B, 3]``."""
    f, b, _ = root_hist.shape
    fp, bp = -(-f // 8) * 8, -(-b // 128) * 128
    root = jnp.pad(jnp.moveaxis(root_hist, -1, 0),
                   ((0, 0), (0, fp - f), (0, bp - b)))
    return jnp.zeros((big_l, 3, fp, bp), jnp.float32).at[0].set(root)


def _pad_meta_tables(imeta, fmeta, fp: int):
    """Pad the kernel meta tables to the cache's feature rows: a pad
    feature has no bins and a zero feature mask, so it never scores."""
    pad = fp - imeta.shape[0]
    return (jnp.pad(imeta, ((0, pad), (0, 0))),
            jnp.pad(fmeta, ((0, pad), (0, 0))))


def _meta_from_tables(imeta, fmeta):
    """Kernel-side FeatureMeta reconstruction (ref values in, the same
    NamedTuple the shared scan helpers consume out)."""
    f = imeta.shape[0]
    return FeatureMeta(
        num_bins=imeta[:, IM_NBINS], missing=imeta[:, IM_MISS],
        default_bin=imeta[:, IM_DEFBIN],
        most_freq_bin=imeta[:, IM_MOSTFREQ],
        monotone=imeta[:, IM_MONO],
        penalty=fmeta[:, 0],
        is_categorical=imeta[:, IM_ISCAT].astype(bool),
        group=imeta[:, IM_GROUP], offset=imeta[:, IM_OFFSET],
        global_id=jnp.arange(f, dtype=jnp.int32)), fmeta[:, 1] > 0


# =====================================================================
# interpret-mode CPU twin body
# =====================================================================

def _twin_split_site(pack, s_ref, t_ref, bsb_ref, cbs_ref, k, big_l):
    """Leaf pick + split-site read on ref-loaded values — the exact
    ops the foil body runs (``jnp.argmax`` over the masked gain row,
    one ``read_site`` column slice)."""
    st = {"S": s_ref[...], "T": t_ref[...]}
    if bsb_ref is not None:
        st["bs_bitset"] = bsb_ref[...]
        st["cat_bitsets"] = cbs_ref[...]
    view = pack.view(st)
    open_gain = jnp.where(jnp.arange(big_l) < k, view["bs_gain"],
                          -jnp.inf)
    leaf = jnp.argmax(open_gain).astype(jnp.int32)
    site = pack.read_site(st, leaf)
    bitset = view["bs_bitset"][leaf]
    return st, view, leaf, site, bitset


def _twin_finish(pack, params, meta, fmask, comm, st, site, leaf, new,
                 s, k, gain, feat, thr, dleft, is_cat, hist_small,
                 hist_other, small_is_left, *, bundled, has_monotone,
                 max_depth, extra_a=None, extra_b=None):
    """Tail of the twin: both children's scans + the packed
    state/tree/bitset writes, via the SAME helpers the per-phase body
    calls (ops/split.py) so every value is bit-identical."""
    inf = jnp.float32(jnp.inf)
    lg, lh, lc = site["bs_lg"], site["bs_lh"], site["bs_lc"]
    pg, ph, pc = site["leaf_g"], site["leaf_h"], site["leaf_c"]
    rg, rh, rc = pg - lg, ph - lh, pc - lc
    lout, rout = site["bs_lout"], site["bs_rout"]
    pcmin = site.get("leaf_cmin", -inf)
    pcmax = site.get("leaf_cmax", inf)
    depth = site["leaf_depth"] + 1

    cmin_l, cmax_l, cmin_r, cmax_r = child_constraints(
        meta, feat, is_cat, lout, rout, pcmin, pcmax, has_monotone)
    scan_leaf = make_scan_leaf(comm, meta, params, fmask,
                               lambda salt: (None, None), bundled,
                               max_depth)
    idx_a = jnp.where(small_is_left, leaf, new)
    idx_b = jnp.where(small_is_left, new, leaf)
    o, split_a, split_b = scan_split_pair(
        comm, scan_leaf, small_is_left, k, depth, hist_small,
        hist_other, lg, lh, lc, rg, rh, rc, lout, rout,
        cmin_l, cmax_l, cmin_r, cmax_r)
    fa, ia = child_columns(split_a, o["ga"], o["ha"], o["ca"],
                           o["out_a"], o["cmin_a"], o["cmax_a"],
                           s, o["side_a"], depth,
                           extra_i=extra_a(idx_a) if extra_a else None)
    fb, ib = child_columns(split_b, o["gb"], o["hb"], o["cb"],
                           o["out_b"], o["cmin_b"], o["cmax_b"],
                           s, o["side_b"], depth,
                           extra_i=extra_b(idx_b) if extra_b else None)
    treef, treei, pnode, upd = split_node_updates(
        params, gain, feat, thr, dleft, is_cat, pg, ph, pc,
        site["ref_node"], leaf, new)
    upds = pack.set_state_cols(st, idx_a, idx_b, fa, fb, ia, ib)
    upds.update(pack.set_tree_col(st, s, treef, treei, pnode, upd,
                                  site["ref_side"]))
    view = pack.view(st)
    upds.update(set_bitsets(pack, view, idx_a, idx_b,
                            split_a.cat_bitset, split_b.cat_bitset, s,
                            view["bs_bitset"][leaf]))
    return upds, idx_a, idx_b


def _segment_kernel_ref(iscal, s_in, t_in, mat_in, ws_in, hist_in,
                        imeta_ref, fmeta_ref,
                        s_out, t_out, mat_out, ws_out, hist_out,
                        *, params, pack, comm, big_l, max_depth, b, f,
                        n, bundled, has_monotone, blk,
                        bsb_in=None, cbs_in=None, bsb_out=None,
                        cbs_out=None):
    """Interpret twin: the partitioned per-phase body on
    ref-loaded values. The stable partition is computed as an exact
    prefix-sum permutation (bit-identical row content to
    ``partition_segment``); the smaller child's histogram reuses the
    SAME interpret-mode histogram stream the foil runs
    (``hist_pallas.histogram_segment``), so the float accumulation
    order — and therefore the model — is bit-identical."""
    del s_in, t_in, mat_in, ws_in, hist_in
    from .hist_pallas import histogram_segment
    from .partition_pallas import partition_decision_lut

    @pl.when(pl.program_id(0) == 0)
    def _():
        meta, fmask = _meta_from_tables(imeta_ref[...], fmeta_ref[...])
        k = iscal[0]
        new = k
        s = k - 1
        st, view, leaf, site, bitset = _twin_split_site(
            pack, s_out, t_out, bsb_out, cbs_out, k, big_l)
        feat = site["bs_feat"]
        thr = site["bs_thr"]
        dleft = site["bs_dleft"]
        gain = site["bs_gain"]
        is_cat = site["bs_iscat"]
        lc = site["bs_lc"]
        rc = site["leaf_c"] - lc
        begin = site["leaf_begin"]
        cnt = site["leaf_cnt"]

        # ---- stable in-place partition of [begin, begin+cnt) --------
        # the EXACT decision of partition_pallas._partition_kernel
        # (shared LUT construction; group-bin-space missing handling),
        # applied as an exact integer prefix-sum permutation — bitwise
        # the same row content the v1 kernel produces
        grp_col, use_lut, lut = partition_decision_lut(
            meta, feat, thr, dleft, is_cat, bitset, bundled)
        mat = mat_out[...]
        npad = mat.shape[0]
        pos = jnp.arange(npad)
        in_seg = (pos >= begin) & (pos < begin + cnt)
        bv = jnp.take(mat, grp_col, axis=1).astype(jnp.int32)
        miss = meta.missing[feat]
        is_missing = jnp.where(
            miss == MISSING_ZERO_CODE, bv == meta.default_bin[feat],
            jnp.where(miss == MISSING_NAN_CODE,
                      bv == meta.num_bins[feat] - 1, False))
        num_left = jnp.where(is_missing, dleft.astype(bool),
                             bv <= thr)
        cat_left = jnp.take(lut[0], jnp.clip(bv, 0, 255)) > 0.5
        go_left = jnp.where(use_lut, cat_left, num_left)
        sel_l = in_seg & go_left
        sel_r = in_seg & ~go_left
        nl = sel_l.sum().astype(jnp.int32)
        dst = jnp.where(
            sel_l, begin + jnp.cumsum(sel_l) - 1,
            jnp.where(sel_r, begin + nl + jnp.cumsum(sel_r) - 1, pos))
        mat2 = jnp.zeros_like(mat).at[dst].set(mat)
        mat_out[...] = mat2
        nr = cnt - nl

        # ---- smaller-child segment histogram + subtraction ----------
        # the SAME interpret histogram stream the foil runs — nested
        # pallas_call, bit-identical block accumulation order
        small_is_left = lc <= rc
        sb = jnp.where(small_is_left, begin, begin + nl)
        sc = jnp.where(small_is_left, nl, nr)
        hist_small = histogram_segment(mat2, sb, sc, b, f, blk=blk,
                                       interpret=True)
        parent_hist = hist_out[leaf]
        hist_other = parent_hist - hist_small

        begin_b = jnp.where(small_is_left, begin + nl, begin)
        cnt_b = cnt - sc

        upds, idx_a, idx_b = _twin_finish(
            pack, params, meta, fmask, comm, st, site, leaf,
            new, s, k, gain, feat, thr, dleft, is_cat, hist_small,
            hist_other, small_is_left, bundled=bundled,
            has_monotone=has_monotone, max_depth=max_depth,
            extra_a=lambda _i: dict(leaf_begin=sb, leaf_cnt=sc),
            extra_b=lambda _i: dict(leaf_begin=begin_b,
                                    leaf_cnt=cnt_b))
        s_out[...] = upds["S"]
        t_out[...] = upds["T"]
        hist_out[idx_a] = hist_small
        hist_out[idx_b] = hist_other
        if bsb_out is not None:
            bsb_out[...] = upds["bs_bitset"]
            cbs_out[...] = upds["cat_bitsets"]


# =====================================================================
# wrappers
# =====================================================================

def _whole(shape):
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)


def _smem_spec(shape):
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd,
                        memory_space=pltpu.SMEM)


def _call_common(alias_pairs, interpret):
    return dict(
        grid=(FUSED_PHASES,),
        input_output_aliases=dict(alias_pairs),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )


@register_jit("fused_split_step_segment",
              donate=("S", "T", "mat", "ws", "hist"))
@functools.partial(
    jax.jit,
    static_argnames=("params", "pack", "comm", "big_l", "max_depth",
                     "b", "f", "n", "bundled", "has_monotone", "blk",
                     "interpret"),
    donate_argnames=("S", "T", "mat", "ws", "hist"))
def fused_split_step_segment(k, S, T, mat, ws, hist, imeta, fmeta,
                             bsb=None, cbs=None, *, params, pack,
                             big_l, max_depth, b, f, n, bundled,
                             has_monotone, comm=None, blk=FUSED_BLK,
                             interpret=True):
    """ONE whole split of the partitioned grow loop as one
    ``pallas_call`` over the training matrix (``mat``/``ws`` aliased
    in place like ``partition_segment``).

    Carry in/out (aliased, donated): merged state ``S`` [Ks, L] i32,
    tree arrays ``T`` [Kt, L-1] i32 (float rows bitcast), ``mat`` /
    ``ws`` the training matrix and its workspace, ``hist`` the per-leaf
    histogram cache (+ the categorical ``bsb``/``cbs`` bitset arrays
    when the config carries them). Read-only: ``imeta``/``fmeta``
    metadata tables. ``k`` is the split index (new leaf id). ``pack``
    is the grow loop's merged ``StatePack`` (static: the rows of ``S``
    and ``T`` by name); ``comm`` the learner's comm, read by the
    interpret twin's scans (the plan admits the serial one only). The
    interpret twin keeps the per-phase body's ``[L, F, B, 3]``
    histogram cache; the compiled path takes ``compiled_hist_cache``'s
    padded channels-major layout."""
    iscal = jnp.reshape(jnp.asarray(k, jnp.int32), (1,))
    has_cat = bsb is not None
    if interpret:
        ins = [iscal, S, T, mat, ws, hist, imeta, fmeta]
        out_shape = [jax.ShapeDtypeStruct(S.shape, S.dtype),
                     jax.ShapeDtypeStruct(T.shape, T.dtype),
                     jax.ShapeDtypeStruct(mat.shape, mat.dtype),
                     jax.ShapeDtypeStruct(ws.shape, ws.dtype),
                     jax.ShapeDtypeStruct(hist.shape, hist.dtype)]
        alias = [(1, 0), (2, 1), (3, 2), (4, 3), (5, 4)]
        kern = functools.partial(
            _segment_kernel_ref,
            params=params, pack=pack, comm=comm, big_l=big_l,
            max_depth=max_depth, b=b, f=f, n=n, bundled=bundled,
            has_monotone=has_monotone, blk=blk)
        if has_cat:
            ins += [bsb, cbs]
            out_shape += [jax.ShapeDtypeStruct(bsb.shape, bsb.dtype),
                          jax.ShapeDtypeStruct(cbs.shape, cbs.dtype)]
            alias += [(8, 5), (9, 6)]

            def kern2(iscal, s_i, t_i, m_i, w_i, h_i, im, fm, bsb_i,
                      cbs_i, s_o, t_o, m_o, w_o, h_o, bsb_o, cbs_o,
                      *scr):
                return kern(iscal, s_i, t_i, m_i, w_i, h_i, im, fm,
                            s_o, t_o, m_o, w_o, h_o, *scr,
                            bsb_in=bsb_i, cbs_in=cbs_i, bsb_out=bsb_o,
                            cbs_out=cbs_o)
        else:
            kern2 = kern
        in_specs = [_smem_spec(iscal.shape)] + \
            [_whole(x.shape) for x in ins[1:]]
        out_specs = [_whole(s.shape) for s in out_shape]
        res = pl.pallas_call(
            kern2,
            out_shape=out_shape,
            in_specs=in_specs,
            out_specs=out_specs,
            **_call_common(alias, interpret),
        )(*ins)
        return tuple(res)

    # ---- compiled Mosaic path (numerical unbundled fast path) -------
    if has_cat or params.has_categorical or bundled:
        raise NotImplementedError(
            "fused split-step Mosaic body covers the numerical "
            "unbundled fast path; categorical/EFB configs use the "
            "per-phase kernels")
    if b > 256 or f > MAX_FUSED_F:
        raise NotImplementedError(
            f"fused split-step Mosaic body: b={b} f={f} exceeds the "
            f"u8-bin / {MAX_FUSED_F}-feature static scope")
    from .partition_pallas import stream_scratch
    seg_blk = SEG_BLK
    cols = mat.shape[1]
    fp, bp = hist.shape[2:]        # compiled_hist_cache's padded dims
    imeta, fmeta = _pad_meta_tables(imeta, fmeta, fp)
    ins = [iscal, S, T, mat, ws, hist, imeta, fmeta]
    out_shape = [jax.ShapeDtypeStruct(S.shape, S.dtype),
                 jax.ShapeDtypeStruct(T.shape, T.dtype),
                 jax.ShapeDtypeStruct(mat.shape, mat.dtype),
                 jax.ShapeDtypeStruct(ws.shape, ws.dtype),
                 jax.ShapeDtypeStruct(hist.shape, hist.dtype)]
    alias = [(1, 0), (2, 1), (3, 2), (4, 3), (5, 4)]
    kern = functools.partial(
        _segment_kernel_tpu,
        params=params, pack=pack, big_l=big_l,
        max_depth=max_depth, b=b, f=f, n=n, bundled=bundled,
        has_monotone=has_monotone, blk=seg_blk)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [_smem_spec(iscal.shape), _whole(S.shape),
                _whole(T.shape), any_spec, any_spec, any_spec,
                _whole(imeta.shape), _whole(fmeta.shape)]
    out_specs = [_whole(S.shape), _whole(T.shape), any_spec, any_spec,
                 any_spec]
    scratch = stream_scratch(seg_blk, cols) + [
        pltpu.VMEM((5, fp, bp), jnp.float32),        # hpl planes
        pltpu.VMEM((3, fp, bp), jnp.float32),        # pbuf parent
        pltpu.VMEM((2, 3, fp, bp), jnp.float32),     # cbuf children
        pltpu.SMEM((1,), jnp.int32),                 # nl carry
        pltpu.SemaphoreType.DMA((2,)),               # sem_w
    ]
    res = pl.pallas_call(
        kern,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        **_call_common(alias, interpret),
    )(*ins)
    return tuple(res)


def lower_for_tpu(pack, *, big_l: int) -> None:
    """Trace + Mosaic-lower the compiled kernel body at a tiny
    canonical shape (no TPU needed — the same mechanism as
    tests/test_mosaic_lowering.py). ``pack``: the merged ``StatePack``
    of a numeric table at ``big_l`` leaves
    (``learner/partitioned.py`` ``segment_grow_pack``). Raises what
    Mosaic raises."""
    from .hist_pallas import matrix_cols, matrix_rows
    from .split import SplitParams
    params = SplitParams(
        lambda_l1=0.0, lambda_l2=1.0, max_delta_step=0.0,
        min_data_in_leaf=1.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0, any_missing=False)
    f, b, n = 8, 16, FUSED_BLK
    S = jnp.zeros((len(pack.sf_fields) + len(pack.si_fields), big_l),
                  jnp.int32)
    T = jnp.zeros((len(pack.tf_fields) + len(pack.ti_fields),
                   big_l - 1), jnp.int32)
    hist = compiled_hist_cache(jnp.zeros((f, b, 3), jnp.float32),
                               big_l)
    mat = jnp.zeros((matrix_rows(n, FUSED_BLK), matrix_cols(f)),
                    jnp.uint8)
    fn = functools.partial(
        fused_split_step_segment, params=params, pack=pack,
        big_l=big_l, max_depth=-1, b=b, f=f, n=n, bundled=False,
        has_monotone=False, blk=FUSED_BLK, interpret=False)
    # probe-only jit: never dispatched, exists to run Mosaic lowering
    jax.jit(fn).trace(  # graftlint: allow[GL506]
        jnp.int32(1), S, T, mat, jnp.zeros_like(mat), hist,
        jnp.zeros((f, 8), jnp.int32),
        jnp.ones((f, 2), jnp.float32)).lower(
        lowering_platforms=("tpu",))


# =====================================================================
# Mosaic TPU body (compiled path; numerical-only scope)
# =====================================================================
#
# Lowering discipline (this jax's Mosaic): no integer reductions (all
# lane/row extractions are f32 select-sums — exact, every integer in
# the state is < 2^24), no dynamic gathers (select-sum again), no
# transposes (the hist accumulates per-feature [8, B] slabs and the
# per-leaf histogram cache rides CHANNELS-MAJOR [L, 3, F, B] on the
# compiled path so every plane is a static-leading-index slice), bool
# vectors only as compare->select intermediates.

def _iota_f32(shape, dim):
    """f32 index grid. Mosaic's ``tpu.iota`` yields integer vectors
    only, so build it as i32 and convert (exact below 2^24)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(
        jnp.float32)


def _packed_column(fvals, ivals):
    """[K, 1] i32 column of the packed state/tree matrices
    (learner/split_step.py StatePack: the carrier is i32, float rows
    bitcast) from f32 and i32 scalars. Both halves are assembled as
    full-height VECTORS and merged by a row select: Mosaic's
    tpu.bitcast accepts vectors only, and a sublane concatenate at an
    unaligned row would need a relayout."""
    nf, kk = len(fvals), len(fvals) + len(ivals)
    rio = jax.lax.broadcasted_iota(jnp.int32, (kk, 1), 0)
    colf = jnp.zeros((kk, 1), jnp.float32)
    for j, v in enumerate(fvals):
        colf = jnp.where(rio == j, jnp.asarray(v, jnp.float32), colf)
    coli = jnp.zeros((kk, 1), jnp.int32)
    for j, v in enumerate(ivals):
        coli = jnp.where(rio == nf + j, jnp.asarray(v, jnp.int32), coli)
    return jnp.where(rio < nf,
                     jax.lax.bitcast_convert_type(colf, jnp.int32),
                     coli)


def _select_sum(row, lane_iota, idx_f):
    """Exact scalar extraction ``row[idx]`` without a dynamic gather:
    select-then-sum (select, not multiply — masked -inf/NaN lanes must
    not poison the sum)."""
    return jnp.sum(jnp.where(lane_iota == idx_f, row, 0.0))


class _SiteTPU:
    """Split-site reads on the merged i32 state matrix inside the
    Mosaic body. Rows load from the ref one at a time (static
    single-row loads; no unaligned sublane slicing of a value): float
    rows bitcast, int rows through an exact f32 convert, then a
    select-sum picks the leaf's lane."""

    def __init__(self, pack, s_ref, big_l):
        self.pack = pack
        self.s_ref = s_ref
        self.nf = len(pack.sf_fields)
        self.lane = _iota_f32((1, big_l), 1)

    def row_f(self, name):
        i = self.pack.sf_idx[name]
        return jax.lax.bitcast_convert_type(
            self.s_ref[i:i + 1, :], jnp.float32)          # [1, L]

    def f(self, name, leaf_f):
        return _select_sum(self.row_f(name), self.lane, leaf_f)

    def i_f(self, name, leaf_f):
        """Int field as an exact f32 scalar (|v| < 2^24)."""
        i = self.nf + self.pack.si_idx[name]
        row = self.s_ref[i:i + 1, :].astype(jnp.float32)
        return _select_sum(row, self.lane, leaf_f)


def _imeta_col_f(imeta_ref, col, fio, feat_f):
    """One int meta field of the split feature as an exact f32
    scalar (a column load from the ref + a select-sum)."""
    return _select_sum(
        imeta_ref[:, col:col + 1].astype(jnp.float32), fio, feat_f)


def _state_column(pack, fd, idd):
    """[Ks, 1] packed state column from the child_columns dicts."""
    return _packed_column([fd[name] for name in pack.sf_fields],
                          [idd[name] for name in pack.si_fields])


def _tree_column(pack, treef, treei):
    return _packed_column([treef[name] for name in pack.tf_fields],
                          [treei[name] for name in pack.ti_fields])


def _best_feature(out, f):
    """assemble_split on the scan_core [F, 8] table, gather-free:
    first-index argmax + per-column select-sums."""
    from .split_scan_pallas import (O_SCORE, O_THR, O_LG, O_LH, O_LC,
                                    O_DLEFT, O_WL, O_WR)
    fio = _iota_f32((f, 1), 0)
    score = out[:, O_SCORE:O_SCORE + 1]
    best = jnp.max(score)
    fidx = jnp.min(jnp.where(score == best, fio, jnp.float32(f)))

    def col(j):
        return _select_sum(out[:, j:j + 1], fio, fidx)

    return dict(gain=col(O_SCORE), feature=fidx.astype(jnp.int32),
                threshold=col(O_THR).astype(jnp.int32),
                default_left=col(O_DLEFT) > 0.5,
                left_g=col(O_LG), left_h=col(O_LH) - kEpsilon,
                left_c=col(O_LC), left_output=col(O_WL),
                right_output=col(O_WR))


class _SplitScalars:
    """Duck-typed stand-in for ops.split.SplitResult inside the Mosaic
    body (child_columns only reads attributes)."""

    def __init__(self, d):
        self.gain = d["gain"]
        self.feature = d["feature"]
        self.threshold = d["threshold"]
        self.default_left = d["default_left"]
        self.left_g = d["left_g"]
        self.left_h = d["left_h"]
        self.left_c = d["left_c"]
        self.left_output = d["left_output"]
        self.right_output = d["right_output"]
        self.is_cat = jnp.bool_(False)
        self.cat_bitset = None


def _scan_and_write_phase(pack, params, iscal, s_in, t_in, imeta_ref,
                          fmeta_ref, s_out, t_out, g_sm, h_sm, c_sm,
                          pbuf, cbuf, hist_out, sem_w, *, big_l,
                          max_depth, b, f, has_monotone,
                          extra_ab=None):
    """Phase-1 tail of the Mosaic body: sibling subtraction, both
    children's scan_core runs, best-feature extraction, and the packed
    state/tree/hist writes. ``extra_ab(site, leaf_f, small_is_left)``
    returns the segment-bound int fields of each child."""
    from .split_scan_pallas import scan_core

    k = iscal[0]
    new = k
    s = k - 1
    site = _SiteTPU(pack, s_in, big_l)
    kf = k.astype(jnp.float32)
    open_gain = jnp.where(site.lane < kf, site.row_f("bs_gain"),
                          NEG_INF)
    best = jnp.max(open_gain)
    leaf_f = jnp.min(jnp.where(open_gain == best, site.lane,
                               jnp.float32(big_l)))
    leaf = leaf_f.astype(jnp.int32)

    gain = site.f("bs_gain", leaf_f)
    lg = site.f("bs_lg", leaf_f)
    lh = site.f("bs_lh", leaf_f)
    lc = site.f("bs_lc", leaf_f)
    lout = site.f("bs_lout", leaf_f)
    rout = site.f("bs_rout", leaf_f)
    pg = site.f("leaf_g", leaf_f)
    ph = site.f("leaf_h", leaf_f)
    pc = site.f("leaf_c", leaf_f)
    feat = site.i_f("bs_feat", leaf_f).astype(jnp.int32)
    feat_f = site.i_f("bs_feat", leaf_f)
    thr = site.i_f("bs_thr", leaf_f).astype(jnp.int32)
    dleft = site.i_f("bs_dleft", leaf_f) > 0.5
    ref_node = site.i_f("ref_node", leaf_f).astype(jnp.int32)
    pside = site.i_f("ref_side", leaf_f).astype(jnp.int32)
    depth = site.i_f("leaf_depth", leaf_f).astype(jnp.int32) + 1
    if has_monotone:
        pcmin = site.f("leaf_cmin", leaf_f)
        pcmax = site.f("leaf_cmax", leaf_f)
    else:
        pcmin = jnp.float32(-jnp.inf)
        pcmax = jnp.float32(jnp.inf)
    is_cat = jnp.bool_(False)

    rg, rh, rc = pg - lg, ph - lh, pc - lc
    small_is_left = lc <= rc
    idx_a = jnp.where(small_is_left, leaf, new)
    idx_b = jnp.where(small_is_left, new, leaf)

    # sibling subtraction (channels-major parent slab)
    g_ot = pbuf[0] - g_sm
    h_ot = pbuf[1] - h_sm
    c_ot = pbuf[2] - c_sm

    fio = _iota_f32((f, 1), 0)
    mono_feat = _imeta_col_f(imeta_ref, IM_MONO, fio, feat_f) \
        .astype(jnp.int32)
    cmin_l, cmax_l, cmin_r, cmax_r = child_constraints_mono(
        mono_feat, is_cat, lout, rout, pcmin, pcmax) \
        if has_monotone else (pcmin, pcmax, pcmin, pcmax)

    o = order_child_pair(small_is_left, k, lg, lh, lc, rg, rh, rc,
                         lout, rout, cmin_l, cmax_l, cmin_r, cmax_r)

    nb_col = imeta_ref[:, IM_NBINS:IM_NBINS + 1]
    miss_col = imeta_ref[:, IM_MISS:IM_MISS + 1]
    defbin_col = imeta_ref[:, IM_DEFBIN:IM_DEFBIN + 1]
    mono_col = imeta_ref[:, IM_MONO:IM_MONO + 1]
    pen_col = fmeta_ref[:, 0:1]
    fmask_col = fmeta_ref[:, 1:2]

    def scan(gch, hch, cch, gpar, hpar, cpar, cmin, cmax):
        return scan_core(gpar, hpar, cpar, cmin, cmax, nb_col,
                         miss_col, defbin_col, mono_col, pen_col,
                         fmask_col, gch, hch, cch, f=f, b=b, p=params)

    out_a = scan(g_sm, h_sm, c_sm, o["ga"], o["ha"], o["ca"],
                 o["cmin_a"], o["cmax_a"])
    out_b = scan(g_ot, h_ot, c_ot, o["gb"], o["hb"], o["cb"],
                 o["cmin_b"], o["cmax_b"])
    blocked = jnp.bool_(max_depth > 0) & (depth >= max_depth)
    sa = _best_feature(out_a, f)
    sb = _best_feature(out_b, f)
    sa["gain"] = jnp.where(blocked, NEG_INF, sa["gain"])
    sb["gain"] = jnp.where(blocked, NEG_INF, sb["gain"])

    extra_a = extra_b = None
    if extra_ab is not None:
        extra_a, extra_b = extra_ab(site, leaf_f, small_is_left)
    fa, ia = child_columns(_SplitScalars(sa), o["ga"], o["ha"],
                           o["ca"], o["out_a"], o["cmin_a"],
                           o["cmax_a"], s, o["side_a"], depth,
                           extra_i=extra_a)
    fb, ib = child_columns(_SplitScalars(sb), o["gb"], o["hb"],
                           o["cb"], o["out_b"], o["cmin_b"],
                           o["cmax_b"], s, o["side_b"], depth,
                           extra_i=extra_b)
    treef, treei, pnode, upd = split_node_updates(
        params, gain, feat, thr, dleft, is_cat, pg, ph, pc, ref_node,
        leaf, new)

    # ---- packed state/tree writes (lane selects == foil scatters) ---
    col_a = _state_column(pack, fa, ia)
    col_b = _state_column(pack, fb, ib)
    idx_a_f = idx_a.astype(jnp.float32)
    idx_b_f = idx_b.astype(jnp.float32)
    s_out[...] = jnp.where(
        site.lane == idx_a_f, col_a,
        jnp.where(site.lane == idx_b_f, col_b, s_in[...]))

    kt = len(pack.tf_fields) + len(pack.ti_fields)
    lane_t = _iota_f32((1, big_l - 1), 1)
    rio_t = _iota_f32((kt, 1), 0)
    s_f = s.astype(jnp.float32)
    T2 = jnp.where(lane_t == s_f, _tree_column(pack, treef, treei),
                   t_in[...])
    r0 = len(pack.tf_fields) + pack.ti_idx["left_child"]
    pnode_f = pnode.astype(jnp.float32)
    ptr = jnp.asarray(s, jnp.int32)
    for side in (0, 1):
        # scalar select first: only vector compares meet in the mask
        hit = jnp.where(upd & (pside == side), pnode_f, -1.0)
        T2 = jnp.where((rio_t == r0 + side) & (lane_t == hit), ptr, T2)
    t_out[...] = T2

    # ---- children -> channels-major per-leaf histogram cache --------
    cbuf[0, 0] = g_sm
    cbuf[0, 1] = h_sm
    cbuf[0, 2] = c_sm
    cbuf[1, 0] = g_ot
    cbuf[1, 1] = h_ot
    cbuf[1, 2] = c_ot
    cp = pltpu.make_async_copy(cbuf.at[0], hist_out.at[idx_a],
                               sem_w.at[0])
    cp.start()
    cp.wait()
    cp = pltpu.make_async_copy(cbuf.at[1], hist_out.at[idx_b],
                               sem_w.at[1])
    cp.start()
    cp.wait()


def _go_left01(bv, thr_f, dleft_f, miss_f, defbin_f, nbins_f):
    """[rows, 1] i32 0/1 "row goes left" from the split feature's bin
    per row (``ops/partition.py`` rows_go_left semantics). The mask
    stays i32, as in ``partition_pallas``: a select between bool
    vectors makes Mosaic narrow i8 to i1, which it refuses
    ("Unsupported target bitwidth for truncation")."""
    # the one bin value that means "missing" for this feature (-2.0:
    # none) and where it goes: scalar selects
    miss_bin = jnp.where(
        miss_f == float(MISSING_ZERO_CODE), defbin_f,
        jnp.where(miss_f == float(MISSING_NAN_CODE), nbins_f - 1.0,
                  -2.0))
    dleft = jnp.where(dleft_f > 0.5, 1, 0)
    return jnp.where(bv == miss_bin, dleft,
                     jnp.where(bv <= thr_f, 1, 0))


def _leaf_site_scalars(pack, iscal, s_in, imeta_ref, big_l):
    """Phase-0 split-site scalars: chosen leaf + partition decision
    inputs, all f32 (gather-free select-sums)."""
    k = iscal[0]
    site = _SiteTPU(pack, s_in, big_l)
    kf = k.astype(jnp.float32)
    open_gain = jnp.where(site.lane < kf, site.row_f("bs_gain"),
                          NEG_INF)
    best = jnp.max(open_gain)
    leaf_f = jnp.min(jnp.where(open_gain == best, site.lane,
                               jnp.float32(big_l)))
    leaf = leaf_f.astype(jnp.int32)
    lc = site.f("bs_lc", leaf_f)
    pc = site.f("leaf_c", leaf_f)
    small_is_left = lc <= (pc - lc)
    sm = jnp.where(small_is_left, leaf, k)
    feat_f = site.i_f("bs_feat", leaf_f)
    thr_f = site.i_f("bs_thr", leaf_f)
    dleft_f = site.i_f("bs_dleft", leaf_f)
    f = imeta_ref.shape[0]
    fio = _iota_f32((f, 1), 0)
    miss_f = _imeta_col_f(imeta_ref, IM_MISS, fio, feat_f)
    defbin_f = _imeta_col_f(imeta_ref, IM_DEFBIN, fio, feat_f)
    nbins_f = _imeta_col_f(imeta_ref, IM_NBINS, fio, feat_f)
    return leaf, k, sm, feat_f, thr_f, dleft_f, miss_f, defbin_f, \
        nbins_f


def _segment_kernel_tpu(iscal, s_in, t_in, mat_in, ws_in, hist_in,
                        imeta_ref, fmeta_ref,
                        s_out, t_out, mat_out, ws_out, hist_out,
                        *scratch,
                        params, pack, big_l, max_depth, b, f,
                        n, bundled, has_monotone, blk):
    """Mosaic body. Phase 0 streams the chosen leaf's contiguous row
    segment through the stable in-place partition
    (``partition_pallas.partition_stream``: the pipelined block stream
    ``partition_segment`` runs), and then, the left count known, the
    SMALLER child's compact segment alone through the histogram
    (``hist_child_stream``): the per-feature one-hot visits the
    child's rows only, 0.28-0.35 of the parent's in the benchmark's
    cells, for a second read of them (0.05 us a parent block against
    0.13 us a feature a block of one-hot, PERF.md section 5). Phase 1
    is the shared subtract/scan/write tail. All lane/row extractions
    are f32 select-sums (this Mosaic lowers no integer reductions —
    the one thing that kept partition v1 off-chip)."""
    del mat_in, ws_in, hist_in  # aliased; all access via out refs
    from .partition_pallas import partition_stream
    *stream, hpl, pbuf, cbuf, nl_ref, sem_w = scratch
    inbuf, sems = stream[0], stream[-1]
    pid = pl.program_id(0)
    cols = mat_out.shape[1]

    @pl.when(pid == 0)
    def _phase0():
        (leaf, new, sm, feat_f, thr_f, dleft_f, miss_f, defbin_f,
         nbins_f) = _leaf_site_scalars(pack, iscal, s_in, imeta_ref,
                                       big_l)
        site = _SiteTPU(pack, s_in, big_l)
        leaf_f = leaf.astype(jnp.float32)
        begin = site.i_f("leaf_begin", leaf_f).astype(jnp.int32)
        cnt = site.i_f("leaf_cnt", leaf_f).astype(jnp.int32)
        lc = site.f("bs_lc", leaf_f)
        pc = site.f("leaf_c", leaf_f)
        small_is_left = lc <= (pc - lc)

        # parent slab for phase 1 (channels-major cache row), read
        # behind the two streams
        parent = pltpu.make_async_copy(hist_out.at[leaf], pbuf,
                                       sem_w.at[1])
        parent.start()

        lane_w = _iota_f32((1, cols), 1)
        fsel = jnp.where(lane_w == feat_f, jnp.float32(1), 0.0)

        def decide(mat_i32, mat_f, valid):
            del mat_i32
            # split feature's bin per row: f32 one-hot lane reduce
            bv = jnp.sum(mat_f * fsel, axis=1,
                         keepdims=True)                  # [win, 1]
            go_left = _go_left01(bv, thr_f, dleft_f, miss_f, defbin_f,
                                 nbins_f)
            return valid * go_left, valid * (1 - go_left)

        nl_total, _ = partition_stream(mat_out, ws_out, stream, begin,
                                       cnt, decide, blk=blk)
        nl_ref[0] = nl_total

        # the smaller child's segment, as phase 1's ``extra_ab`` has
        # it; every write of the partition has landed, the stream's
        # input slots and their semaphores are free
        sb = jnp.where(small_is_left, begin, begin + nl_total)
        sc = jnp.where(small_is_left, nl_total, cnt - nl_total)
        hist_child_stream(mat_out, inbuf, sems, hpl, sb, sc, f=f,
                          blk=blk)
        parent.wait()

    @pl.when(pid == 1)
    def _phase1():
        g_sm = hpl[0] + hpl[1]
        h_sm = hpl[2] + hpl[3]
        c_sm = hpl[4]

        def extra_ab(site, leaf_f, small_is_left):
            nl = nl_ref[0]
            begin = site.i_f("leaf_begin", leaf_f).astype(jnp.int32)
            cnt = site.i_f("leaf_cnt", leaf_f).astype(jnp.int32)
            sb = jnp.where(small_is_left, begin, begin + nl)
            sc = jnp.where(small_is_left, nl, cnt - nl)
            begin_b = jnp.where(small_is_left, begin + nl, begin)
            return (dict(leaf_begin=sb, leaf_cnt=sc),
                    dict(leaf_begin=begin_b, leaf_cnt=cnt - sc))

        _scan_and_write_phase(
            pack, params, iscal, s_in, t_in,
            imeta_ref, fmeta_ref, s_out, t_out, g_sm, h_sm, c_sm,
            pbuf, cbuf, hist_out, sem_w, big_l=big_l,
            max_depth=max_depth, b=hpl.shape[2], f=hpl.shape[1],
            has_monotone=has_monotone, extra_ab=extra_ab)
