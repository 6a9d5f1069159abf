"""Pallas TPU kernel: in-place stable partition of a row segment.

Reference analog: ``DataPartition::Split`` (data_partition.hpp:101-120)
+ ``DenseBin::Split`` (dense_bin.hpp:132+). The reference reorders a
leaf's index array with a parallel stable partition; here the TRAINING
MATRIX ROWS THEMSELVES are moved (ops/hist_pallas.py layout: features +
gh payload + row-id bytes per row), so the histogram kernel can stream
each leaf as one contiguous segment.

Algorithm (sequential block stream over [begin, begin+count)):
  1. read a row block; pick the split feature's bin per row (one-hot
     lane reduction) and decide left/right (numerical threshold with
     missing handling, or categorical bitset via a 256-entry LUT
     matmul);
  2. stable-compact the block's left rows via a permutation matmul
     (PT[src, dst] one-hot x row block on the MXU — bin/payload bytes
     are exact in bf16) and write them at the left write head IN
     PLACE; rights go to a workspace buffer the same way;
  3. after the stream, copy the workspace back behind the lefts.

All writes use read-merge-write windows aligned to Mosaic's 8-row u8
granule, so segment boundaries can sit anywhere and neighbours' rows
survive. Prefix sums are triangular matmuls (no native cumsum).
Returns the left-row count NL; children are [begin, begin+NL) and
[begin+NL, begin+count).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jit_registry import register_jit

ALIGN = 8

# scalar input slots
S_BEGIN, S_COUNT, S_FEAT, S_THR, S_DLEFT, S_MISS, S_DEFBIN, S_NBINS, \
    S_ISCAT = range(9)

MISSING_NONE_CODE = 0
MISSING_ZERO_CODE = 1
MISSING_NAN_CODE = 2


def _partition_kernel(scal_ref, lut_ref, mat_in, ws_in,
                      mat_hbm, ws_hbm, nl_ref,
                      inbuf, staged, flushbuf, rbuf, sems,
                      *, blk: int, cols: int, use_lut_path: bool):
    # mat_in/ws_in alias mat_hbm/ws_hbm (input_output_aliases); all
    # reads and writes go through the output refs
    del mat_in, ws_in
    begin = scal_ref[S_BEGIN]
    count = scal_ref[S_COUNT]
    feat = scal_ref[S_FEAT]
    thr = scal_ref[S_THR]
    dleft = scal_ref[S_DLEFT]
    miss = scal_ref[S_MISS]
    defbin = scal_ref[S_DEFBIN]
    nbins = scal_ref[S_NBINS]
    iscat = scal_ref[S_ISCAT]

    nblk = pl.cdiv(count, blk)
    base = (begin // ALIGN) * ALIGN
    shift = begin - base
    win = blk + ALIGN
    win8 = blk + ALIGN  # staged rows: in-window shift (<8) + <=blk rows

    lane_w = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    row_w = jax.lax.broadcasted_iota(jnp.int32, (win, 1), 0)
    dst_w8 = jax.lax.broadcasted_iota(jnp.int32, (win, win8), 1)
    row_w8 = jax.lax.broadcasted_iota(jnp.int32, (win8, 1), 0)
    # inclusive prefix-sum operator: tri[s, d] = s <= d
    tri = (jax.lax.broadcasted_iota(jnp.int32, (win, win), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (win, win), 1))
    tri_bf = jnp.where(tri, jnp.float32(1), jnp.float32(0)).astype(
        jnp.bfloat16)

    def copy(src, dst, sem):
        d = pltpu.make_async_copy(src, dst, sem)
        d.start()
        d.wait()

    def compact_and_write(mat_bf, sel, dest, out_hbm, sem_a, sem_b):
        """Stable-compact rows with sel==1 to ``out_hbm[dest, ...)``.

        Returns the number of rows written. Read-merge-write on an
        8-aligned window keeps neighbouring rows intact.
        """
        sel_bf = sel.astype(jnp.float32).astype(
            jnp.bfloat16)                               # [win, 1] 0/1
        cs = jax.lax.dot_general(
            tri_bf, sel_bf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [win, 1] incl
        n = cs[win - 1, 0].astype(jnp.int32)
        wstart = (dest // ALIGN) * ALIGN
        dshift = dest - wstart
        slot = jnp.where(sel > 0, dshift + cs.astype(jnp.int32) - 1, -1)
        pt = jnp.where(slot == dst_w8, jnp.float32(1),
                       jnp.float32(0)).astype(jnp.bfloat16)  # [win, win8]
        staged[...] = jax.lax.dot_general(
            pt, mat_bf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [win8, C]
        # merge with current window contents
        copy(out_hbm.at[pl.ds(pl.multiple_of(wstart, ALIGN), win8), :],
             rbuf, sem_a)
        keep = (row_w8 >= dshift) & (row_w8 < dshift + n)
        merged = jnp.where(
            keep, staged[...].astype(jnp.int32), rbuf[...].astype(
                jnp.int32)).astype(jnp.uint8)
        flushbuf[...] = merged
        copy(flushbuf, out_hbm.at[pl.ds(pl.multiple_of(wstart, ALIGN),
                                        win8), :], sem_b)
        return n

    def block_body(k, carry):
        dest_l, dest_r = carry
        copy(mat_hbm.at[pl.ds(pl.multiple_of(base + k * blk, ALIGN),
                              win), :], inbuf, sems.at[0])
        mat_i32 = inbuf[...].astype(jnp.int32)          # [win, C]
        mat_bf = mat_i32.astype(jnp.float32).astype(jnp.bfloat16)

        rem = jnp.minimum(count - k * blk, blk)
        # all masks kept as i32 0/1: Mosaic cannot narrow i8 vectors to
        # i1, which jnp bool intermediates would require
        valid = jnp.where((row_w >= shift) & (row_w < shift + rem),
                          1, 0)                         # [win, 1] i32

        # split feature's bin value per row (one-hot lane reduction)
        fsel = jnp.where(lane_w == feat, 1, 0)          # [1, C]
        bv = jnp.sum(mat_i32 * fsel, axis=1, keepdims=True)  # [win, 1]

        # decision (ops/partition.py rows_go_left semantics)
        is_missing = jnp.where(
            miss == MISSING_ZERO_CODE,
            jnp.where(bv == defbin, 1, 0),
            jnp.where(miss == MISSING_NAN_CODE,
                      jnp.where(bv == nbins - 1, 1, 0), 0))
        num_left = is_missing * dleft \
            + (1 - is_missing) * jnp.where(bv <= thr, 1, 0)
        if use_lut_path:
            # categorical bitset / bundled-group membership via a
            # 256-entry LUT matmul; statically compiled out for
            # cat-free unbundled datasets (the [win, 256] one-hot is
            # ~800 VPU lane-ops/row the bench path must not pay)
            onehot = jnp.where(
                bv == jax.lax.broadcasted_iota(jnp.int32, (win, 256), 1),
                jnp.float32(1), jnp.float32(0)).astype(jnp.bfloat16)
            cat_left = jnp.where(jax.lax.dot_general(
                onehot,
                lut_ref[...].reshape(256, 1).astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) > 0.5, 1, 0)
            go_left = jnp.where(iscat > 0, cat_left, num_left)
        else:
            go_left = num_left

        gl = valid * go_left
        gr = valid * (1 - go_left)
        nl = compact_and_write(mat_bf, gl, dest_l, mat_hbm,
                               sems.at[1], sems.at[2])
        nr = compact_and_write(mat_bf, gr, dest_r, ws_hbm,
                               sems.at[1], sems.at[2])
        return dest_l + nl, dest_r + nr

    dest_l, dest_r = jax.lax.fori_loop(
        0, nblk, block_body, (begin, jnp.int32(0)))
    nl_total = dest_l - begin
    nl_ref[0, 0] = nl_total

    # phase 2: rights from workspace -> mat[begin+NL, begin+count)
    nr_total = count - nl_total

    def back_body(j, _):
        copy(ws_hbm.at[pl.ds(pl.multiple_of(j * blk, ALIGN), win), :],
             inbuf, sems.at[0])
        cnt_j = jnp.minimum(nr_total - j * blk, blk)
        sel = ((row_w >= 0) & (row_w < cnt_j)).astype(jnp.int32)
        mat_bf = inbuf[...].astype(jnp.int32).astype(
            jnp.float32).astype(jnp.bfloat16)
        compact_and_write(mat_bf, sel, dest_l + j * blk, mat_hbm,
                          sems.at[1], sems.at[2])
        return 0

    jax.lax.fori_loop(0, pl.cdiv(nr_total, blk), back_body, 0)


@register_jit("partition_segment")
@functools.partial(
    jax.jit, static_argnames=("blk", "interpret", "use_lut_path"))
def partition_segment(mat, ws, begin, count, feat, thr, default_left,
                      missing_code, default_bin, num_bins_f, is_cat,
                      cat_lut, *, blk: int = 512,
                      interpret: bool = False,
                      use_lut_path: bool = True):
    """Stable-partition rows [begin, begin+count) of the training
    matrix by the split decision. Returns (mat', ws', nl) where nl is
    the left-child row count (shape [1] i32).

    ``cat_lut``: [1, 256] f32 0/1 membership of each BIN on the left
    side (from the split's bin bitset); all-zero for numerical splits.
    ``use_lut_path=False`` (static) compiles the LUT machinery out —
    only valid when no split can be categorical or bundled.
    ``ws`` is a scratch buffer of the same shape as ``mat``.
    """
    if blk % ALIGN:
        raise ValueError(f"blk must be a multiple of {ALIGN}")
    _, cols = mat.shape
    to32 = lambda v: jnp.asarray(v, jnp.int32)
    scal = jnp.stack([
        to32(begin), to32(count), to32(feat), to32(thr),
        to32(default_left), to32(missing_code), to32(default_bin),
        to32(num_bins_f), to32(is_cat)])
    kernel = functools.partial(_partition_kernel, blk=blk, cols=cols,
                               use_lut_path=use_lut_path)
    win = blk + ALIGN
    mat2, ws2, nl = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(mat.shape, jnp.uint8),
            jax.ShapeDtypeStruct(ws.shape, jnp.uint8),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((win, cols), jnp.uint8),      # inbuf
            pltpu.VMEM((win, cols), jnp.float32),    # staged
            pltpu.VMEM((win, cols), jnp.uint8),      # flushbuf
            pltpu.VMEM((win, cols), jnp.uint8),      # rbuf
            pltpu.SemaphoreType.DMA((3,)),
        ],
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        # raise the scoped-VMEM ceiling like the histogram kernels
        # (hist_pallas.VMEM_LIMIT): block intermediates beyond the
        # declared scratch live on the Mosaic stack, and the default
        # 16 MB budget OOMed the hist kernel's first v5e compile
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            vmem_limit_bytes=100 * 1024 * 1024),
    )(scal, cat_lut, mat, ws)
    return mat2, ws2, nl.reshape(1)


def bitset_to_lut(cat_bitset) -> jnp.ndarray:
    """[W] uint32 bin bitset -> [1, 256] f32 membership LUT."""
    w = cat_bitset.shape[0]
    bins = jnp.arange(w * 32, dtype=jnp.uint32)
    bit = (cat_bitset[bins // 32] >> (bins % 32)) & jnp.uint32(1)
    lut = bit.astype(jnp.float32).reshape(1, w * 32)
    if w * 32 < 256:
        lut = jnp.pad(lut, ((0, 0), (0, 256 - w * 32)))
    return lut[:, :256]
