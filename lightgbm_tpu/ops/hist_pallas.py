"""Pallas TPU histogram kernel over a dynamic row segment.

Reference analog: the OpenCL histogram kernels
(``src/treelearner/ocl/histogram256.cl``) + ``DenseBin::
ConstructHistogramInner`` (dense_bin.hpp:76-105). The GPU reference
scatter-adds into workgroup-local memory with float atomics; TPUs have
no scatter-add, so the kernel is reformulated for the MXU: per block
of rows and per feature f,

    hist[f] += pay[win, 8]^T @ (mat[:, f] == bins)[win, B128]

one bf16 matmul whose one-hot factor (built on the VPU from the bin
byte) is exact and whose payload operand holds grad and hess as bf16
hi/lo pairs summing to the f32 value, accumulated in f32: full f32
fidelity on the bf16 datapath (the reference's ``gpu_use_dp`` story
one level up, gpu_tree_learner.cpp:299).

**Single training-matrix layout.** Everything a tree build touches
rides in ONE row-major uint8 matrix (the TPU analog of the reference
packing 4 dense feature groups per 32-bit word, Feature4,
gpu_tree_learner.h:75-77):

    cols [0, F)        feature bins (u8)
    col  F+0..3        grad f32 bytes (little-endian)
    col  F+4..7        hess f32 bytes
    col  F+8           bagging/count indicator (0/1)
    col  F+9..12       row id (i32 bytes; partition bookkeeping)
    C = round_up(F+13, 128)

Since XLA pads a [N, F] u8 array's minor dim to 128 anyway, these
payload columns are FREE whenever F % 128 <= 115 — and one buffer
means the partition kernel moves rows once and the histogram kernel
issues one DMA stream.

The segment [begin, begin+count) is DYNAMIC — per-leaf cost is
O(leaf rows), not O(N) (the point of partitioned layout; LightGBM
scans only the leaf's rows via DataPartition, data_partition.hpp:161).
DMA windows start at the 8-aligned floor of `begin` (Mosaic granule
for u8 rows); the in-window shift is masked via the payload operand,
so no dynamic VMEM slicing is needed anywhere.

**One form** (``hist_child_stream``), whoever asks: the root's
histogram, a leaf segment's (``histogram_segment``) and the smaller
child's inside the split-step megakernel are the same block stream.
The table's width only says whether it runs over whole rows (up to
``MAX_FUSED_F`` columns: the body unrolls over exactly ``F`` features)
or a ``SLICE_F``-column slice at a time (past it: one ``pallas_call``
whose grid axis is the slice, one body for every slice).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.telemetry import get_telemetry
from ..utils.device import on_tpu
from ..utils.jit_registry import register_jit
from ..utils.matrix_layout import matrix_cols

ALIGN = 8          # Mosaic offset granule for u8 2-D row slices
RID_OFF = 9        # row-id bytes start at column F + RID_OFF

def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def matrix_rows(n: int, blk: int = 2048) -> int:
    # slack so any window [base + k*blk, +blk+ALIGN) stays in bounds
    return _round_up(n, blk) + blk + ALIGN


def _split_hi_lo_f32(x):
    """bf16 hi/lo pair summing to f32 x. The hi part TRUNCATES the
    mantissa via integer masking — a plain astype(bf16).astype(f32)
    round-trip is folded to identity under XLA's
    allow-excess-precision, which would silently drop the residual."""
    hi_f32 = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, jnp.uint32)
        & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi_f32.astype(jnp.bfloat16), (x - hi_f32).astype(jnp.bfloat16)


def build_matrix(binned, blk: int = 2048) -> jnp.ndarray:
    """[N, F] int bins -> training matrix [N_pad, C] u8 with row ids."""
    n, f = binned.shape
    mat = jnp.zeros((matrix_rows(n, blk), matrix_cols(f)), jnp.uint8)
    mat = mat.at[:n, :f].set(binned.astype(jnp.uint8))
    rid = jnp.arange(n, dtype=jnp.uint32)
    for k in range(4):
        mat = mat.at[:n, f + RID_OFF + k].set(
            ((rid >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)).astype(
                jnp.uint8))
    return mat


def pack_gh(mat, num_features: int, grad, hess, cnt) -> jnp.ndarray:
    """Write the gh payload columns for rows [0, len(grad)): each f32
    as its four bytes, low byte first, then the 0/1 count. Bitcasts, not
    shifted byte planes: XLA may lay each plane out as an [n, 1] column
    padded to 128 lanes (9 GB at 6.5 M rows in the mesh learner's
    program, compiled for the v5e)."""
    payload = jnp.concatenate([
        jax.lax.bitcast_convert_type(grad.astype(jnp.float32), jnp.uint8),
        jax.lax.bitcast_convert_type(hess.astype(jnp.float32), jnp.uint8),
        (cnt > 0).astype(jnp.uint8)[:, None]], axis=1)     # [n, 9]
    return jax.lax.dynamic_update_slice(mat, payload, (0, num_features))


def extract_row_ids(mat, num_features: int, n: int) -> jnp.ndarray:
    """Recover i32 row ids from the payload columns (rows [0, n)): four
    bytes, low byte first, read as one word."""
    col = num_features + RID_OFF
    return jax.lax.bitcast_convert_type(mat[:n, col:col + 4], jnp.int32)


# the widest table whose whole rows go through ONE per-feature
# unrolled body: the histogram stream below and the split-step
# megakernel that holds it (ops/split_step_pallas.py refuses a wider
# table, ``plan_split_step`` calls it ``wide``). Past it the histogram
# is cut into column slices.
MAX_FUSED_F = 192

# a wider table's histogram is cut into slices of this many columns:
# one lane tile of the u8 matrix, so a slice's DMA starts on a tile
# boundary whatever its index
SLICE_F = 128
SLICE_BLK = 512    # row block of the stream (the megakernel's)


def _decode_block(mat_i32, feat0: int, shift, rem, win: int):
    """Block decode shared by the histogram kernel and the split-step
    megakernel's phase 0 (ops/split_step_pallas.py): validity mask +
    the payload planes ((g, h) as exact bf16 hi/lo pairs, 0/1 count)
    read back out of the row bytes. Returns
    ``(valid, g_hi, g_lo, h_hi, h_lo, cnt)`` — all [win, 1], cnt f32.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, (win, 1), 0)
    valid = jnp.where((row >= shift) & (row < shift + rem),
                      jnp.float32(1), jnp.float32(0))   # [win, 1]

    def i32b(c):
        return mat_i32[:, c:c + 1]

    def f32col(c):                                   # little-endian f32
        # mul-add instead of shift-or: i32 `<< 16` miscompiles on
        # this Mosaic version (observed on v5e); multiplies are
        # exact (i32 wraparound gives the same bit pattern)
        u = (i32b(c) + i32b(c + 1) * 256 + i32b(c + 2) * 65536
             + i32b(c + 3) * 16777216)
        return jax.lax.bitcast_convert_type(u, jnp.float32)

    g = f32col(feat0 + 0) * valid
    h = f32col(feat0 + 4) * valid
    cnt = mat_i32[:, feat0 + 8:feat0 + 9].astype(jnp.float32) * valid
    g_hi, g_lo = _split_hi_lo_f32(g)
    h_hi, h_lo = _split_hi_lo_f32(h)
    return valid, g_hi, g_lo, h_hi, h_lo, cnt


def _segment_scalars(begin, count):
    return jnp.stack([jnp.asarray(begin, jnp.int32),
                      jnp.asarray(count, jnp.int32)])


def _sum_planes(g_hi, g_lo, h_hi, h_lo, cnt):
    """The kernels' five payload planes -> [F, B, 3] (g, h, count)."""
    return jnp.stack([g_hi + g_lo, h_hi + h_lo, cnt], axis=-1)


def hist_child_stream(mat_hbm, buf, sems, hpl, begin, count, *,
                      f: int, blk: int, col0=None):
    """The plain one-hot histogram: a pipelined block stream over the
    rows ``mat_hbm[begin, begin+count)`` alone, accumulated into the
    five ``hpl`` planes ``[5, F8, B128]`` f32 (g hi, g lo, h hi, h lo,
    count; zeroed here first). Every histogram of a partitioned
    learner is this body: ``histogram_segment`` runs it over the root
    and over a leaf's segment, and phase 0 of the split-step megakernel
    (ops/split_step_pallas.py) after ``partition_stream`` has returned,
    on the smaller child's compact segment.

    ``col0`` None: whole rows are streamed (``buf`` [2, blk+8, C] u8,
    ``sems`` two DMA semaphores or more) and columns ``[0, f)``
    histogrammed. ``col0`` a traced multiple of ``SLICE_F``: only the
    ``SLICE_F`` columns from ``col0`` on and the lane tiles that hold
    the payload are streamed (``buf`` [2, blk+8, SLICE_F + C - tile0],
    four semaphores), and all ``SLICE_F`` columns are histogrammed,
    whatever lies past the table's ``f`` among them: the caller cuts
    them off, so every slice runs one kernel body.

    Windows start at the 8-aligned floor of ``begin``; rows outside
    ``[shift, shift+rem)`` of a window are masked through the payload
    (``_decode_block``). Block k+1 is read into ``buf``'s other slot
    while block k computes. Per block and feature: a ``[win, B128]``
    one-hot of the bin byte on the VPU, one matmul with the exact bf16
    hi/lo payload pairs, f32 accumulation."""
    # counted where the stream enters a kernel's trace, like
    # ``kernels.partition_pipelined``
    get_telemetry().count("kernels.hist_child_stream")
    win = blk + ALIGN
    nblk = pl.cdiv(count, blk)
    base = (begin // ALIGN) * ALIGN
    shift = begin - base
    cols = mat_hbm.shape[1]
    if col0 is None:
        nf, pay0 = f, f
    else:
        tile0 = f // SLICE_F * SLICE_F      # the payload's lane tile
        nf, pay0 = SLICE_F, SLICE_F + f - tile0
    # pad lanes: no bin
    bins_l = jax.lax.broadcasted_iota(
        jnp.int32, (1, hpl.shape[2]), 1).astype(jnp.float32)
    hpl[...] = jnp.zeros_like(hpl)

    def reads(k, slot):
        rows = pl.ds(pl.multiple_of(base + k * blk, ALIGN), win)
        if col0 is None:
            return [pltpu.make_async_copy(
                mat_hbm.at[rows, :], buf.at[slot], sems.at[slot])]
        return [
            pltpu.make_async_copy(
                mat_hbm.at[rows, pl.ds(col0, nf)],
                buf.at[slot, :, pl.ds(0, nf)], sems.at[slot]),
            pltpu.make_async_copy(
                mat_hbm.at[rows, pl.ds(tile0, cols - tile0)],
                buf.at[slot, :, pl.ds(nf, cols - tile0)],
                sems.at[2 + slot])]

    @pl.when(nblk > 0)
    def _():
        for cp in reads(0, 0):
            cp.start()

    def block_body(k, _):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < nblk)
        def _():
            for cp in reads(k + 1, 1 - slot):
                cp.start()

        for cp in reads(k, slot):
            cp.wait()
        mat_i32 = buf[slot].astype(jnp.int32)            # [win, C]
        mat_f = mat_i32.astype(jnp.float32)
        rem = jnp.minimum(count - k * blk, blk)
        _, g_hi, g_lo, h_hi, h_lo, c_ch = _decode_block(
            mat_i32, pay0, shift, rem, win)
        zero = jnp.zeros_like(g_hi)
        pay = jnp.concatenate(
            [g_hi, g_lo, h_hi, h_lo, c_ch.astype(jnp.bfloat16), zero,
             zero, zero], axis=1)                        # [win, 8]
        for fx in range(nf):
            fcol = mat_f[:, fx:fx + 1]                   # [win, 1]
            onehot = jnp.where(fcol == bins_l, jnp.float32(1),
                               0.0).astype(jnp.bfloat16)
            res = jax.lax.dot_general(
                pay, onehot, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # [8, B]
            for ch in range(5):
                hpl[ch, pl.ds(fx, 1), :] += res[ch:ch + 1, :]
        return 0

    jax.lax.fori_loop(0, nblk, block_body, 0)


def _hist_rows_kernel(scal_ref, mat_hbm, hpl, buf, sems, *, f, blk):
    hist_child_stream(mat_hbm, buf, sems, hpl, scal_ref[0], scal_ref[1],
                      f=f, blk=blk)


@register_jit("hist_child_stream")
@functools.partial(
    jax.jit,
    static_argnames=("num_features", "num_bins", "blk", "interpret"))
def histogram_child_stream(mat, begin, count, *, num_features: int,
                           num_bins: int, blk: int = SLICE_BLK,
                           interpret: bool = False):
    """``hist_child_stream`` over whole rows, as ``partition_segment``
    wraps ``partition_stream`` -> [F, B, 3]: the histogram of a table
    of at most ``MAX_FUSED_F`` columns, exactly ``F`` of them
    histogrammed."""
    f = num_features
    fp, bp = _round_up(f, 8), _round_up(num_bins, 128)
    planes = pl.pallas_call(
        functools.partial(_hist_rows_kernel, f=f, blk=blk),
        out_shape=jax.ShapeDtypeStruct((5, fp, bp), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, blk + ALIGN, mat.shape[1]), jnp.uint8),
            pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(_segment_scalars(begin, count), mat)[:, :f, :num_bins]
    return _sum_planes(*planes)


def _hist_slices_kernel(scal_ref, mat_hbm, hpl, buf, sems, *, f, blk):
    # one grid step a column slice; ``hpl`` is the slice's block of
    # the output planes
    col0 = pl.multiple_of(pl.program_id(0) * SLICE_F, SLICE_F)
    hist_child_stream(mat_hbm, buf, sems, hpl, scal_ref[0], scal_ref[1],
                      f=f, blk=blk, col0=col0)


@register_jit("hist_segment_slices")
@functools.partial(
    jax.jit,
    static_argnames=("num_features", "num_bins", "blk", "interpret"))
def _histogram_segment_slices(mat, begin, count, *, num_features: int,
                              num_bins: int, blk: int = SLICE_BLK,
                              interpret: bool = False):
    """The histogram of a table past ``MAX_FUSED_F`` columns ->
    [F, B, 3]: ONE ``pallas_call`` whose grid axis is the column slice
    (``SLICE_F`` columns each), one Mosaic body for every slice. A
    slice streams its own columns and the payload's lane tile, not the
    whole row; the last slice runs past ``F`` into the payload and
    padding columns, which are cut off here (``matrix_cols`` rounds the
    row up to whole tiles, so the slice is always inside the matrix)."""
    f, cols = num_features, mat.shape[1]
    n_slices = -(-f // SLICE_F)
    bp = _round_up(num_bins, 128)
    pay_cols = cols - f // SLICE_F * SLICE_F
    planes = pl.pallas_call(
        functools.partial(_hist_slices_kernel, f=f, blk=blk),
        grid=(n_slices,),
        out_shape=jax.ShapeDtypeStruct((5, n_slices * SLICE_F, bp),
                                       jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((5, SLICE_F, bp), lambda s: (0, s, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, blk + ALIGN, SLICE_F + pay_cols), jnp.uint8),
            pltpu.SemaphoreType.DMA((4,))],
        interpret=interpret,
    )(_segment_scalars(begin, count), mat)[:, :f, :num_bins]
    return _sum_planes(*planes)


def histogram_segment(mat, begin, count, num_bins: int, num_features: int,
                      blk: int = 2048,
                      interpret: bool = False) -> jnp.ndarray:
    """Histogram of rows [begin, begin+count) -> [F, B, 3] f32 by the
    one-hot stream: over whole rows up to ``MAX_FUSED_F`` columns, a
    ``SLICE_F``-column slice at a time past it. The table's width alone
    decides. ``blk`` is the row block the matrix was padded for
    (``matrix_rows``), the most the stream's may be (``SLICE_BLK``
    where ``blk`` allows it, so that a window never leaves the matrix).
    ``ops/histogram.py`` is the reference the tests compare it with."""
    f = num_features
    sliced = f > MAX_FUSED_F
    # counted where a histogram call is traced: the column slices it
    # is cut into
    get_telemetry().count("kernels.hist_feature_slices",
                          -(-f // SLICE_F) if sliced else 1)
    stream = _histogram_segment_slices if sliced else histogram_child_stream
    return stream(mat, begin, count, num_features=f, num_bins=num_bins,
                  blk=min(blk, SLICE_BLK), interpret=interpret)


def histogram_pallas(binned, ghc, num_bins: int, blk: int = 2048,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in full-range histogram (ops/histogram.py "pallas" method).

    binned [N, F] int, ghc [N, 3] f32 -> [F, B, 3] f32. Builds the
    training matrix on the fly — the partitioned learner keeps it
    resident instead.
    """
    if interpret is None:
        interpret = not on_tpu()
    n, f = binned.shape
    mat = build_matrix(binned, blk)
    mat = pack_gh(mat, f, ghc[:, 0], ghc[:, 1], ghc[:, 2])
    return histogram_segment(mat, 0, n, num_bins, f, blk=blk,
                             interpret=interpret)
