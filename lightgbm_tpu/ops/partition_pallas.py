"""Pallas TPU kernel: in-place stable partition of a row segment.

Reference analog: ``DataPartition::Split`` (data_partition.hpp:101-120)
+ ``DenseBin::Split`` (dense_bin.hpp:132+). The reference reorders a
leaf's index array with a parallel stable partition; here the TRAINING
MATRIX ROWS THEMSELVES are moved (ops/hist_pallas.py layout: features +
gh payload + row-id bytes per row), so the histogram kernel can stream
each leaf as one contiguous segment.

Algorithm (sequential block stream over [begin, begin+count),
``partition_stream`` — ONE block step for this kernel and for phase 0
of the split-step megakernel, which imports it):
  1. read a row block; pick the split feature's bin per row (one-hot
     lane reduction) and decide left/right (numerical threshold with
     missing handling, or categorical bitset via a 256-entry LUT
     matmul) — the caller's ``decide``;
  2. stable-compact the block's left rows via a permutation matmul
     (PT[src, dst] one-hot x row block on the MXU — bin/payload bytes
     are exact in bf16) and write them at the left write head IN
     PLACE; rights go to a workspace buffer the same way;
  3. after the stream, copy the workspace back behind the lefts.

Writes go through windows aligned to Mosaic's 8-row u8 granule, so
segment boundaries can sit anywhere and neighbours' rows survive.
Prefix sums are triangular matmuls (no native cumsum).

The pipeline (PR 28): no DMA wait on the stream's critical path.
  * Input: two ``inbuf`` slots; block k+1 (and workspace window j+1
    in the back-copy) is read while block k computes.
  * Write heads carried in VMEM: of a destination window only the
    up-to-7 rows before ``dest`` in its granule must survive where
    everything else it covers is dead — consumed rows not yet
    rewritten, workspace scratch — and those rows are the side's
    previous window's own (``head``). Such a window takes the FAST
    path and reads nothing back: every workspace window; a forward
    left window that ends at or before the last row its block
    consumed (``forward_fast``); a back-copy window after the first
    that lies inside the segment (``back_fast``). Any other window —
    block 0, a left window before ~8 rows have gone right, a
    segment's last windows — takes the old read-merge-write.
  * Writes behind the computation: a window's write is started and
    waited for only when its side's next window is ready to go (one
    compaction later), before a merge reads that side, before the
    back-copy reads the workspace, and at the end. One write a side
    in flight, not two: consecutive windows of a side overlap, and
    two overlapping writes in flight could land in either order.
  * The prefetched window of block k+1 begins ``shift`` (< 8) rows
    inside block k's rows, which a fast left write may be touching;
    ``valid`` masks them out of the decision and the carried head.
The block size is unchanged, so every f32 sum keeps its order and the
trees are byte-identical to the unpipelined kernel's.

Returns the left-row count NL and the number of windows that took the
merge path (``merge_windows`` is the host twin); children are
[begin, begin+NL) and [begin+NL, begin+count).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.telemetry import get_telemetry
from ..utils.jit_registry import register_jit

ALIGN = 8

# scalar input slots
S_BEGIN, S_COUNT, S_FEAT, S_THR, S_DLEFT, S_MISS, S_DEFBIN, S_NBINS, \
    S_ISCAT = range(9)

MISSING_NONE_CODE = 0
MISSING_ZERO_CODE = 1
MISSING_NAN_CODE = 2


# sides of the stream: lefts compact in place into the matrix, rights
# into the workspace
_L, _R = 0, 1
# DMA semaphores of ``stream_scratch``: two input slots, the merge
# path's window read, one write per side
_SEM_IN, _SEM_RBUF, _SEM_W = 0, 2, 3


def stream_scratch(blk: int, cols: int):
    """``scratch_shapes`` of ``partition_stream``, in the order it takes
    them (``inbuf, staged, flush, rbuf, head, sems``)."""
    win = blk + ALIGN
    return [
        pltpu.VMEM((2, win, cols), jnp.uint8),       # inbuf: 2 slots
        pltpu.VMEM((win, cols), jnp.float32),        # staged window
        pltpu.VMEM((2, win, cols), jnp.uint8),       # flush: per side
        pltpu.VMEM((win, cols), jnp.uint8),          # rbuf: merge path
        pltpu.VMEM((2, ALIGN, cols), jnp.float32),   # head: per side
        pltpu.SemaphoreType.DMA((5,)),
    ]


def partition_stream(mat_hbm, ws_hbm, scratch, begin, count, decide,
                     *, blk: int):
    """The pipelined block stream both partition kernels run: stable
    partition of ``mat_hbm[begin, begin+count)`` in place, rights via
    ``ws_hbm``. ``decide(mat_i32, mat_f, valid, shift, rem)`` returns
    the block's ``(go_left, go_right)`` [win, 1] i32 0/1 masks (already
    masked by ``valid``): the decision alone, in both kernels (the
    megakernel histograms the smaller child in a stream of its own,
    after this one has returned). Returns ``(NL, merge-path windows)``.
    Every write has landed by then, the back-copy's included, and the
    ``inbuf`` slots and their semaphores are free.

    No DMA wait sits on the critical path (module docstring): block
    k+1 is read while block k computes; each window write is waited
    only when its side's next window is ready to go, a whole
    compaction later; and a window is read back (``rbuf``) only where
    it holds rows the stream does not own.
    """
    inbuf, staged, flush, rbuf, head, sems = scratch
    # counted where the helper enters a kernel's trace, like
    # ``learner.megakernel_traces``
    get_telemetry().count("kernels.partition_pipelined")
    win = blk + ALIGN
    nblk = pl.cdiv(count, blk)
    base = (begin // ALIGN) * ALIGN
    shift = begin - base
    seg_end = begin + count
    outs = (mat_hbm, ws_hbm)

    row_w = jax.lax.broadcasted_iota(jnp.int32, (win, 1), 0)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (ALIGN, 1), 0)
    dst_w = jax.lax.broadcasted_iota(jnp.int32, (win, win), 1)
    # inclusive prefix-sum operator: tri[s, d] = s <= d
    tri = (jax.lax.broadcasted_iota(jnp.int32, (win, win), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (win, win), 1))
    tri_bf = jnp.where(tri, jnp.float32(1), jnp.float32(0)).astype(
        jnp.bfloat16)

    def window(ref, start):
        return ref.at[pl.ds(pl.multiple_of(start, ALIGN), win), :]

    def read(src_hbm, start, slot):
        return pltpu.make_async_copy(window(src_hbm, start),
                                     inbuf.at[slot],
                                     sems.at[_SEM_IN + slot])

    def write(side, wstart):
        return pltpu.make_async_copy(flush.at[side],
                                     window(outs[side], wstart),
                                     sems.at[_SEM_W + side])

    def drain(side, inflight):
        """Wait for the side's window write in flight, if any:
        ``inflight`` is its window's first row, -1 for none."""
        @pl.when(inflight >= 0)
        def _():
            write(side, inflight).wait()

    def load_block(slot):
        mat_i32 = inbuf[slot].astype(jnp.int32)          # [win, C]
        mat_f = mat_i32.astype(jnp.float32)
        return mat_i32, mat_f, mat_f.astype(jnp.bfloat16)

    def compact_and_write(mat_bf, sel, dest, side, fast, inflight):
        """Stable-compact rows with sel==1 to ``outs[side][dest, ...)``
        through the 8-aligned window that holds ``dest``; the write is
        left in flight. Returns the number of rows written and the
        window's first row (the side's next ``inflight``).

        ``fast``: every window row outside [dest, dest+n) is either
        one of the up-to-7 rows before ``dest`` in its granule, which
        this side's previous window wrote and ``head`` carries, or
        dead (consumed and not yet rewritten, or workspace scratch):
        nothing is read back. Otherwise a read-merge-write keeps
        the neighbours' and the unconsumed rows.
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
        pt = jnp.where(slot == dst_w, jnp.float32(1),
                       jnp.float32(0)).astype(jnp.bfloat16)  # [win, win]
        staged[...] = jax.lax.dot_general(
            pt, mat_bf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [win, C]
        # windows of one side overlap, so its previous write must have
        # landed before this one starts (and before a merge reads the
        # window, and before ``flush[side]`` is refilled)
        drain(side, inflight)

        def carry_head():
            staged[0:ALIGN, :] = jnp.where(
                row8 < dshift, head[side], staged[0:ALIGN, :])

        def merge():
            cp = pltpu.make_async_copy(window(outs[side], wstart), rbuf,
                                       sems.at[_SEM_RBUF])
            cp.start()
            cp.wait()
            keep = (row_w >= dshift) & (row_w < dshift + n)
            staged[...] = jnp.where(
                keep, staged[...],
                rbuf[...].astype(jnp.int32).astype(jnp.float32))

        if fast is True:
            carry_head()
        else:
            pl.when(fast)(carry_head)
            pl.when(jnp.logical_not(fast))(merge)
        flush[side] = staged[...].astype(jnp.int32).astype(jnp.uint8)
        write(side, wstart).start()
        # the rows before the side's next ``dest`` in its granule
        nxt = ((dshift + n) // ALIGN) * ALIGN
        head[side] = staged[pl.ds(pl.multiple_of(nxt, ALIGN), ALIGN), :]
        return n, wstart

    # ---- forward: lefts in place, rights to the workspace ------------
    @pl.when(nblk > 0)
    def _():
        read(mat_hbm, base, 0).start()

    def block_body(k, carry):
        dest_l, dest_r, fly_l, fly_r, merges = carry
        slot = jax.lax.rem(k, 2)

        # the prefetched window begins ``shift`` rows inside block k's
        # rows, which a fast left write below may touch while they are
        # read: ``valid`` masks them out of everything
        @pl.when(k + 1 < nblk)
        def _():
            read(mat_hbm, base + (k + 1) * blk, 1 - slot).start()

        read(mat_hbm, base + k * blk, slot).wait()
        mat_i32, mat_f, mat_bf = load_block(slot)
        rem = jnp.minimum(count - k * blk, blk)
        # all masks kept as i32 0/1: Mosaic cannot narrow i8 vectors to
        # i1, which jnp bool intermediates would require
        valid = jnp.where((row_w >= shift) & (row_w < shift + rem),
                          1, 0)                         # [win, 1] i32
        gl, gr = decide(mat_i32, mat_f, valid, shift, rem)
        # the left window ends at or before the last row this block
        # consumed: false while fewer than ~8 rows have gone right
        # (block 0 always) and at the segment's end
        fast_l = forward_fast(begin, k, rem, dest_l, blk)
        nl, fly_l = compact_and_write(mat_bf, gl, dest_l, _L, fast_l,
                                      fly_l)
        nr, fly_r = compact_and_write(mat_bf, gr, dest_r, _R, True,
                                      fly_r)
        return (dest_l + nl, dest_r + nr, fly_l, fly_r,
                merges + jnp.where(fast_l, 0, 1))

    none = jnp.int32(-1)
    dest_l, _, fly_l, fly_r, merges = jax.lax.fori_loop(
        0, nblk, block_body, (begin, jnp.int32(0), none, none,
                              jnp.int32(0)))
    nl_total = dest_l - begin
    # the back-copy reads the workspace windows and merges its first
    # matrix window: both sides' last writes must have landed
    drain(_L, fly_l)
    drain(_R, fly_r)

    # ---- back-copy: rights from workspace -> mat[begin+NL, seg_end) --
    nr_total = count - nl_total
    nback = pl.cdiv(nr_total, blk)

    @pl.when(nback > 0)
    def _():
        read(ws_hbm, 0, 0).start()

    def back_body(j, carry):
        fly_l, merges = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nback)
        def _():
            read(ws_hbm, (j + 1) * blk, 1 - slot).start()

        read(ws_hbm, j * blk, slot).wait()
        _, _, mat_bf = load_block(slot)
        cnt_j = jnp.minimum(nr_total - j * blk, blk)
        sel = jnp.where(row_w < cnt_j, 1, 0)
        fast = back_fast(seg_end, j, dest_l, blk)
        _, fly_l = compact_and_write(mat_bf, sel, dest_l + j * blk, _L,
                                     fast, fly_l)
        return fly_l, merges + jnp.where(fast, 0, 1)

    fly_l, merges = jax.lax.fori_loop(
        0, nback, back_body, (none, merges))
    # the caller's next phase (and the next split) sees every write
    drain(_L, fly_l)
    return nl_total, merges


def forward_fast(begin, k, rem, dest_l, blk):
    """Forward block k's left window needs no read-back: it ends at or
    before the last of the ``rem`` rows the block consumed, so all it
    covers past the write head is dead (every row of the segment is
    rewritten by the end of the call). One rule for the kernel and
    ``merge_windows``."""
    wstart = (dest_l // ALIGN) * ALIGN
    return wstart + blk + ALIGN <= begin + k * blk + rem


def back_fast(seg_end, j, dest_l, blk):
    """Back-copy window j needs no read-back: it lies inside the
    segment, and its head is the previous back window's tail. Window 0
    (its head is the last lefts, or the neighbour's rows) merges."""
    wstart = ((dest_l + j * blk) // ALIGN) * ALIGN
    return (j > 0) & (wstart + blk + ALIGN <= seg_end)


def merge_windows(begin: int, count: int, nl_by_block, blk: int = 512):
    """Host twin of the kernel's second output word: how many windows
    of one call take the merge path, from the left count of each
    forward block. Workspace windows never do."""
    merges, dest_l = 0, begin
    for k, nl in enumerate(nl_by_block):
        rem = min(count - k * blk, blk)
        merges += not forward_fast(begin, k, rem, dest_l, blk)
        dest_l += int(nl)
    nr = count - (dest_l - begin)
    for j in range(-(-nr // blk)):
        merges += not back_fast(begin + count, j, dest_l, blk)
    return int(merges)


def stream_windows(count: int, nl: int, blk: int = 512) -> int:
    """Windows one call writes: a left and a right one per forward
    block, one per back-copy block (the merge share's divisor)."""
    return 2 * -(-count // blk) + -(-(count - nl) // blk)


def _partition_kernel(scal_ref, lut_ref, mat_in, ws_in,
                      mat_hbm, ws_hbm, nl_ref, *scratch,
                      blk: int, cols: int, use_lut_path: bool):
    # mat_in/ws_in alias mat_hbm/ws_hbm (input_output_aliases); all
    # reads and writes go through the output refs
    del mat_in, ws_in
    feat = scal_ref[S_FEAT]
    thr = scal_ref[S_THR]
    dleft = scal_ref[S_DLEFT]
    miss = scal_ref[S_MISS]
    defbin = scal_ref[S_DEFBIN]
    nbins = scal_ref[S_NBINS]
    iscat = scal_ref[S_ISCAT]
    win = blk + ALIGN
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

    def decide(mat_i32, mat_f, valid, shift, rem):
        del mat_f, shift, rem
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
        return valid * go_left, valid * (1 - go_left)

    nl_total, merges = partition_stream(
        mat_hbm, ws_hbm, scratch, scal_ref[S_BEGIN], scal_ref[S_COUNT],
        decide, blk=blk)
    nl_ref[0, 0] = nl_total
    nl_ref[0, 1] = merges


@register_jit("partition_segment")
@functools.partial(
    jax.jit, static_argnames=("blk", "interpret", "use_lut_path"))
def partition_segment(mat, ws, begin, count, feat, thr, default_left,
                      missing_code, default_bin, num_bins_f, is_cat,
                      cat_lut, *, blk: int = 512,
                      interpret: bool = False,
                      use_lut_path: bool = True):
    """Stable-partition rows [begin, begin+count) of the training
    matrix by the split decision. Returns (mat', ws', nl): ``nl[0]``
    is the left-child row count, ``nl[1]`` the number of windows that
    took the merge path (``merge_windows``); shape [2] i32.

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
    mat2, ws2, nl = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(mat.shape, jnp.uint8),
            jax.ShapeDtypeStruct(ws.shape, jnp.uint8),
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
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
        scratch_shapes=stream_scratch(blk, cols),
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        # raise the scoped-VMEM ceiling (v5e has 128 MB): block
        # intermediates beyond the declared scratch live on the Mosaic
        # stack, which the default 16 MB budget may not hold
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            vmem_limit_bytes=100 * 1024 * 1024),
    )(scal, cat_lut, mat, ws)
    return mat2, ws2, nl.reshape(2)


def bitset_to_lut(cat_bitset) -> jnp.ndarray:
    """[W] uint32 bin bitset -> [1, 256] f32 membership LUT."""
    w = cat_bitset.shape[0]
    bins = jnp.arange(w * 32, dtype=jnp.uint32)
    bit = (cat_bitset[bins // 32] >> (bins % 32)) & jnp.uint32(1)
    lut = bit.astype(jnp.float32).reshape(1, w * 32)
    if w * 32 < 256:
        lut = jnp.pad(lut, ((0, 0), (0, 256 - w * 32)))
    return lut[:, :256]


def partition_decision_lut(meta, feat, thr, dleft, is_cat, bitset,
                           bundled: bool):
    """(grp_col, use_lut, lut) for one split's physical partition —
    the 256-entry "group value -> goes left" table encoding decode +
    missing handling in feature-bin space for bundled splits, the raw
    bin bitset for categorical ones. ONE definition shared by the
    per-phase body's ``partition_segment`` call and the split-step
    megakernel's interpret twin (bit-exactness-critical)."""
    lut = jnp.where(is_cat, bitset_to_lut(bitset),
                    jnp.zeros((1, 256), jnp.float32))
    grp_col = meta.group[feat] if bundled else feat
    use_lut = is_cat
    if bundled:
        from ..data.bundling import decode_feature_bin
        off = meta.offset[feat]
        nbf = meta.num_bins[feat]
        vals = jnp.arange(256, dtype=jnp.int32)
        # offset 0 would pass values through; masked by
        # is_bundled_split below, so raw splits keep the fast path
        fbin = decode_feature_bin(vals, off, nbf)
        mcode = meta.missing[feat]
        is_miss = jnp.where(
            mcode == 1, fbin == meta.default_bin[feat],
            jnp.where(mcode == 2, fbin == nbf - 1, False))
        go_left = jnp.where(is_miss, dleft, fbin <= thr)
        blut = go_left.astype(jnp.float32).reshape(1, 256)
        is_bundled_split = (off > 0) & ~is_cat
        lut = jnp.where(is_bundled_split, blut, lut)
        use_lut = is_cat | is_bundled_split
    return grp_col, use_lut, lut
