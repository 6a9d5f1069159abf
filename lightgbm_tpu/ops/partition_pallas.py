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
  2. stable-compact the block ONCE (PR 34): both masks become rows
     and are prefix-summed in one product; one destination-major
     one-hot ``PT[dst, src]`` sends the lefts to the left write
     head's place in their window and the rights to the right write
     head's place in a window of their own, which begins at the first
     8-row boundary past the lefts; one permutation product (one-hot x
     row block on the MXU -- bin/payload bytes are exact in bf16)
     fills both windows. The lefts' window is written at the left
     write head IN PLACE, the rights' to a workspace buffer;
  3. after the stream, copy the workspace back behind the lefts. Its
     rows are compact already and the block is a multiple of 8 rows,
     so every block of a call goes the same ``(begin + NL) % 8`` rows
     down its window: a sublane roll, no compaction.

Writes go through windows aligned to Mosaic's 8-row u8 granule, so
segment boundaries can sit anywhere and neighbours' rows survive.
Prefix sums are triangular matmuls (no native cumsum). No operand of
a product is one the VPU has to transpose, and the slot arithmetic
runs on [8, win] rows, not on [win, 1] columns of one lane a vreg: a
column mask becomes a row through the MXU's own transposed-operand
form (``pick`` x ``sel_cols``^T).

The pipeline (PR 28): no DMA wait on the stream's critical path.
  * Input: two ``inbuf`` slots; block k+1 (and workspace window j+1
    in the back-copy) is read while block k computes.
  * Write heads carried in VMEM: of a destination window only the
    up-to-7 rows before ``dest`` in its granule must survive where
    everything else it covers is dead — consumed rows not yet
    rewritten, workspace scratch; dead whatever is written there: the
    tail of the same product, which holds the block's rights, or rows
    of ``staged`` no product of the call has reached — and those rows
    are the side's previous window's own
    (``head``). Such a window takes the FAST path and reads nothing
    back: every workspace window; a forward left window that ends at
    or before the last row its block consumed (``forward_fast``); a
    back-copy window after the first that lies inside the segment
    (``back_fast``). Any other window — block 0, a left window
    before ~8 rows have gone right, a segment's last windows — takes
    the old read-merge-write.
  * Writes behind the computation: a window's write is started and
    waited for only when its side's next window is ready to go (one
    block -- one compaction -- later), before a merge reads that
    side, before the back-copy reads the workspace, and at the end.
    One write a side in flight, not two: consecutive windows of a
    side overlap, and two overlapping writes in flight could land in
    either order.
  * The prefetched window of block k+1 begins ``shift`` (< 8) rows
    inside block k's rows, which a fast left write may be touching;
    ``valid`` masks them out of the decision and the carried head.
The block size is unchanged, so every f32 sum keeps its order and the
trees are byte-identical to the unpipelined kernel's.

Returns the left-row count NL and the number of windows that took the
merge path (``merge_windows`` is the host twin); children are
[begin, begin+NL) and [begin+NL, begin+count). The compactions a call
runs are a property of the program, not of the data:
``stream_compactions`` is the host rule, and the products over whole
rows in each loop's traced body are the count it is held to
(tests/test_partition_v2.py, ``tools/check_kernels_on_chip.py``).
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
# rows of ``staged`` past a window that one product fills: the lefts'
# window and, from the first granule boundary past the lefts, the
# rights' (head rows included) end at most 8 + 7 + 7 rows past ``blk``
PAD = 2 * ALIGN

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
    them (``inbuf, staged, flush, rbuf, head, sems``). ``staged`` holds
    two windows: the one product of a block fills its first
    ``win + PAD`` rows, and the rights' window, a static ``win`` rows
    from wherever the lefts end, starts as late as row ``win`` (a
    block whose rows all go left). The rows past ``win + PAD`` are
    never written: they reach a workspace window's dead tail alone."""
    win = blk + ALIGN
    return [
        pltpu.VMEM((2, win, cols), jnp.uint8),       # inbuf: 2 slots
        pltpu.VMEM((2 * win, cols), jnp.float32),    # staged windows
        pltpu.VMEM((2, win, cols), jnp.uint8),       # flush: per side
        pltpu.VMEM((win, cols), jnp.uint8),          # rbuf: merge path
        pltpu.VMEM((2, ALIGN, cols), jnp.float32),   # head: per side
        pltpu.SemaphoreType.DMA((5,)),
    ]


def partition_stream(mat_hbm, ws_hbm, scratch, begin, count, decide,
                     *, blk: int):
    """The pipelined block stream both partition kernels run: stable
    partition of ``mat_hbm[begin, begin+count)`` in place, rights via
    ``ws_hbm``. ``decide(mat_i32, mat_f, valid)`` returns the block's
    ``(go_left, go_right)`` [win, 1] i32 0/1 masks (already masked by
    ``valid``): the decision alone, in both kernels (the megakernel
    histograms the smaller child in a stream of its own, after this
    one has returned). Returns ``(NL, merge-path windows)``. Every
    write has landed by then, the back-copy's included, and the
    ``inbuf`` slots and their semaphores are free.

    ONE compaction a forward block (module docstring): both masks
    turned into rows and prefix-summed in one product, one
    destination-major one-hot, one permutation product that fills the
    lefts' window and, behind it, the rights'. The back-copy runs
    none: its rows are compact already, so a block is rolled by the
    one shift every block of a call shares.

    No DMA wait sits on the critical path: block k+1 is read while
    block k computes; each window write is waited only when its
    side's next window is ready to go, a whole block later; and a
    window is read back (``rbuf``) only where it holds rows the stream
    does not own.
    """
    inbuf, staged, flush, rbuf, head, sems = scratch
    # counted where the helper enters a kernel's trace, like
    # ``learner.megakernel_traces``
    tel = get_telemetry()
    tel.count("kernels.partition_pipelined")
    win = blk + ALIGN
    nblk = pl.cdiv(count, blk)
    base = (begin // ALIGN) * ALIGN
    shift = begin - base
    seg_end = begin + count
    outs = (mat_hbm, ws_hbm)

    row_w = jax.lax.broadcasted_iota(jnp.int32, (win, 1), 0)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (ALIGN, 1), 0)
    one, zero = jnp.float32(1), jnp.float32(0)
    # inclusive prefix-sum operator on ROWS: tri[s, d] = s <= d
    tri_bf = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (win, win), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (win, win), 1),
        one, zero).astype(jnp.bfloat16)
    # masks as columns -> masks as rows: lane 0 of ``sel_cols`` is the
    # lefts' mask, lane 1 the rights'; ``pick`` @ ``sel_cols``^T (the
    # MXU's own transposed-operand form) has them as rows 0 and 1
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (win, 128), 1)
    pick = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (ALIGN, 128), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (ALIGN, 128), 1),
        one, zero).astype(jnp.bfloat16)
    # destination row of the one-hot, destination-major
    dst_w = jax.lax.broadcasted_iota(jnp.int32, (win + PAD, win), 0)

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
        return mat_i32, mat_f

    def write_window(side, r0, dest, n, fast, inflight):
        """``staged[r0, r0+win)``, whose rows from ``dest % 8`` on are
        the ``n`` rows for ``outs[side][dest, dest+n)``, goes out
        through the 8-aligned window that holds ``dest``; the write is
        left in flight. Returns the window's first row (the side's
        next ``inflight``).

        ``fast``: every window row outside [dest, dest+n) is either
        one of the up-to-7 rows before ``dest`` in its granule, which
        this side's previous window wrote and ``head`` carries, or
        dead (consumed and not yet rewritten, or workspace scratch)
        whatever it holds -- the other side's rows of the same
        product, rows no product has written: nothing is read back.
        Otherwise a read-merge-write keeps the neighbours' and the
        unconsumed rows.
        """
        wstart = (dest // ALIGN) * ALIGN
        dshift = dest - wstart
        r0 = r0 if isinstance(r0, int) else pl.multiple_of(r0, ALIGN)
        w_rows = pl.ds(r0, win)
        # windows of one side overlap, so its previous write must have
        # landed before this one starts (and before a merge reads the
        # window, and before ``flush[side]`` is refilled)
        drain(side, inflight)

        def carry_head():
            h_rows = pl.ds(r0, ALIGN)
            staged[h_rows, :] = jnp.where(
                row8 < dshift, head[side], staged[h_rows, :])

        def merge():
            cp = pltpu.make_async_copy(window(outs[side], wstart), rbuf,
                                       sems.at[_SEM_RBUF])
            cp.start()
            cp.wait()
            keep = (row_w >= dshift) & (row_w < dshift + n)
            staged[w_rows, :] = jnp.where(
                keep, staged[w_rows, :],
                rbuf[...].astype(jnp.int32).astype(jnp.float32))

        if fast is True:
            carry_head()
        else:
            pl.when(fast)(carry_head)
            pl.when(jnp.logical_not(fast))(merge)
        flush[side] = staged[w_rows, :].astype(jnp.int32).astype(
            jnp.uint8)
        write(side, wstart).start()
        # the rows before the side's next ``dest`` in its granule
        nxt = r0 + ((dshift + n) // ALIGN) * ALIGN
        head[side] = staged[pl.ds(pl.multiple_of(nxt, ALIGN), ALIGN), :]
        return wstart

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
        mat_i32, mat_f = load_block(slot)
        rem = jnp.minimum(count - k * blk, blk)
        # all masks kept as i32 0/1: Mosaic cannot narrow i8 vectors to
        # i1, which jnp bool intermediates would require
        valid = jnp.where((row_w >= shift) & (row_w < shift + rem),
                          1, 0)                         # [win, 1] i32
        gl, gr = decide(mat_i32, mat_f, valid)
        # the compaction, counted where it enters a kernel's trace: a
        # second one in this block, or one in the back-copy, reads 2
        # or 3 a stream. Every operand as the MXU takes it (none
        # transposed by the VPU), and all the slot arithmetic on
        # [8, win] rows, not on [win, 1] columns of one lane a vreg
        tel.count("kernels.partition_one_compaction")
        sel_cols = jnp.where(
            lane_w == 0, gl, jnp.where(lane_w == 1, gr, 0)).astype(
            jnp.float32).astype(jnp.bfloat16)           # [win, 128]
        sel = jax.lax.dot_general(
            pick, sel_cols, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [8, win] 0/1
        cs = jax.lax.dot_general(
            sel.astype(jnp.bfloat16), tri_bf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(
            jnp.int32)                                  # [8, win] incl
        nl = cs[_L, win - 1]
        nr = rem - nl
        # lefts to ``dest_l``'s place in its window; rights to
        # ``dest_r``'s place in a window of their own, which begins at
        # the first granule boundary past the lefts
        dshift_l = dest_l % ALIGN
        r0 = ((dshift_l + nl + ALIGN - 1) // ALIGN) * ALIGN
        slot_of = jnp.where(
            sel[_L:_L + 1, :] > 0.5, dshift_l + cs[_L:_L + 1, :] - 1,
            jnp.where(sel[_R:_R + 1, :] > 0.5,
                      r0 + dest_r % ALIGN + cs[_R:_R + 1, :] - 1,
                      -1))                              # [1, win]
        pt = jnp.where(dst_w == slot_of, one, zero).astype(
            jnp.bfloat16)                               # [win+PAD, win]
        staged[0:win + PAD, :] = jax.lax.dot_general(
            pt, mat_f.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [win+PAD, C]
        # the left window ends at or before the last row this block
        # consumed: false while fewer than ~8 rows have gone right
        # (block 0 always) and at the segment's end
        fast_l = forward_fast(begin, k, rem, dest_l, blk)
        # the rights first: a merge of the left window rewrites every
        # row of it in ``staged``, the rights in its tail too
        fly_r = write_window(_R, r0, dest_r, nr, True, fly_r)
        fly_l = write_window(_L, 0, dest_l, nl, fast_l, fly_l)
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
        _, mat_f = load_block(slot)
        cnt_j = jnp.minimum(nr_total - j * blk, blk)
        # the rows are compact already and ``blk`` is a multiple of 8,
        # so every block goes ``dest_l % 8`` rows down its window: a
        # roll. What wraps round lands in the head rows, which
        # ``head`` or the merge replaces; rows past ``cnt_j`` exist in
        # the last block alone, which always merges
        dest = dest_l + j * blk
        staged[0:win, :] = pltpu.roll(mat_f, dest % ALIGN, 0)
        fast = back_fast(seg_end, j, dest_l, blk)
        fly_l = write_window(_L, 0, dest, cnt_j, fast, fly_l)
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


def stream_compactions(count: int, blk: int = 512) -> int:
    """Compactions (a one-hot and its permutation product over whole
    rows) one call runs: one a forward block, none in the back-copy
    (the parent of PR 34 ran one a window, ``stream_windows``). The
    program's side of the rule is read off its trace: the products
    over whole rows in the forward and the back-copy loop's body
    (``tools/check_kernels_on_chip.py`` ``traced_products``)."""
    return -(-count // blk)


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

    def decide(mat_i32, mat_f, valid):
        del mat_f
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
