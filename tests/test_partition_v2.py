"""Partition kernel vs oracle, interpret mode.

Historically this file covered the sub-tiled v2 partition kernel; the
split-step megakernel (ops/split_step_pallas.py) made the v1/v2 split
dead weight and v2 was deleted — the oracle suite now points at the
surviving ``partition_segment`` so the consolidated module keeps the
exact coverage the v2 kernel had (stability, missing routing,
categorical LUT, all-one-side edge cases).
"""

import functools

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.hist_pallas import (build_matrix, extract_row_ids,
                                          pack_gh)
from lightgbm_tpu.ops.partition_pallas import (bitset_to_lut,
                                               partition_segment)


def _mk(n, f, b, seed=0):
    rng = np.random.RandomState(seed)
    binned = rng.randint(0, b, (n, f)).astype(np.uint8)
    mat = build_matrix(jnp.asarray(binned), 2048)
    mat = pack_gh(mat, f, jnp.asarray(rng.randn(n).astype(np.float32)),
                  jnp.asarray(rng.rand(n).astype(np.float32)),
                  jnp.asarray(np.ones(n, np.float32)))
    return binned, mat


@pytest.mark.parametrize("begin,count", [
    (0, 3000), (8, 2992), (13, 2048), (517, 997), (2989, 11), (5, 3)])
def test_partition_matches_oracle_numerical(begin, count):
    n, f, b = 3000, 7, 64
    binned, mat = _mk(n, f, b)
    col, thr = 3, 30
    lut = jnp.zeros((1, 256), jnp.float32)
    args = (jnp.int32(begin), jnp.int32(count), jnp.int32(col),
            jnp.int32(thr), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.int32(b), jnp.int32(0), lut)
    m2, _, nl = partition_segment(mat, jnp.zeros_like(mat), *args,
                                  blk=256, interpret=True)
    sl = slice(begin, begin + count)
    go_left = binned[sl, col] <= thr
    assert int(nl[0]) == int(go_left.sum())
    rid = np.asarray(extract_row_ids(m2, f, mat.shape[0]))
    rid_orig = np.arange(n)
    # stability: left rows in original order, then right rows in order
    want = np.concatenate([rid_orig[sl][go_left], rid_orig[sl][~go_left]])
    np.testing.assert_array_equal(rid[sl], want)
    # rows outside the segment untouched
    np.testing.assert_array_equal(rid[:begin], rid_orig[:begin])
    np.testing.assert_array_equal(rid[begin + count:n],
                                  rid_orig[begin + count:n])
    # block size must not change the result (the old v2 coverage)
    m1, _, nl1 = partition_segment(mat, jnp.zeros_like(mat), *args,
                                   blk=512, interpret=True)
    assert int(nl1[0]) == int(nl[0])
    np.testing.assert_array_equal(np.asarray(m2)[:n], np.asarray(m1)[:n])


def test_partition_missing_and_categorical():
    n, f, b = 2000, 5, 32
    binned, mat = _mk(n, f, b, seed=3)
    # NaN-missing: bin b-1 is the NaN bin, default_left=1
    col = 2
    args = (jnp.int32(100), jnp.int32(1500), jnp.int32(col),
            jnp.int32(10), jnp.int32(1), jnp.int32(2), jnp.int32(0),
            jnp.int32(b), jnp.int32(0), jnp.zeros((1, 256), jnp.float32))
    m2, _, nl = partition_segment(mat, jnp.zeros_like(mat), *args,
                                  blk=256, interpret=True)
    sl = slice(100, 1600)
    bv = binned[sl, col]
    go_left = np.where(bv == b - 1, True, bv <= 10)
    assert int(nl[0]) == int(go_left.sum())

    # categorical via bitset LUT
    cats = np.array([1, 7, 19], np.int64)
    bits = np.zeros(8, np.uint32)
    for cv in cats:
        bits[cv // 32] |= np.uint32(1) << np.uint32(cv % 32)
    lut = bitset_to_lut(jnp.asarray(bits))
    args = (jnp.int32(0), jnp.int32(n), jnp.int32(col), jnp.int32(0),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(b),
            jnp.int32(1), lut)
    m3, _, nl3 = partition_segment(mat, jnp.zeros_like(mat), *args,
                                   blk=256, interpret=True)
    left = np.isin(binned[:, col], cats)
    assert int(nl3[0]) == int(left.sum())
    rid = np.asarray(extract_row_ids(m3, f, mat.shape[0]))[:n]
    np.testing.assert_array_equal(
        rid, np.concatenate([np.arange(n)[left], np.arange(n)[~left]]))


def test_partition_all_one_side():
    n, f, b = 1500, 4, 16
    binned, mat = _mk(n, f, b, seed=5)
    lut = jnp.zeros((1, 256), jnp.float32)
    for thr, side in [(b, "left"), (-1, "right")]:
        m2, _, nl = partition_segment(
            mat, jnp.zeros_like(mat), jnp.int32(11), jnp.int32(1200),
            jnp.int32(1), jnp.int32(thr), jnp.int32(0), jnp.int32(0),
            jnp.int32(0), jnp.int32(b), jnp.int32(0), lut,
            blk=256, interpret=True)
        assert int(nl[0]) == (1200 if side == "left" else 0)
        rid = np.asarray(extract_row_ids(m2, f, mat.shape[0]))
        np.testing.assert_array_equal(rid[:1500], np.arange(1500))


# ---- PR 28: the pipelined stream (prefetched input, write heads
# carried in VMEM, window writes left in flight) -----------------------

from lightgbm_tpu.ops import partition_pallas
from lightgbm_tpu.ops.partition_pallas import (ALIGN, forward_fast,
                                               merge_windows,
                                               stream_compactions,
                                               stream_windows)

P_BLK, P_F, P_B, P_COL, P_THR = 512, 5, 64, 2, 30
P_ROWS = 7 + 5 * P_BLK + 3 + 40          # largest begin + count, + slack
P_SENTINEL = 0xAB

SPLITS = {
    "all_left": lambda i, rng: np.ones_like(i, bool),
    "all_right": lambda i, rng: np.zeros_like(i, bool),
    "one_right_in_100": lambda i, rng: i % 100 != 57,
    "half": lambda i, rng: rng.rand(i.size) < 0.5,
    "alternating": lambda i, rng: i % 2 == 0,
}


@functools.lru_cache(maxsize=None)
def _stream_inputs(split):
    """Matrix whose column P_COL sends row i left as SPLITS[split]
    says, for the numeric threshold and for the category table."""
    rng = np.random.RandomState(11)
    left = SPLITS[split](np.arange(P_ROWS), rng)
    binned = rng.randint(0, P_B, (P_ROWS, P_F)).astype(np.uint8)
    binned[:, P_COL] = np.where(left, rng.randint(0, P_THR + 1, P_ROWS),
                                rng.randint(P_THR + 1, P_B, P_ROWS))
    mat = build_matrix(jnp.asarray(binned), 2048)
    mat = pack_gh(mat, P_F,
                  jnp.asarray(rng.randn(P_ROWS).astype(np.float32)),
                  jnp.asarray(rng.rand(P_ROWS).astype(np.float32)),
                  jnp.ones((P_ROWS,), jnp.float32))
    bits = np.zeros(8, np.uint32)
    for cv in range(P_THR + 1):
        bits[cv // 32] |= np.uint32(1) << np.uint32(cv % 32)
    return left, mat, np.asarray(mat), bitset_to_lut(jnp.asarray(bits))


def _run_stream(split, begin, count, use_lut, mat=None):
    _, mat0, _, lut = _stream_inputs(split)
    mat = mat0 if mat is None else mat
    ws = jnp.full(mat.shape, P_SENTINEL, jnp.uint8)
    # the category table decides where the LUT path is compiled in;
    # the threshold is then one no row passes
    thr, is_cat = (-1, 1) if use_lut else (P_THR, 0)
    return partition_segment(
        mat, ws, jnp.int32(begin), jnp.int32(count), jnp.int32(P_COL),
        jnp.int32(thr), jnp.int32(0), jnp.int32(0), jnp.int32(0),
        jnp.int32(P_B), jnp.int32(is_cat), lut, blk=P_BLK,
        interpret=True, use_lut_path=use_lut)


def _check_stream(split, begin, count, res, mat_np=None, left=None):
    """Stable order, NL, the merge-window count, and every row the
    call does not own untouched."""
    left0, _, mat0_np, _ = _stream_inputs(split)
    left = left0 if left is None else left
    mat_np = mat0_np if mat_np is None else mat_np
    m2, w2, nl = (np.asarray(a) for a in res)
    sl = slice(begin, begin + count)
    go = left[sl]
    assert int(nl[0]) == int(go.sum())
    want = np.concatenate([mat_np[sl][go], mat_np[sl][~go]])
    np.testing.assert_array_equal(m2[sl], want)      # whole rows, in order
    np.testing.assert_array_equal(m2[:begin], mat_np[:begin])
    np.testing.assert_array_equal(m2[begin + count:],
                                  mat_np[begin + count:])
    # the workspace is scratch up to the end of the last rights window
    nr = count - int(go.sum())
    assert (w2[(nr // ALIGN) * ALIGN + P_BLK + ALIGN:]
            == P_SENTINEL).all()
    np.testing.assert_array_equal(w2[:nr], mat_np[sl][~go])
    nl_by_block = [int(go[k:k + P_BLK].sum())
                   for k in range(0, count, P_BLK)]
    assert int(nl[1]) == merge_windows(begin, count, nl_by_block, P_BLK)


@pytest.mark.parametrize("use_lut", [False, True])
@pytest.mark.parametrize("split", list(SPLITS))
@pytest.mark.parametrize("count", [0, 1, 7, 511, 512, 513, 520,
                                   5 * 512 + 3])
@pytest.mark.parametrize("begin", range(8))
def test_pipelined_stream_property(begin, count, split, use_lut):
    _check_stream(split, begin, count,
                  _run_stream(split, begin, count, use_lut))


def test_merge_windows_is_the_rule():
    # 5 blocks + 3 rows, half left: block 0 (no right has gone yet),
    # back window 0 and the last back window; at an even split the
    # last left window ends far inside the consumed rows
    nlb = [256] * 5 + [1]
    assert merge_windows(3, 5 * 512 + 3, nlb, 512) == 3
    # all left: no right ever frees a window, every block merges
    assert merge_windows(0, 2048, [512] * 4, 512) == 4
    # all right: block 0 only, then back window 0 and the last one
    assert merge_windows(0, 2048, [0] * 4, 512) == 3
    assert merge_windows(5, 0, [], 512) == 0


class _LateCopies:
    """``pltpu`` stand-in for the hazard tests: the chosen async copies
    land when they are WAITED for, not when they are started — the
    other end of what the hardware may do (the interpreter lands them
    at ``start``). A kernel that is right at both ends reads no window
    before its write was waited for, refills no buffer in flight, and
    leaves no write un-waited (that one never lands)."""

    def __init__(self, real, late_reads, late_writes):
        self._real, self._r, self._w = real, late_reads, late_writes

    def __getattr__(self, name):
        return getattr(self._real, name)

    def make_async_copy(self, src, dst, sem):
        cp = self._real.make_async_copy(src, dst, sem)
        is_write = "any" in str(getattr(dst, "ref", dst).aval)
        if not (self._w if is_write else self._r):
            return cp

        class Late:
            def start(self):
                pass

            def wait(self):
                cp.start()
                cp.wait()
        return Late()


@pytest.fixture
def late_copies(monkeypatch):
    import jax

    def arm(reads, writes):
        jax.clear_caches()
        monkeypatch.setattr(
            partition_pallas, "pltpu",
            _LateCopies(partition_pallas.pltpu, reads, writes))
    yield arm
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("reads,writes,hazard", [
    # the prefetched window of block k+1 begins inside block k's rows,
    # which block k's fast left write overwrites: read late, those
    # rows hold the NEW content; they must reach nothing
    (True, False, "prefetch_reads_rows_a_fast_write_touched"),
    # a merge-path read must see the last write of its side, and no
    # flush buffer may be refilled while its write is in flight
    (False, True, "merge_reads_after_its_sides_last_write"),
    # the back-copy's first workspace read, and whoever runs next,
    # must see every write
    (True, True, "every_write_lands_before_the_stream_returns"),
])
@pytest.mark.parametrize("begin,count,split", [
    (5, 5 * 512 + 3, "half"), (3, 5 * 512 + 3, "one_right_in_100"),
    (7, 1030, "alternating")])
def test_pipelined_stream_hazards(late_copies, reads, writes, hazard,
                                  begin, count, split):
    late_copies(reads, writes)
    res = _run_stream(split, begin, count, False)
    _check_stream(split, begin, count, res)
    # the next split of the same rows sees what this one wrote
    nl = int(res[2][0])
    if nl > 1:
        m1 = np.asarray(res[0])
        res2 = partition_segment(
            res[0], res[1], jnp.int32(begin), jnp.int32(nl),
            jnp.int32(0), jnp.int32(P_B // 2), jnp.int32(0),
            jnp.int32(0), jnp.int32(0), jnp.int32(P_B), jnp.int32(0),
            jnp.zeros((1, 256), jnp.float32), blk=P_BLK,
            interpret=True, use_lut_path=False)
        go = m1[begin:begin + nl, 0] <= P_B // 2
        np.testing.assert_array_equal(
            np.asarray(res2[0])[begin:begin + nl],
            np.concatenate([m1[begin:begin + nl][go],
                            m1[begin:begin + nl][~go]]))


def test_masked_rows_reach_nothing():
    """Rows a window holds beside the block's own (the neighbour's
    before ``begin``, whatever follows the segment) reach neither the
    decision nor a carried head: filled with 0xFF (bin 255, NaN
    payload) they change nothing and come back untouched."""
    begin, count, split = 5, 1030, "half"
    _, mat, mat_np, _ = _stream_inputs(split)
    poisoned = mat_np.copy()
    poisoned[:begin] = 0xFF
    poisoned[begin + count:begin + count + 600] = 0xFF
    res = _run_stream(split, begin, count, False,
                      mat=jnp.asarray(poisoned))
    _check_stream(split, begin, count, res, mat_np=poisoned)


# ---- PR 34: one compaction a block (both sides in one one-hot and one
# product; the back-copy a roll) ----------------------------------------

def _run_and_check(left, begin, count):
    """The stream over the "half" matrix with column P_COL rewritten so
    that row i goes left as ``left[i]`` says; returns its result."""
    _, _, mat_np, _ = _stream_inputs("half")
    mat_np = mat_np.copy()
    mat_np[:P_ROWS, P_COL] = np.where(left, 0, P_B - 1)
    res = _run_stream("half", begin, count, False,
                      mat=jnp.asarray(mat_np))
    _check_stream("half", begin, count, res, mat_np=mat_np, left=left)
    return res


BLOCK_SPLITS = {
    "all_left": lambda n: np.ones(n, bool),
    "all_right": lambda n: np.zeros(n, bool),
    "one_left": lambda n: np.arange(n) == 100,
    "one_right": lambda n: np.arange(n) != 100,
}


@pytest.mark.parametrize("path", ["fast", "merge"])
@pytest.mark.parametrize("block", list(BLOCK_SPLITS))
@pytest.mark.parametrize("dshift_r", range(ALIGN))
@pytest.mark.parametrize("dshift_l", range(ALIGN))
def test_one_compaction_seams(dshift_l, dshift_r, block, path):
    """Block 1 meets every pair of write-head offsets: its lefts start
    ``dshift_l`` rows into their window, its rights ``dshift_r`` rows
    into theirs, which begins at the first granule boundary past the
    lefts in the SAME staged product. All rows left (the rights'
    window holds a head alone), all right (it begins at the lefts' own
    granule), one row each way. ``fast`` (a whole block, a third
    behind it): the left window's dead tail now holds rights and is
    written as it is; ``merge`` (the segment's last 200 rows): the
    window reaches past the segment, is read back, and ``keep`` masks
    the rights out of it. Sentinels before, after and in the workspace
    (``_check_stream``)."""
    # lefts of block 0: its rights leave ``dshift_r``, and ``begin``
    # then puts the left head at ``dshift_l``
    l0 = P_BLK // 2 - dshift_r
    begin = (dshift_l - l0) % ALIGN
    n1, tail = (P_BLK, 77) if path == "fast" else (200, 0)
    count = P_BLK + n1 + tail
    left = np.zeros(P_ROWS, bool)
    rng = np.random.RandomState(dshift_l * 8 + dshift_r)
    left[begin:begin + P_BLK] = rng.permutation(np.arange(P_BLK) < l0)
    left[begin + P_BLK:begin + P_BLK + n1] = BLOCK_SPLITS[block](n1)
    left[begin + P_BLK + n1:begin + count] = rng.rand(tail) < 0.5
    assert (begin + l0) % ALIGN == dshift_l
    assert (P_BLK - l0) % ALIGN == dshift_r
    assert bool(forward_fast(begin, 1, n1, begin + l0, P_BLK)) \
        == (path == "fast")
    _run_and_check(left, begin, count)


@pytest.mark.parametrize("last", [1, 7, 8, 511])
@pytest.mark.parametrize("dshift", range(ALIGN))
def test_back_copy_rolls_by_one_shift(dshift, last):
    """The back-copy compacts nothing: every block of a call goes the
    same ``(begin + NL) % 8`` rows down its window (a roll, what wraps
    round replaced by the carried head or the merge). Two back blocks,
    the last of 1, 7, 8 and 511 rows, at every shift."""
    nl_total, nr_total = 700, P_BLK + last
    begin = (dshift - nl_total) % ALIGN
    count = nl_total + nr_total
    left = np.zeros(P_ROWS, bool)
    left[begin:begin + count] = np.random.RandomState(
        dshift * 1000 + last).permutation(np.arange(count) < nl_total)
    assert int(_run_and_check(left, begin, count)[2][0]) == nl_total


@pytest.mark.parametrize("count,nl,compactions,before", [
    (0, 0, 0, 0), (1, 1, 1, 2), (512, 0, 1, 3), (513, 256, 2, 5),
    (5 * 512 + 3, 1300, 6, 15), (10_500_000, 5_000_000, 20508, 51759)])
def test_stream_compactions_is_the_rule(count, nl, compactions, before):
    """One compaction a forward block, against one a window (two a
    forward block and one a back-copy block), ``before``."""
    assert stream_compactions(count, 512) == compactions
    assert stream_windows(count, nl, 512) == before


@pytest.mark.parametrize("use_lut", [False, True])
def test_traced_program_runs_the_rule(use_lut):
    """The count ``stream_compactions`` is held to, taken from the
    program itself in interpret mode: the forward loop's body holds
    ONE product over whole rows (the permutation, both sides' windows
    in one ``[win + PAD, cols]`` output) beside the two small ones
    that turn the masks into rows and prefix-sum them (and the table's
    lookup), the back-copy loop's body none. A second compaction in
    the block, or one in the back-copy, is a second whole-row product:
    the parent's bodies read 2 and 1."""
    from tools.check_kernels_on_chip import traced_products
    _, mat, _, lut = _stream_inputs("half")
    cols = mat.shape[1]
    win = P_BLK + ALIGN
    forward, back = traced_products(
        functools.partial(partition_pallas.partition_segment, blk=P_BLK,
                          interpret=True, use_lut_path=use_lut),
        mat, mat, *([jnp.int32(0)] * 9), lut)
    small = [(ALIGN, win), (ALIGN, win)]
    assert sorted(forward) == sorted(
        small + [(win + partition_pallas.PAD, cols)]
        + ([(win, 1)] if use_lut else []))
    assert back == []
    for count, nr in ((1, 0), (P_BLK, P_BLK), (5 * P_BLK + 3, 1300)):
        ran = sum(s[-1] == cols for s in forward) * -(-count // P_BLK) \
            + sum(s[-1] == cols for s in back) * -(-nr // P_BLK)
        assert ran == stream_compactions(count, P_BLK)
