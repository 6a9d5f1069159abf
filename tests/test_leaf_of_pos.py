"""Position -> leaf of the partitioned matrix (ops/leaf_of_pos.py)
against an oracle that paints each used leaf's segment, against the
search it replaced, on mesh shards with empty local segments, and on
both sides of the bound on num_leaves (ISSUE 26); and every case again
with a table of f32 leaf values painted in place of the leaf indices,
bit-equal to ``table[leaf_of_pos(...)]`` (ISSUE 36)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from lightgbm_tpu.ops import leaf_of_pos as lp


def paint(begin, cnt, k, n):
    """The plain loop over segments. Used leaves partition [0, n)."""
    out = np.full(n, -1, np.int32)
    for leaf in range(k):
        out[begin[leaf]:begin[leaf] + cnt[leaf]] = leaf
    assert (out >= 0).all()
    return out


def present_search(begin, cnt, k, n):
    """The lines this pass replaced (learner/partitioned.py at PR 24),
    verbatim."""
    big_l = begin.shape[0]
    used = jnp.arange(big_l) < k
    begin_eff = jnp.where(used, begin, n + 1)
    order_leaves = jnp.argsort(begin_eff)
    bounds = begin_eff[order_leaves]
    pos = jnp.arange(n)
    seg_idx = jnp.searchsorted(bounds, pos, side="right") - 1
    return order_leaves[jnp.clip(seg_idx, 0, big_l - 1)].astype(jnp.int32)


def partition(seed, n, big_l, k, empties=0):
    """k used leaves in random leaf order over [0, n), ``empties`` of
    them without a row; the other big_l - k entries are garbage."""
    rng = np.random.RandomState(seed)
    full = k - empties
    cuts = np.sort(rng.choice(np.arange(1, n), full - 1, replace=False))
    b = np.concatenate([[0], cuts]).astype(np.int32)
    c = np.diff(np.concatenate([b, [n]])).astype(np.int32)
    # an empty segment begins where another begins or where the last ends
    eb = rng.choice(np.concatenate([b, [n]]), empties).astype(np.int32)
    begin = rng.randint(0, n + 2, big_l).astype(np.int32)
    cnt = rng.randint(0, n, big_l).astype(np.int32)
    slots = rng.permutation(k)
    begin[slots] = np.concatenate([b, eb])
    cnt[slots] = np.concatenate([c, np.zeros(empties, np.int32)])
    return begin, cnt


def run(begin, cnt, k, n, table=None):
    """The leaves by position, or ``table``'s f32 entries by position."""
    fn = jax.jit(functools.partial(lp.leaf_of_pos, n=n, interpret=True))
    got = fn(jnp.asarray(begin), jnp.asarray(cnt), jnp.int32(k),
             None if table is None else jnp.asarray(table))
    assert got.shape == (n,)
    assert got.dtype == (jnp.int32 if table is None else jnp.float32)
    return np.asarray(got)


def value_table(big_l, seed=0):
    """f32 leaf values with what a select must carry and arithmetic
    would not: negatives, both zeros, denormals of both signs, the
    smallest one. The first used leaves hold one of each."""
    rng = np.random.RandomState(seed)
    t = rng.randn(big_l).astype(np.float32)
    t[0::6] = -np.abs(t[0::6]) - 0.5
    t[1::6] = -0.0
    t[2::6] = np.float32(1e-41) * np.arange(1, len(t[2::6]) + 1)
    t[3::6] = 0.0
    t[4::6] = -np.float32(3e-40)
    if big_l > 5:
        t[5] = np.float32(1e-45)            # the smallest denormal
    assert (t[2::6] > 0).all() and (t[2::6] < 1.2e-38).all()
    return t


def bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def assert_values_follow_the_leaves(begin, cnt, k, n, seed=0):
    """The painted f32 block against the table read by the painted
    leaf, bit for bit; returns the leaves."""
    table = value_table(len(begin), seed)
    leaves = run(begin, cnt, k, n)
    got = run(begin, cnt, k, n, table)
    assert np.array_equal(bits(got), bits(table[leaves]))
    return leaves


# n is never a multiple of the block (131072 positions, or all of a
# smaller n rounded up to 8 rows of 128); 300001 spans three blocks
CASES = [
    pytest.param(2, 2, 1000, id="a-L2-full"),
    pytest.param(15, 15, 5003, id="a-L15-full"),
    pytest.param(255, 255, 300001, id="a-L255-full-3blocks"),
    pytest.param(15, 6, 5003, id="b-L15-k6-garbage"),
    pytest.param(255, 100, 300001, id="b-L255-k100-garbage"),
    pytest.param(2, 1, 1000, id="c-L2-root"),
    pytest.param(255, 1, 140001, id="c-L255-root-2blocks"),
]


@pytest.mark.parametrize("big_l,k,n", CASES)
def test_against_the_painted_segments(big_l, k, n):
    begin, cnt = partition(big_l * 7 + k, n, big_l, k)
    assert np.array_equal(run(begin, cnt, k, n), paint(begin, cnt, k, n))


@pytest.mark.parametrize("big_l,k,n", CASES)
def test_bit_equal_to_the_search_it_replaced(big_l, k, n):
    begin, cnt = partition(big_l * 11 + k, n, big_l, k)
    want = jax.jit(functools.partial(present_search, n=n))(
        jnp.asarray(begin), jnp.asarray(cnt), jnp.int32(k))
    assert np.array_equal(run(begin, cnt, k, n), np.asarray(want))


def test_the_search_it_replaced_is_wrong_on_an_empty_segment():
    """What ISSUE 26 found: of two equal begins the search takes the
    last, so an empty leaf with the higher index owns its neighbour."""
    begin = np.array([0, 100, 100], np.int32)
    cnt = np.array([100, 50, 0], np.int32)
    got = np.asarray(present_search(jnp.asarray(begin), jnp.asarray(cnt),
                                    jnp.int32(3), n=150))
    assert (got[100:] == 2).all()
    assert np.array_equal(run(begin, cnt, 3, 150), paint(begin, cnt, 3, 150))


@pytest.mark.parametrize("begin,cnt", [
    # the empty leaf has the higher index than the one it shares with
    pytest.param([0, 100, 100], [100, 50, 0], id="d-empty-higher"),
    pytest.param([0, 100, 100], [100, 0, 50], id="d-empty-lower"),
    pytest.param([0, 0, 0, 60], [0, 60, 0, 90], id="d-empties-at-0"),
    pytest.param([150, 0, 150, 70], [0, 70, 0, 80], id="d-empties-at-n"),
    pytest.param([0, 0, 0], [0, 0, 150], id="d-only-the-last-has-rows"),
])
def test_empty_used_segments_own_nothing(begin, cnt):
    begin, cnt = np.array(begin, np.int32), np.array(cnt, np.int32)
    k = len(begin)
    # one unused garbage leaf behind the used ones
    begin, cnt = np.append(begin, 7), np.append(cnt, 31)
    assert np.array_equal(run(begin, cnt, k, 150),
                          paint(begin, cnt, k, 150))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_partitions_with_empties(seed):
    begin, cnt = partition(seed, 140001, 63, 40, empties=9)
    assert np.array_equal(run(begin, cnt, 40, 140001),
                          paint(begin, cnt, 40, 140001))


def test_no_live_segment_reads_the_first_leaf():
    """A shard without a row: every position (all padding) reads leaf 0,
    as the search gave."""
    z = np.zeros(15, np.int32)
    assert (run(z, z, 3, 1000) == 0).all()


def test_inside_a_shard_map_with_different_local_partitions():
    """Two shards, the same leaves, their own local segments; leaf 1
    holds no row of shard 0 and leaf 2 none of shard 1."""
    n = 5003
    begin = np.array([[0, 3000, 3000, 9], [0, 1200, n, 9]], np.int32)
    cnt = np.array([[3000, 0, n - 3000, 9], [1200, n - 1200, 0, 9]],
                   np.int32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def shard(b, c):
        return lp.leaf_of_pos(b[0], c[0], jnp.int32(3), n=n,
                              interpret=True)

    got = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=P("data"), check_vma=False))(
            jnp.asarray(begin), jnp.asarray(cnt))
    got = np.asarray(got).reshape(2, n)
    for s in range(2):
        assert np.array_equal(got[s], paint(begin[s], cnt[s], 3, n)), s


@pytest.mark.parametrize("k", [1, 40])
def test_both_sides_of_the_bound_on_num_leaves_agree(k):
    """The construction is chosen by num_leaves alone; the same used
    segments under a table one leaf past the bound read the same."""
    at, past = lp.DENSE_MAX_LEAVES, lp.DENSE_MAX_LEAVES + 1
    assert lp.uses_block_pass(at) and not lp.uses_block_pass(past)
    n = 140001
    begin, cnt = partition(k, n, at, k)
    dense = run(begin, cnt, k, n)
    search = run(np.append(begin, 5), np.append(cnt, 5), k, n)
    assert np.array_equal(dense, paint(begin, cnt, k, n))
    assert np.array_equal(dense, search)


def test_the_search_side_masks_empty_segments_too():
    big_l = lp.DENSE_MAX_LEAVES + 1
    begin = np.zeros(big_l, np.int32)
    cnt = np.zeros(big_l, np.int32)
    begin[:3] = [0, 100, 100]
    cnt[:3] = [100, 50, 0]
    assert np.array_equal(run(begin, cnt, 3, 150),
                          paint(begin, cnt, 3, 150))


# ---------------------------------------------------------------------
# ISSUE 36: the same pass painting a [num_leaves] table's entry (the
# fused driver's leaf values); every case above once more


def test_the_value_table_holds_what_only_a_select_carries():
    t = value_table(15)
    assert (t < 0).any() and (bits(t) == 0x80000000).any()
    assert (bits(t) == 0).any() and (bits(t) == 1).any()
    denormal = (np.abs(t) > 0) & (np.abs(t) < np.finfo(np.float32).tiny)
    assert (denormal & (t > 0)).any() and (denormal & (t < 0)).any()


@pytest.mark.parametrize("big_l,k,n", CASES)
def test_values_bit_equal_to_the_table_by_leaf(big_l, k, n):
    begin, cnt = partition(big_l * 7 + k, n, big_l, k)
    leaves = assert_values_follow_the_leaves(begin, cnt, k, n,
                                             seed=big_l + k)
    assert np.array_equal(leaves, paint(begin, cnt, k, n))


def test_values_where_the_search_it_replaced_is_wrong():
    begin = np.array([0, 100, 100], np.int32)
    cnt = np.array([100, 50, 0], np.int32)
    table = value_table(3)
    got = run(begin, cnt, 3, 150, table)
    assert (bits(got[:100]) == bits(table[:1])).all()
    assert (bits(got[100:]) == bits(table[1:2])).all()      # -0.0


@pytest.mark.parametrize("begin,cnt", [
    pytest.param([0, 100, 100], [100, 50, 0], id="d-empty-higher"),
    pytest.param([0, 100, 100], [100, 0, 50], id="d-empty-lower"),
    pytest.param([0, 0, 0, 60], [0, 60, 0, 90], id="d-empties-at-0"),
    pytest.param([150, 0, 150, 70], [0, 70, 0, 80], id="d-empties-at-n"),
    pytest.param([0, 0, 0], [0, 0, 150], id="d-only-the-last-has-rows"),
])
def test_values_of_empty_used_segments_are_painted_nowhere(begin, cnt):
    begin, cnt = np.array(begin, np.int32), np.array(cnt, np.int32)
    k = len(begin)
    begin, cnt = np.append(begin, 7), np.append(cnt, 31)
    leaves = assert_values_follow_the_leaves(begin, cnt, k, 150)
    assert np.array_equal(leaves, paint(begin, cnt, k, 150))
    # distinct entries, so a value painted is a leaf that owns rows
    table = np.arange(1, k + 2, dtype=np.float32)
    got = run(begin, cnt, k, 150, table)
    assert set(got.tolist()) == {float(i + 1) for i in range(k)
                                 if cnt[i] > 0}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_values_on_random_partitions_with_empties(seed):
    begin, cnt = partition(seed, 140001, 63, 40, empties=9)
    leaves = assert_values_follow_the_leaves(begin, cnt, 40, 140001,
                                             seed=seed)
    assert np.array_equal(leaves, paint(begin, cnt, 40, 140001))


def test_values_with_no_live_segment_read_the_first_entry():
    z = np.zeros(15, np.int32)
    table = value_table(15)
    got = run(z, z, 3, 1000, table)
    assert (bits(got) == bits(table[:1])).all()


def test_values_inside_a_shard_map_with_different_local_partitions():
    """The mesh learners' call: replicated leaf values, each shard's
    own segments."""
    n = 5003
    begin = np.array([[0, 3000, 3000, 9], [0, 1200, n, 9]], np.int32)
    cnt = np.array([[3000, 0, n - 3000, 9], [1200, n - 1200, 0, 9]],
                   np.int32)
    table = value_table(4, seed=5)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def shard(b, c, t):
        return lp.leaf_of_pos(b[0], c[0], jnp.int32(3), t, n=n,
                              interpret=True)

    got = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P("data"), P("data"), P()),
        out_specs=P("data"), check_vma=False))(
            jnp.asarray(begin), jnp.asarray(cnt), jnp.asarray(table))
    got = np.asarray(got).reshape(2, n)
    for s in range(2):
        want = table[paint(begin[s], cnt[s], 3, n)]
        assert np.array_equal(bits(got[s]), bits(want)), s


@pytest.mark.parametrize("k", [1, 40])
def test_values_on_both_sides_of_the_bound_on_num_leaves_agree(k):
    at = lp.DENSE_MAX_LEAVES
    n = 140001
    begin, cnt = partition(k, n, at, k)
    table = value_table(at + 1, seed=k)
    dense = run(begin, cnt, k, n, table[:at])
    search = run(np.append(begin, 5), np.append(cnt, 5), k, n,
                        table)
    assert np.array_equal(bits(dense), bits(table[paint(begin, cnt, k, n)]))
    assert np.array_equal(bits(dense), bits(search))


def test_values_on_the_search_side_mask_empty_segments_too():
    big_l = lp.DENSE_MAX_LEAVES + 1
    begin = np.zeros(big_l, np.int32)
    cnt = np.zeros(big_l, np.int32)
    begin[:3] = [0, 100, 100]
    cnt[:3] = [100, 50, 0]
    assert_values_follow_the_leaves(begin, cnt, 3, 150)
