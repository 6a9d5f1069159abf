"""Position -> leaf (or a leaf's table entry) of the partitioned
training matrix.

After a tree is grown the partitioned learner holds its leaves as row
segments ``[leaf_begin[l], leaf_begin[l] + leaf_cnt[l])`` of the
matrix; the score update wants the inverse, "which leaf owns position
p", and of that leaf only its value. Both are piecewise-constant
functions of p with at most ``num_leaves`` pieces, so they need no
search and no gather over the positions: the live segments are sorted
by their begin (a sort of ``num_leaves`` elements), and ONE pass writes
each block of positions as the entry of the segment that owns the
block's first position, overwritten by compare-and-select with the few
segments that begin inside the block. Which segments those are is a
``[blocks + 1]`` table of counts made outside the kernel; the
per-position work is one store plus two vector operations for each
boundary inside the block, whatever ``num_leaves`` is. What is painted
is the caller's: the leaf indices, or a ``[num_leaves]`` table's entry
for each leaf (the fused driver's leaf values, PR 36), so no table is
read by position afterwards. The pass only selects: a 32-bit table goes
through it as int32 words and comes out bit for bit.

A segment that holds no row (a used leaf with no local rows on a mesh
shard) is masked like an unused leaf: it owns no position, and its
``begin`` equals a neighbour's, which a search over the begins would
resolve to the wrong one of the two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jit_registry import register_jit

LANES = 128
# positions per block = BLOCK_ROWS * LANES (a 512 KB int32 tile)
BLOCK_ROWS = 1024

# Above this many leaves the begins and entries (two int32 tables in
# SMEM) stop being small and nothing has been timed: the search stays.
# Alone on 10.5 M positions, every leaf used (TPU v5e, chip run of
# PR 26; PERF.md section 6), a tree's pass took
#   num_leaves   block pass   search (8 / 10 / 12 dependent passes)
#        255       0.23 ms        925 ms
#       1023       0.32 ms        784 ms
#       4095       0.82 ms       1183 ms
# so the block pass wins through the largest size measured and the
# bound sits just above it.
DENSE_MAX_LEAVES = 4096

_NEVER = jnp.iinfo(jnp.int32).max     # a begin no position reaches


def uses_block_pass(num_leaves: int) -> bool:
    """Static: which of the two constructions ``leaf_of_pos`` traces."""
    return num_leaves <= DENSE_MAX_LEAVES


def _block_kernel(upto_ref, bounds_ref, entries_ref, out_ref, *, rows):
    i = pl.program_id(0)
    lo = upto_ref[i]            # segments that begin at or before the
    hi = upto_ref[i + 1]        # block's first position / the next's
    pos = (i * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, LANES), 0)) * LANES \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    out_ref[...] = jnp.full((rows, LANES),
                            entries_ref[jnp.maximum(lo - 1, 0)], jnp.int32)

    def later_segment(j, carry):
        out_ref[...] = jnp.where(pos >= bounds_ref[j], entries_ref[j],
                                 out_ref[...])
        return carry

    jax.lax.fori_loop(lo, hi, later_segment, 0)


@register_jit("leaf_of_pos")
@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def leaf_of_pos(leaf_begin, leaf_cnt, k, table=None, *, n: int,
                interpret: bool):
    """``[n]``: for each position the leaf whose segment holds it
    (int32; ``table`` None) or that leaf's entry of ``table``, a
    ``[num_leaves]`` array of a 32-bit dtype (bit-equal to
    ``table[leaf_of_pos(...)]`` with no read by position). Segments
    come from the first ``k`` entries of the ``[num_leaves]`` segment
    tables (the rest is garbage). Positions no live segment holds
    (there are none when the segments partition ``[0, n)``) read the
    segment before them, or the first."""
    big_l = leaf_begin.shape[0]
    # begins ascending with their leaves; unused and empty leaves go to
    # the end under a begin that never fires
    live = (jnp.arange(big_l) < k) & (leaf_cnt > 0)
    begin_eff = jnp.where(live, leaf_begin, _NEVER)
    leaves = jnp.argsort(begin_eff).astype(jnp.int32)
    bounds = begin_eff[leaves]
    # a gather over num_leaves entries, not over the positions
    entries = leaves if table is None else table[leaves]
    if not uses_block_pass(big_l):
        seg = jnp.searchsorted(bounds, jnp.arange(n), side="right") - 1
        return entries[jnp.clip(seg, 0, big_l - 1)]
    rows = min(BLOCK_ROWS, pl.cdiv(pl.cdiv(n, LANES), 8) * 8)
    blocks = pl.cdiv(n, rows * LANES)
    starts = jnp.arange(blocks + 1, dtype=jnp.int32) * (rows * LANES)
    upto = jnp.sum(bounds[None, :] <= starts[:, None], axis=1,
                   dtype=jnp.int32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_block_kernel, rows=rows),
        out_shape=jax.ShapeDtypeStruct((blocks * rows, LANES), jnp.int32),
        grid=(blocks,),
        in_specs=[smem, smem, smem],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        interpret=interpret,
        name="leaf_of_pos_blocks",
    )(upto, bounds, jax.lax.bitcast_convert_type(entries, jnp.int32))
    return jax.lax.bitcast_convert_type(out.reshape(-1)[:n], entries.dtype)
