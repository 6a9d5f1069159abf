"""A stray ``all_gather`` inside a mesh grow body: the committed GC401
multiset pins the data-parallel recipe's exact per-split traffic
({reduce-scatter: 1, all-gather: 1} — the reduce-scattered child
histogram plus ONE packed winner gather, learner/comm.py). An extra
all_gather per split — e.g. someone tree-maps a gather over a
SplitResult again, the exact 30-gather regression ISSUE 14 collapsed —
changes the census to {reduce-scatter: 1, all-gather: 2} and must trip
GC401 even though every numeric test still passes."""

NAME = "fixture_bad_mesh_collective"
CONTRACT = dict(collective=True)
ENTRY = dict(ops=10_000, ops_slack=0, fusions=10_000, fusions_slack=0,
             collectives={"reduce-scatter": 1, "all-gather": 1},
             donation=0)
EXPECT = ["GC401"]


def build():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()), ("d",))

    def grow_body(hist):
        # the committed shape: reduce-scatter the child histogram,
        # scan the local slice, gather ONE packed winner buffer
        local = jax.lax.psum_scatter(hist, "d", scatter_dimension=0,
                                     tiled=True)
        winner = jax.lax.all_gather(local.max(axis=0), "d")
        # the seeded defect: a second, stray all_gather of the whole
        # local histogram slice sneaks into the split body
        stray = jax.lax.all_gather(local, "d")
        return winner.sum() + stray.sum()

    mapped = jax.shard_map(grow_body, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False)
    n = jax.device_count()
    return jax.jit(mapped).lower(jnp.zeros((n * 2, 8), jnp.float32))
