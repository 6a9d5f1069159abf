"""Surprise collective: a ``psum`` appears in a program whose
contract declares none — one extra all-reduce PER SPLIT is exactly
the communication cost the voting-parallel algorithm (arxiv
1611.01276) exists to avoid, and it regresses no numeric test."""

NAME = "fixture_bad_collective"
CONTRACT = dict(collective=False)
ENTRY = dict(ops=10_000, ops_slack=0, fusions=10_000, fusions_slack=0,
             collectives={}, donation=0)
EXPECT = ["GC401"]


def build():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()), ("d",))

    def summed(x):
        return jax.lax.psum(x, "d")

    mapped = jax.shard_map(summed, mesh=mesh, in_specs=(P("d"),),
                           out_specs=P(), check_vma=False)
    n = jax.device_count()
    return jax.jit(mapped).lower(jnp.zeros((n, 8), jnp.float32))
