"""Multiboost regressions under vmap: the model axis silently WIDENS
a collective (every per-model psum becomes B cross-device ops in the
one batched program — GC401, the contract declares none) and the
batched score donation is dropped because the vmapped body returns a
widened buffer the [B, N] input cannot back (GC101). Both defects
compile clean and regress no numeric test — exactly the class the
multiboost_grow contract in contracts.json exists to pin."""

NAME = "fixture_bad_multiboost"
CONTRACT = dict(donate=(0,), collective=False)
ENTRY = dict(ops=10_000, ops_slack=0, fusions=10_000, fusions_slack=0,
             collectives={}, donation=1)
EXPECT = ["GC101", "GC401"]


def build():
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()), ("d",))

    def summed(x):
        return jax.lax.psum(x, "d")

    mapped = jax.shard_map(summed, mesh=mesh, in_specs=(P("d"),),
                           out_specs=P(), check_vma=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def grow_batch(score):
        # vmap over the model axis widens the per-model psum into B
        # collectives in ONE compiled program
        leaf = jax.vmap(mapped)(score)
        # widened output: the donated [B, n, 8] score cannot back it,
        # so XLA silently drops the declared alias
        return jnp.concatenate([leaf, leaf])

    n = jax.device_count()
    return grow_batch.lower(jnp.zeros((3, n, 8), jnp.float32))
