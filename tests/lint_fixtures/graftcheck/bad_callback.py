"""Host callback inside a hot program: every dispatch round-trips
through the python interpreter (a host sync per dispatch). The
compiled module carries a ``custom-call`` to the cpu-callback target
— GC301."""

NAME = "fixture_bad_callback"
CONTRACT = dict(hot=True)
ENTRY = dict(ops=10_000, ops_slack=0, fusions=10_000, fusions_slack=0,
             collectives={}, donation=0)
EXPECT = ["GC301"]


def build():
    import jax
    import jax.numpy as jnp
    import numpy as np

    def logged(x):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct((64,), jnp.float32), x)

    return jax.jit(logged).lower(jnp.zeros((64,), jnp.float32))
