"""The one answer to "is this process computing on a TPU?".

Every choice the library makes from the platform — compiled Pallas
kernels vs their interpret twins, the partitioned learner vs the XLA
learners, the fused-scan iteration driver, where the persistent compile
cache lives — reads this predicate, so a run is on one side of all of
them at once.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (``JAX_PLATFORMS=cpu``
    test runs and CPU hosts are False)."""
    return jax.default_backend() == "tpu"
