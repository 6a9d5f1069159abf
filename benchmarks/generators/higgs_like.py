"""Dense numeric rows with a binary label, from a seed.

Stands for the reference's dense numeric tables (Higgs, Criteo), whose
files are not here: every feature is standard normal and the label is
the sign of a noisy interaction logit over the first seven features
(the function of ``chip_smoke.higgs_like``), so a boosted model has
main effects and interactions to find and the other features are
noise. The same seed gives the same rows.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# the rows are drawn in this many independent streams, each from its
# own child of the seed, so the result does not depend on how many
# threads draw them
STREAMS = 16


def make(seed: int, rows: int, features: int):
    """``(x [rows, features] f32, y [rows] f32)``."""
    if features < 7:
        raise ValueError("the label function reads seven features")
    x = np.empty((rows, features), np.float32)
    noise = np.empty(rows, np.float32)
    bounds = np.linspace(0, rows, STREAMS + 1).astype(np.int64)
    children = np.random.SeedSequence(seed).spawn(STREAMS)

    def draw(i: int) -> None:
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        rng = np.random.default_rng(children[i])
        rng.standard_normal(out=x[lo:hi], dtype=np.float32)
        rng.standard_normal(out=noise[lo:hi], dtype=np.float32)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(draw, range(STREAMS)))
    logit = (2.0 * x[:, 0] - 1.5 * x[:, 1] + x[:, 2] * x[:, 3]
             + 0.8 * x[:, 4] * x[:, 5] - x[:, 6])
    y = (logit + noise > 0).astype(np.float32)
    return x, y
