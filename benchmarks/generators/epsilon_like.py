"""Dense unit-length rows with a binary label, from a seed.

Stands for the PASCAL Large Scale Learning Challenge's Epsilon set
(400,000 training rows x 2,000 dense features), whose file is not
here. The challenge standardised every feature and then scaled every
row to unit length; here every feature is standard normal and every
row is scaled to unit length, so a value is about 1 / sqrt(features)
in size and no column stands out by its scale.

The label is the sign of a noisy *dense* linear logit: every column
carries weight, the magnitudes decay like ``1 / sqrt(rank)`` and the
ranks and signs are drawn once from ``table_seed`` (the
configuration's, not the run's), so every run seed gives rows of one
table: the trees, and with them the work in a training step, differ
from seed to seed only as the sample does. A boosted model has a few
hundred weak columns to find, as on the real set, where a linear model
is already strong; no few columns carry the signal. The rows are drawn
from the seed: the same seed gives the same rows.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# the rows are drawn in this many independent streams, each from its
# own child of the seed, so the result does not depend on how many
# threads draw them
STREAMS = 16


def make(seed: int, rows: int, features: int, table_seed: int = 2008,
         signal: float = 3.0):
    """``(x [rows, features] f32, y [rows] f32)``. ``signal`` is the
    standard deviation of the logit before the unit-normal noise."""
    table_rng = np.random.default_rng(table_seed)
    w = table_rng.permutation(features).astype(np.float64) + 1.0
    w = table_rng.choice([-1.0, 1.0], features) / np.sqrt(w)
    # x.w has variance |w|^2 / features on unit-length rows
    w = (w * signal * np.sqrt(features)
         / np.linalg.norm(w)).astype(np.float32)
    x = np.empty((rows, features), np.float32)
    logit = np.empty(rows, np.float32)
    bounds = np.linspace(0, rows, STREAMS + 1).astype(np.int64)
    children = np.random.SeedSequence(seed).spawn(STREAMS)

    def draw(i: int) -> None:
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        rng = np.random.default_rng(children[i])
        part = x[lo:hi]
        rng.standard_normal(out=part, dtype=np.float32)
        part /= np.linalg.norm(part, axis=1, keepdims=True)
        logit[lo:hi] = part @ w \
            + rng.standard_normal(hi - lo, dtype=np.float32)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(draw, range(STREAMS)))
    return x, (logit > 0).astype(np.float32)
