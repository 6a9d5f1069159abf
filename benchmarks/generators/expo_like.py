"""Rows shaped like the airline on-time table (Expo), from a seed.

Stands for the reference's categorical table, whose file is not here:
the leading columns follow the airline schema (``columns`` in the
configuration file gives each one's name, cardinality and skew), the
rest are standard normal. A categorical column holds category ids
``0 .. cardinality - 1``; which id is how frequent and what it does to
the label are part of the table's definition, drawn once from
``table_seed`` (the configuration's, not the run's), so the order of
the ids says nothing, a learner that reads the column as ordered gains
little, and every run seed gives rows of the same table: the trees, and
with them the work in a training step, differ from seed to seed only
as the sample does (with the tables drawn from the run's seed the rate
of one cell spread by 14 % over seven seeds; chip runs, PR 27).
``P(rank k) ~ 1 / k ** exponent``: 0 for the calendar's columns, which
are uniform.

The label is the sign of a noisy logit: one effect per category of the
columns named in ``EFFECTS`` (normal, drawn from ``table_seed``) and an
interaction of the first numeric columns. A boosted model therefore has
to send many categories one way and many the other to do well. The rows
are drawn from the seed: the same seed gives the same rows.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# the rows are drawn in this many independent streams, each from its
# own child of the seed, so the result does not depend on how many
# threads draw them
STREAMS = 16

# column name -> standard deviation of its per-category effect
EFFECTS = {"UniqueCarrier": 0.7, "Origin": 0.9, "Dest": 0.6,
           "Month": 0.4, "DayOfWeek": 0.3, "FlightNum": 0.4}


def _cdf(col) -> np.ndarray:
    p = 1.0 / np.arange(1, int(col["cardinality"]) + 1) \
        ** float(col.get("exponent", 0.0))
    return np.cumsum(p / p.sum())


def make(seed: int, rows: int, features: int, columns,
         table_seed: int = 2009):
    """``(x [rows, features] f32, y [rows] f32)``; ``columns`` lists the
    leading schema columns in order, ``table_seed`` fixes which category
    is how frequent and what it does to the label."""
    lead = len(columns)
    if features < lead + 4:
        raise ValueError("the label function reads four numeric columns "
                         "behind the schema's")
    children = np.random.SeedSequence(seed).spawn(STREAMS)
    table_rng = np.random.default_rng(table_seed)
    # per column: rank -> category id, and category id -> label effect
    id_of_rank, effect = [], []
    for col in columns:
        k = int(col["cardinality"])
        id_of_rank.append(table_rng.permutation(k).astype(np.float32))
        effect.append(table_rng.standard_normal(k)
                      * EFFECTS.get(col["name"], 0.0))
    cdfs = [_cdf(col) for col in columns]

    x = np.empty((rows, features), np.float32)
    logit = np.empty(rows, np.float32)
    bounds = np.linspace(0, rows, STREAMS + 1).astype(np.int64)

    def draw(i: int) -> None:
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        rng = np.random.default_rng(children[i])
        part = x[lo:hi]
        rng.standard_normal(out=part, dtype=np.float32)
        z = rng.standard_normal(hi - lo, dtype=np.float32)     # noise
        num = part[:, lead:]
        z += 0.8 * num[:, 0] + num[:, 1] * num[:, 2] - 0.5 * num[:, 3]
        for j, col in enumerate(columns):
            rank = np.searchsorted(cdfs[j], rng.random(hi - lo))
            rank = np.minimum(rank, len(cdfs[j]) - 1)
            ids = id_of_rank[j][rank]
            part[:, j] = ids
            z += effect[j][ids.astype(np.int64)].astype(np.float32)
        logit[lo:hi] = z

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(draw, range(STREAMS)))
    return x, (logit > 0).astype(np.float32)
