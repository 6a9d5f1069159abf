"""Judged query-document rows in ragged query groups, from a seed.

Stands for Microsoft's MSLR-WEB30K as the reference's experiments use
it (``{S1,S2,S3}`` as the train set: 2,270,296 rows x 137 columns),
whose file is not here. A row is one document retrieved for one query;
a query's documents are contiguous rows; the label is a relevance
grade 0-4.

What belongs to the TABLE comes from ``table_seed`` (the
configuration's, not the run's), so every run seed gives rows of one
table and the work in a training step differs from seed to seed only
as the sample does:

* the **query sizes** (``query_sizes``): a long-tailed draw (log-normal,
  ``sigma`` 0.7) with mean ``mean_query`` documents, least 1, the
  longest exactly ``longest``, summing to ``rows``. At the published
  row count that is 18,919 queries of 120 documents in the mean and
  1,251 in the longest (MSLR-WEB30K's documented figures as recalled);
* the **column kinds**: about two fifths heavy-tailed integer counts
  (``floor(exp(a z + b))``: term and link counts, many with fewer than
  255 distinct values), three tenths ratios in (0, 1), the rest
  unbounded scores; dense, no missing values;
* the **label's weights and thresholds**: a latent relevance that is a
  noisy function of a dozen columns (nine linear, three in products)
  plus a per-query offset (query difficulty), cut at thresholds that
  give the grades the shares ``GRADE_SHARES``.

The values come from ``seed``: the same seed gives the same rows.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

# the rows are drawn in this many independent streams, each from its
# own child of the seed, so the result does not depend on how many
# threads draw them
STREAMS = 16
GRADE_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)
SIGNAL_COLUMNS = 12
# standard deviations of the latent's three parts: the columns'
# function, the query's offset, the noise
SIGNAL, QUERY_OFFSET, NOISE = 1.0, 0.6, 0.8


def query_sizes(rows: int, table_seed: int = 30000,
                mean_query: float = 120.0, longest: int = 1251):
    """``[queries] int64``: documents a query, summing to ``rows``; a
    function of the table alone."""
    rng = np.random.default_rng([table_seed, 1])
    queries = max(1, int(round(rows / mean_query)))
    longest = min(longest, rows - (queries - 1))
    if queries == 1:
        return np.asarray([rows], np.int64)
    draw = np.exp(0.7 * rng.standard_normal(queries))
    draw[0] = 0.0                         # the longest query's place
    rest = rows - longest
    sizes = np.clip(np.floor(draw * rest / draw.sum()), 1,
                    max(longest - 1, 1)).astype(np.int64)
    sizes[0] = 0
    # whole documents: hand the remainder out one at a time
    while True:
        diff = int(rest - sizes.sum())
        if diff == 0:
            break
        room = np.flatnonzero((sizes < longest - 1) if diff > 0
                              else (sizes > 1))
        room = room[room > 0]
        pick = rng.choice(room, min(abs(diff), len(room)), replace=False)
        sizes[pick] += 1 if diff > 0 else -1
    sizes[0] = longest
    # the longest query somewhere in the table, not at its head
    return np.roll(sizes, int(rng.integers(queries)))


def _table(features: int, table_seed: int):
    """Column kinds, their parameters and the label's weights."""
    rng = np.random.default_rng([table_seed, 2])
    kind = rng.choice(3, features, p=[0.4, 0.3, 0.3])   # count/ratio/score
    scale = rng.uniform(0.5, 1.5, features).astype(np.float32)
    shift = rng.uniform(0.0, 3.0, features).astype(np.float32)
    cols = rng.choice(features, SIGNAL_COLUMNS, replace=False)
    w = rng.choice([-1.0, 1.0], SIGNAL_COLUMNS) \
        * rng.uniform(0.5, 1.0, SIGNAL_COLUMNS)
    # nine linear terms and three products, scaled to SIGNAL
    w = (w * SIGNAL / np.sqrt((w[:9] ** 2).sum() + (w[9:] ** 2).sum()
                              )).astype(np.float32)
    # the thresholds of the grades, from the latent's own distribution
    z = rng.standard_normal((200_000, SIGNAL_COLUMNS)).astype(np.float32)
    latent = _signal(z, w) \
        + QUERY_OFFSET * rng.standard_normal(200_000) \
        + NOISE * rng.standard_normal(200_000)
    cuts = np.quantile(latent, np.cumsum(GRADE_SHARES)[:-1])
    return kind, scale, shift, cols, w, cuts.astype(np.float32)


def _signal(z, w):
    """The columns' part of the latent from their standard-normal
    draws ``z [rows, SIGNAL_COLUMNS]``."""
    return z[:, :9] @ w[:9] + w[9] * z[:, 9] * z[:, 10] \
        + w[10] * z[:, 10] * z[:, 11] + w[11] * z[:, 9] * z[:, 11]


def make(seed: int, rows: int, features: int, table_seed: int = 30000,
         mean_query: float = 120.0, longest: int = 1251):
    """``(x [rows, features] f32, grade [rows] f32, sizes [queries]
    int64)``."""
    if features < SIGNAL_COLUMNS:
        raise ValueError(f"the label reads {SIGNAL_COLUMNS} columns")
    sizes = query_sizes(rows, table_seed, mean_query, longest)
    kind, scale, shift, cols, w, cuts = _table(features, table_seed)
    count, ratio = kind == 0, kind == 1
    x = np.empty((rows, features), np.float32)
    latent = np.empty(rows, np.float32)
    bounds = np.linspace(0, rows, STREAMS + 1).astype(np.int64)
    children = np.random.SeedSequence(seed).spawn(STREAMS + 1)

    def draw(i: int) -> None:
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        rng = np.random.default_rng(children[i])
        part = x[lo:hi]
        rng.standard_normal(out=part, dtype=np.float32)
        latent[lo:hi] = _signal(part[:, cols], w) \
            + NOISE * rng.standard_normal(hi - lo, dtype=np.float32)
        # the columns as the table holds them: monotone functions of
        # the draw, a kind a column
        part *= scale
        part[:, count] = np.floor(np.exp(part[:, count] + shift[count]))
        r = part[:, ratio]
        part[:, ratio] = 0.5 + 0.5 * r / np.sqrt(1.0 + r * r)
        part[:, kind == 2] += shift[kind == 2]

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(draw, range(STREAMS)))
    offset = np.random.default_rng(children[STREAMS]).standard_normal(
        len(sizes)).astype(np.float32)
    latent += QUERY_OFFSET * np.repeat(offset, sizes)
    grade = np.searchsorted(cuts, latent).astype(np.float32)
    return x, grade, sizes
