"""A one-hot encoded insurance table as a scipy CSR, from a seed.

Stands for the Allstate Claim Prediction Challenge's table as the
reference's benchmark scripts prepare it (13,184,290 rows, 4,228
columns after every categorical column is one-hot encoded), whose file
is not here. The schema is the challenge's, with the cardinalities the
configuration fixes (``cards``):

* 16 numeric columns, every one non-zero in every row, so the table
  stores them densely: calendar year (1-3), model year (1-28), the
  eight vehicle variables and the four non-vehicle variables (standard
  normal), two ordinals (1-6, 1-4);
* 17 categorical columns, each encoded as one indicator column a
  value, exactly one indicator a row a categorical: the vehicle's
  make, model and sub-model, which nest (a sub-model belongs to one
  model, a model to one make), twelve small factors, one ordinal
  factor and one non-vehicle factor.

A row therefore stores 33 of its 4,228 values. A model that has one
sub-model gives two indicator columns that are equal in every row, and
a make that has one model three: the real table has such columns too.

The hierarchy, every level's frequencies (Zipf(1) within a parent,
mixed with a uniform floor so that no column of the published width is
empty) and the label's weights are drawn once from ``table_seed`` (the
configuration's, not the run's): every run seed gives rows of one
table, and the trees, and with them the work in a training step,
differ from seed to seed only as the sample does. The label is rare
(about one row in a hundred, as a paid claim is) and weak: a Bernoulli
draw from a noisy logit over make, model, three small factors and
three numeric columns.

Rows are drawn in blocks of ``BLOCK`` rows, each from its own child of
the seed, so the result does not depend on how many threads draw them,
and ``head`` rows of a table are the first ``head`` rows of the whole
table: the correctness check regenerates its rows without making the
rest. Nothing dense of shape ``rows x features`` is ever made.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

NUMERIC = 16
BLOCK = 1 << 16
# make, model, sub-model, twelve small factors, the ordinal factor, the
# non-vehicle factor: 4,212 indicator columns
CARDS = (75, 1300, 2750, 2, 2, 3, 3, 4, 5, 5, 6, 7, 8, 9, 10, 8, 15)


def _zipf(n: int) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1)
    return p / p.sum()


def _cdf(p: np.ndarray) -> np.ndarray:
    """Cumulative shares ending in exactly 1, for ``_pick``."""
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    return cdf


def _pick(cdf: np.ndarray, u: np.ndarray) -> np.ndarray:
    """The value each uniform draw in [0, 1) falls on."""
    return np.searchsorted(cdf, u, side="right")


def _children(rng, parents: int, children: int) -> np.ndarray:
    """``parent_of [children]``: every parent gets one child, the rest
    go to parents by Zipf(1), so popular parents have many."""
    extra = rng.choice(parents, children - parents, p=_zipf(parents))
    return np.sort(np.concatenate([np.arange(parents), extra]))


def _nested(parent_of: np.ndarray, p_parent: np.ndarray) -> np.ndarray:
    """Probability of each child: its parent's, times Zipf(1) among
    the parent's children (in index order)."""
    first = np.searchsorted(parent_of, parent_of)     # sorted parents
    rank = np.arange(len(parent_of)) - first + 1.0
    norm = np.bincount(parent_of, 1.0 / rank)
    return p_parent[parent_of] / rank / norm[parent_of]


class _Table:
    """What ``table_seed`` fixes: the hierarchy, the frequencies, the
    label's weights."""

    def __init__(self, table_seed: int, cards: Sequence[int],
                 floor: float, signal: float, positive: float):
        rng = np.random.default_rng(table_seed)
        n_make, n_model, n_sub = cards[:3]
        self.cards = tuple(int(c) for c in cards)
        self.make_of_model = _children(rng, n_make, n_model)
        self.model_of_sub = _children(rng, n_model, n_sub)
        p_model = _nested(self.make_of_model, _zipf(n_make))
        p_sub = _nested(self.model_of_sub, p_model)
        p_sub = (1.0 - floor) * p_sub + floor / n_sub
        self.cdf_sub = _cdf(p_sub)
        self.cdf_factor = [_cdf(_zipf(c)) for c in self.cards[3:]]
        self.base = NUMERIC + np.concatenate(
            [[0], np.cumsum(self.cards)[:-1]]).astype(np.int32)
        # the logit: make, model, the first three small factors, three
        # numeric columns; ``signal`` scales all of it
        self.w_make = rng.standard_normal(n_make) * signal
        self.w_model = rng.standard_normal(n_model) * signal
        self.w_factor = [rng.standard_normal(c) * 0.5 * signal
                         for c in self.cards[3:6]]
        self.w_numeric = np.array([0.6, -0.4, 0.3]) * signal
        self.bias = float(np.log(positive / (1.0 - positive)))


def make(seed: int, rows: int, features: int, table_seed: int = 2011,
         cards: Sequence[int] = CARDS, floor: float = 0.1,
         signal: float = 0.35, positive: float = 0.01,
         head: Optional[int] = None):
    """``(x, y)``: ``x`` a scipy CSR ``[rows, features]`` (float32
    data, int32 indices, column indices ascending within a row), ``y
    [rows]`` float32. ``head``, if given, makes only the table's first
    ``head`` rows."""
    import scipy.sparse as sp
    if features != NUMERIC + sum(cards) or len(cards) != len(CARDS):
        raise ValueError(
            f"{features} columns are not {NUMERIC} numeric ones and "
            f"the indicators of {len(CARDS)} categoricals {list(cards)}")
    table = _Table(table_seed, cards, floor, signal, positive)
    made = rows if head is None else min(int(head), rows)
    per_row = NUMERIC + len(cards)
    data = np.ones((made, per_row), np.float32)
    indices = np.empty((made, per_row), np.int32)
    indices[:, :NUMERIC] = np.arange(NUMERIC, dtype=np.int32)
    y = np.empty(made, np.float32)
    blocks = -(-rows // BLOCK)
    children = np.random.SeedSequence(seed).spawn(blocks)

    def draw(i: int) -> None:
        lo = i * BLOCK
        # a block is drawn whole, whatever part of it is kept, so that
        # the head of a table is the head of the whole table
        m, keep = min(BLOCK, rows - lo), min(BLOCK, made - lo)
        rng = np.random.default_rng(children[i])
        num = np.empty((m, NUMERIC), np.float32)
        num[:, 0] = rng.integers(1, 4, m)
        num[:, 1] = rng.integers(1, 29, m)
        num[:, 2:14] = rng.standard_normal((m, 12), dtype=np.float32)
        num[:, 14] = rng.integers(1, 7, m)
        num[:, 15] = rng.integers(1, 5, m)
        sub = _pick(table.cdf_sub, rng.random(m))
        model = table.model_of_sub[sub]
        maker = table.make_of_model[model]
        codes = [maker, model, sub] + [
            _pick(cdf, rng.random(m)) for cdf in table.cdf_factor]
        logit = table.bias + table.w_make[maker] + table.w_model[model] \
            + num[:, 2:5].astype(np.float64) @ table.w_numeric
        for w, code in zip(table.w_factor, codes[3:6]):
            logit += w[code]
        label = rng.random(m) < 1.0 / (1.0 + np.exp(-logit))
        data[lo:lo + keep, :NUMERIC] = num[:keep]
        for k, code in enumerate(codes):
            indices[lo:lo + keep, NUMERIC + k] = table.base[k] \
                + code[:keep]
        y[lo:lo + keep] = label[:keep]

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(draw, range(-(-made // BLOCK))))
    indptr = np.arange(made + 1, dtype=np.int64) * per_row
    if indptr[-1] < np.iinfo(np.int32).max:
        indptr = indptr.astype(np.int32)
    x = sp.csr_matrix((data.reshape(-1), indices.reshape(-1), indptr),
                      shape=(made, features))
    return x, y
