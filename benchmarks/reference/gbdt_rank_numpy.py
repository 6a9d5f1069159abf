"""Plain reference of the learning-to-rank configuration: leaf-wise
gradient-boosted trees with lambdarank's gradients on a binned numeric
table whose rows come in query groups, in NumPy and float64. Nothing
of ``lightgbm_tpu``: no query layout, no length classes, no sort with
payload.

The gradients (``lambdarank_gradients``) follow the published
``LambdarankNDCG::GetGradientsForOneQuery``
(``rank_objective.hpp:139-230``), one query at a time in a plain loop,
the query's pairs as one ``[n, n]`` matrix:

* the documents sorted by score, descending, ties in row order (a
  stable sort, as ``std::stable_sort``);
* every pair of documents with unequal labels: the higher label is
  ``high``, ``delta_score = score[high] - score[low]``, the pair's
  weight ``(gain[high] - gain[low]) x |discount[rank_high] -
  discount[rank_low]| x inverse_max_dcg`` with ``discount[r] = 1 /
  log2(2 + r)`` and the maximum DCG at ``lambdarank_truncation_level``;
* with ``lambdarank_norm`` and a query whose best and worst scores
  differ, the weight divided by ``0.01 + |delta_score|``;
* ``p = 1 / (1 + exp(sigmoid x delta_score))``; ``lambda = -sigmoid x
  weight x p`` added to ``high`` and taken from ``low``; ``hessian =
  sigmoid^2 x weight x p x (1 - p)`` added to both;
* with ``lambdarank_norm`` and a positive sum of ``-2 lambda`` over
  the pairs, everything times ``log2(1 + sum) / sum``.

Departures from the published code: the sigmoid is evaluated exactly,
where the reference reads a 2^20-entry table (the program does the
same and says so, ``objective/rank.py``); every pair is taken, as
v2.3.2 does (a later version stops at the truncation level).

``ndcg_at`` is ``NDCGMetric`` with ``DCGCalculator``'s rule
(``dcg_calculator.cpp``): documents ranked by score, descending, ties
in row order; a query whose labels are all zero counts as 1.

The tree grower is ``gbdt_cat_numpy.py``'s, with every column numeric:
its ``train`` computes the binary log-loss's gradients itself and takes
no gradient function, so the boosting loop is here again, around its
``_histogram`` and ``_best_split``.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from .gbdt_cat_numpy import DEFAULTS, _best_split, _histogram

RANK_DEFAULTS = {"sigmoid": 1.0, "lambdarank_norm": True,
                 "lambdarank_truncation_level": 20}


def label_gains(params: Dict) -> np.ndarray:
    """``label_gain``, by default ``2^l - 1``."""
    given = params.get("label_gain")
    return np.asarray(given, np.float64) if given \
        else 2.0 ** np.arange(31) - 1.0


def lambdarank_gradients(score, labels, sizes, params: Dict):
    """``(gradient [rows], hessian [rows])`` in float64."""
    p = dict(RANK_DEFAULTS)
    p.update({k: params[k] for k in p if k in params})
    sigmoid, norm = float(p["sigmoid"]), bool(p["lambdarank_norm"])
    level = int(p["lambdarank_truncation_level"])
    gains = label_gains(params)
    score = np.asarray(score, np.float64)
    labels = np.asarray(labels).astype(np.int64)
    grad, hess = np.zeros(len(score)), np.zeros(len(score))
    start = 0
    for n in np.asarray(sizes, np.int64):
        s, lab = score[start:start + n], labels[start:start + n]
        ideal = np.sort(gains[lab])[::-1][:level]
        max_dcg = (ideal / np.log2(2.0 + np.arange(len(ideal)))).sum()
        order = np.argsort(-s, kind="stable")
        rank = np.empty(n, np.int64)
        rank[order] = np.arange(n)
        discount = 1.0 / np.log2(2.0 + rank)
        # the pair (i, j) counts where i holds the higher label
        high = lab[:, None] > lab[None, :]
        delta_score = s[:, None] - s[None, :]
        weight = (gains[lab][:, None] - gains[lab][None, :]) \
            * np.abs(discount[:, None] - discount[None, :]) \
            * (1.0 / max_dcg if max_dcg > 0 else 0.0)
        if norm and s.max() != s.min():
            weight = weight / (0.01 + np.abs(delta_score))
        prob = 1.0 / (1.0 + np.exp(sigmoid * delta_score))
        lam = np.where(high, -sigmoid * weight * prob, 0.0)
        hes = np.where(high, sigmoid * sigmoid * weight * prob
                       * (1.0 - prob), 0.0)
        g = lam.sum(axis=1) - lam.sum(axis=0)
        h = hes.sum(axis=1) + hes.sum(axis=0)
        total = -2.0 * lam.sum()
        if norm and total > 0:
            g, h = (a * np.log2(1.0 + total) / total for a in (g, h))
        grad[start:start + n], hess[start:start + n] = g, h
        start += n
    return grad, hess


def ndcg_at(score, labels, sizes, k: int, params: Optional[Dict] = None):
    """Mean NDCG@k over the queries."""
    gains = label_gains(params or {})
    score = np.asarray(score, np.float64)
    labels = np.asarray(labels).astype(np.int64)
    out, start = [], 0
    for n in np.asarray(sizes, np.int64):
        s, gain = score[start:start + n], gains[labels[start:start + n]]
        top = min(k, n)
        discount = 1.0 / np.log2(2.0 + np.arange(top))
        ideal = (np.sort(gain)[::-1][:top] * discount).sum()
        got = (gain[np.argsort(-s, kind="stable")[:top]] * discount).sum()
        out.append(got / ideal if ideal > 0 else 1.0)
        start += n
    return float(np.mean(out))


def train(binned: np.ndarray, num_bins, labels, sizes, params: Dict,
          trees: int, forest: Optional[List] = None,
          init_score=None, first_gradients: Optional[List] = None,
          gradients: Callable = lambdarank_gradients) -> np.ndarray:
    """Raw training scores ``[rows]`` after ``trees`` boosting rounds of
    lambdarank on ``binned [rows, features]`` (bin indices of numeric
    columns), rows in query groups of ``sizes``. Boosting starts at
    ``init_score`` (zero if not given: lambdarank has no average to
    boost from). ``forest`` is ``gbdt_cat_numpy.train``'s.
    ``first_gradients``, if a list, receives the first round's
    ``(gradient, hessian)``. ``gradients`` is the gradient function: a
    test hands over one in a lower precision or with a step left out,
    the readings the configuration's tolerances are set against."""
    p = dict(DEFAULTS)
    p.update({k: params[k] for k in p if k in params})
    num_leaves = int(params["num_leaves"])
    rate = float(params["learning_rate"])
    binned = np.asarray(binned)
    num_bins = np.asarray(num_bins, np.int64)
    n, f = binned.shape
    numeric = np.zeros(f, bool)
    bins = int(num_bins.max())
    offsets = np.arange(f, dtype=np.int64) * bins
    size = f * bins
    score = np.zeros(n) if init_score is None \
        else np.array(init_score, np.float64)

    def best_of(hist):
        return _best_split(hist, num_bins, num_bins, numeric, bins, p)

    for _ in range(trees):
        grad, hess = gradients(score, labels, sizes, params)
        if first_gradients is not None and not first_gradients:
            first_gradients.append((grad, hess))
        rows = {0: np.arange(n)}
        hists = {0: _histogram(binned, rows[0], grad, hess, offsets, size)}
        best = {0: best_of(hists[0])}
        value = {0: -grad.sum() / (hess.sum() + p["lambda_l2"])}
        splits = []
        for new in range(1, num_leaves):
            leaf = max(best, key=lambda k: (best[k]["gain"], -k))
            split = best[leaf]
            if not split["gain"] > 0.0:
                break
            r = rows[leaf]
            goes_left = binned[r, split["feature"]] <= split["threshold"]
            r_left, r_right = r[goes_left], r[~goes_left]
            small_is_left = len(r_left) <= len(r_right)
            small = _histogram(binned, r_left if small_is_left
                               else r_right, grad, hess, offsets, size)
            large = hists[leaf] - small
            rows[leaf], rows[new] = r_left, r_right
            hists[leaf], hists[new] = (small, large) if small_is_left \
                else (large, small)
            for k in (leaf, new):
                best[k] = best_of(hists[k])
                value[k] = -grad[rows[k]].sum() \
                    / (hess[rows[k]].sum() + split["l2"])
            splits.append(dict(split, leaf=leaf, rows=len(r)))
        for k, r in rows.items():
            score[r] += rate * value[k]
        if forest is not None:
            forest.append({"splits": splits,
                           "leaf_values": [rate * value[k]
                                           for k in sorted(value)]})
    return score
