"""Plain reference of the categorical training configuration: leaf-wise
gradient-boosted trees with the binary log-loss on a binned table whose
columns are numeric (ordered bins) or categorical (a bin is a
category), in NumPy and float64. No kernels, no partitioned matrix, no
device, nothing of ``lightgbm_tpu``: per split, the histogram of the
smaller child by ``bincount``, the sibling by subtraction, then every
candidate of every column scored.

Numeric columns are scored as ``gbdt_numpy.py`` scores them: every
threshold, ``min_data_in_leaf`` rows and ``min_sum_hessian_in_leaf`` on
each side. Categorical columns follow the published rule
(``FeatureHistogram::FindBestThresholdCategoricalInner``):

* a column with at most ``max_cat_to_onehot`` bins is split one
  category against the rest;
* otherwise the categories with at least ``cat_smooth`` rows are sorted
  by ``grad / (hess + cat_smooth)`` and taken from the low end and from
  the high end in turn, up to ``min(max_cat_threshold, (used + 1) // 2)``
  of them on the left; a candidate is scored each time the categories
  added since the last candidate hold ``min_data_per_group`` rows, with
  ``cat_l2`` added to ``lambda_l2``; the search from one end stops when
  the right side falls under ``min_data_in_leaf``,
  ``min_data_per_group`` or ``min_sum_hessian_in_leaf``;
* a row goes left iff its bin is in the chosen set; a bin that is no
  category (the table's bin for rare, unseen and missing values, above
  ``category_bins``) never is.

A leaf's output is ``-G / (H + l2)`` of the split that made it, ``l2``
with ``cat_l2`` for a sorted-category split, times the learning rate;
the root's uses ``lambda_l2``. Departures, all the program's own
(``ops/split_categorical.py`` documents the first): the rows in a bin
are counted, where the reference estimates them from the hessians; the
reference's ``kEpsilon`` (1e-15) terms are left out, far below float64
sums of this size; no missing values in numeric columns, no sampling.
Among candidates of exactly equal gain the first wins (first category,
low end before high end, first column); a numeric column keeps
``gbdt_numpy``'s highest bin.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

DEFAULTS = {"min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
            "lambda_l2": 0.0, "min_gain_to_split": 0.0,
            "cat_smooth": 10.0, "cat_l2": 10.0, "max_cat_threshold": 32,
            "max_cat_to_onehot": 4, "min_data_per_group": 100}


def _histogram(binned, rows, grad, hess, offsets, size):
    """``[features * bins, 3]``: gradient, hessian and count sums of
    ``rows`` per (feature, bin)."""
    idx = (binned[rows].astype(np.int64) + offsets[None, :]).ravel()
    f = len(offsets)
    out = np.empty((size, 3), np.float64)
    out[:, 0] = np.bincount(idx, np.repeat(grad[rows], f), size)
    out[:, 1] = np.bincount(idx, np.repeat(hess[rows], f), size)
    out[:, 2] = np.bincount(idx, minlength=size)
    return out


def _gain(g, h, l2):
    return g * g / (h + l2)


def _numeric(h3, num_bins, total, p):
    """Per numeric column ``(gain above the parent's, threshold bin)``;
    ``-inf`` where no threshold is allowed."""
    bins = h3.shape[1]
    left = np.cumsum(h3, axis=1)[:, :-1, :]              # bins <= t
    right = total[None, None, :] - left
    t = np.arange(bins - 1)[None, :]
    ok = (t <= num_bins[:, None] - 2) \
        & (left[..., 2] >= p["min_data_in_leaf"]) \
        & (right[..., 2] >= p["min_data_in_leaf"]) \
        & (left[..., 1] >= p["min_sum_hessian_in_leaf"]) \
        & (right[..., 1] >= p["min_sum_hessian_in_leaf"])
    l2 = p["lambda_l2"]
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = _gain(left[..., 0], left[..., 1], l2) \
            + _gain(right[..., 0], right[..., 1], l2) \
            - _gain(total[0], total[1], l2)
    gain = np.where(ok & (gain > p["min_gain_to_split"]), gain, -np.inf)
    rev = gain[:, ::-1]                 # highest bin among equal gains
    return rev.max(axis=1), bins - 2 - rev.argmax(axis=1)


def _one_hot(g, h, c, total, shift, p):
    """One category against the rest: ``(gain, [bin], l2)``."""
    best, found = -np.inf, None
    l2 = p["lambda_l2"]
    for t in range(len(g)):
        if c[t] < p["min_data_in_leaf"] \
                or h[t] < p["min_sum_hessian_in_leaf"] \
                or total[2] - c[t] < p["min_data_in_leaf"] \
                or total[1] - h[t] < p["min_sum_hessian_in_leaf"]:
            continue
        gain = _gain(g[t], h[t], l2) \
            + _gain(total[0] - g[t], total[1] - h[t], l2)
        if gain > shift and gain > best:
            best, found = gain, [t]
    return best, found, l2


def _sorted_categories(g, h, c, total, shift, p):
    """Many against many: ``(gain, bins on the left, l2)``."""
    used = [t for t in range(len(g)) if c[t] >= p["cat_smooth"]]
    used.sort(key=lambda t: g[t] / (h[t] + p["cat_smooth"]))   # stable
    l2 = p["lambda_l2"] + p["cat_l2"]
    most = min(int(p["max_cat_threshold"]), (len(used) + 1) // 2)
    best, found = -np.inf, None
    for order in (used, used[::-1]):
        lg = lh = lc = group = 0.0
        for i, t in enumerate(order[:most]):
            lg, lh, lc = lg + g[t], lh + h[t], lc + c[t]
            group += c[t]
            if lc < p["min_data_in_leaf"] \
                    or lh < p["min_sum_hessian_in_leaf"]:
                continue
            rc, rh = total[2] - lc, total[1] - lh
            if rc < p["min_data_in_leaf"] \
                    or rc < p["min_data_per_group"] \
                    or rh < p["min_sum_hessian_in_leaf"]:
                break
            if group < p["min_data_per_group"]:
                continue
            group = 0.0
            gain = _gain(lg, lh, l2) + _gain(total[0] - lg, rh, l2)
            if gain > shift and gain > best:
                best, found = gain, order[:i + 1]
    return best, found, l2


def _best_split(hist, num_bins, category_bins, categorical, bins, p):
    """The best split of a leaf with histogram ``hist``: a dict with
    ``gain`` (above the parent's; ``-inf`` when none is allowed),
    ``terms`` (the two children's score terms, ``G * G / (H + l2)``
    each, of which ``gain`` is what is left above the parent's: the
    size that the rounding of a gain goes by), ``feature``,
    ``threshold`` or ``left_bins``, and the ``l2`` its children's
    outputs take."""
    h3 = hist.reshape(len(num_bins), bins, 3)
    total = h3[0].sum(axis=0)
    gains, thresholds = _numeric(h3, num_bins, total, p)
    gains = np.where(categorical, -np.inf, gains)
    sets: Dict[int, tuple] = {}
    parent = _gain(total[0], total[1], p["lambda_l2"])
    shift = parent + p["min_gain_to_split"]
    for f in np.flatnonzero(categorical):
        k = int(category_bins[f])
        g, h, c = (h3[f, :k, j].tolist() for j in range(3))
        search = _one_hot if num_bins[f] <= p["max_cat_to_onehot"] \
            else _sorted_categories
        gain, left_bins, l2 = search(g, h, c, total, shift, p)
        if left_bins is not None:
            gains[f] = gain - shift
            sets[f] = (sorted(left_bins), l2)
    f = int(gains.argmax())                 # first column among equals
    out = {"gain": float(gains[f]), "feature": f, "l2": p["lambda_l2"],
           "terms": float(gains[f] + (shift if f in sets else parent))}
    if f in sets:
        out["left_bins"], out["l2"] = sets[f]
    else:
        out["threshold"] = int(thresholds[f])
    return out


def train(binned: np.ndarray, num_bins, labels, params: Dict, trees: int,
          categorical=None, category_bins=None,
          forest: Optional[List] = None, quantize=None,
          init_score=None) -> np.ndarray:
    """Raw training scores ``[rows]`` after ``trees`` boosting rounds on
    ``binned [rows, features]`` (bin indices). ``categorical [features]``
    says which columns' bins are categories, ``category_bins
    [features]`` how many of a column's bins are (``num_bins`` where not
    given: the table's bin for rare and missing values, if it has one,
    is the last and is none). ``forest``, if a list, receives per tree
    ``{"splits": [{leaf, rows, feature, gain, terms, threshold |
    left_bins}, ...], "leaf_values": [...]}`` in the order the leaves
    were made. ``init_score [rows]``, if given, is where boosting
    starts, in place of the labels' log-odds.
    ``quantize``, if given, is applied to each round's gradients and
    hessians: the reading in a lower precision that the configuration's
    tolerances are set against (``PERF.md``)."""
    p = dict(DEFAULTS)
    p.update({k: params[k] for k in p if k in params})
    num_leaves = int(params["num_leaves"])
    rate = float(params["learning_rate"])
    binned = np.asarray(binned)
    num_bins = np.asarray(num_bins, np.int64)
    n, f = binned.shape
    categorical = np.zeros(f, bool) if categorical is None \
        else np.asarray(categorical, bool)
    category_bins = num_bins if category_bins is None \
        else np.asarray(category_bins, np.int64)
    bins = int(num_bins.max())
    offsets = np.arange(f, dtype=np.int64) * bins
    size = f * bins
    y = np.asarray(labels, np.float64)
    mean = y.mean()
    score = np.full(n, np.log(mean / (1.0 - mean))) if init_score is None \
        else np.array(init_score, np.float64)

    def best_of(hist):
        return _best_split(hist, num_bins, category_bins, categorical,
                           bins, p)

    for _ in range(trees):
        prob = 1.0 / (1.0 + np.exp(-score))
        grad, hess = prob - y, prob * (1.0 - prob)
        if quantize is not None:
            grad, hess = quantize(grad), quantize(hess)
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
            col = binned[r, split["feature"]]
            if "left_bins" in split:
                goes_left = np.isin(col, split["left_bins"])
            else:
                goes_left = col <= split["threshold"]
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
