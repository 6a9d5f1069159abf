"""Plain reference of the training configurations: leaf-wise
gradient-boosted trees on binned dense numeric features with the binary
log-loss, in NumPy and float64. No kernels, no partitioned matrix, no
device: per split, the histogram of the smaller child by ``bincount``,
the sibling by subtraction, every threshold of every feature scored.

It follows the reference's semantics for this case (no missing values,
no categorical features, no sampling): the score starts at the log-odds
of the label mean; a split needs ``min_data_in_leaf`` rows and
``min_sum_hessian_in_leaf`` on each side and a gain above its parent's;
the leaf with the largest gain is split next; a leaf's output is
``-G / (H + lambda_l2)`` times the learning rate. Departure: among
thresholds of exactly equal gain the first feature and the highest bin
win, which can differ from the program's choice when float32 sums
round differently; the comparison allows for it with a tolerance on
the metrics, not on the trees.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


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


def _best_split(hist, num_bins, bins, p):
    """``(gain, feature, threshold_bin)`` of the best split of a leaf
    with histogram ``hist``; gain ``-inf`` when none is allowed."""
    h3 = hist.reshape(len(num_bins), bins, 3)
    total = h3[0].sum(axis=0)
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
        gain = left[..., 0] ** 2 / (left[..., 1] + l2) \
            + right[..., 0] ** 2 / (right[..., 1] + l2) \
            - total[0] ** 2 / (total[1] + l2)
    gain = np.where(ok & (gain > p["min_gain_to_split"]), gain, -np.inf)
    # highest bin among equal gains within a feature, first feature
    # among equal gains across features
    rev = gain[:, ::-1]
    t_best = bins - 2 - rev.argmax(axis=1)
    g_best = rev.max(axis=1)
    f = int(g_best.argmax())
    return float(g_best[f]), f, int(t_best[f])


def train(binned: np.ndarray, num_bins, labels, params: Dict,
          trees: int) -> np.ndarray:
    """Raw training scores ``[rows]`` after ``trees`` boosting rounds
    on ``binned [rows, features]`` (bin indices)."""
    p = {"min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
         "lambda_l2": 0.0, "min_gain_to_split": 0.0}
    p.update({k: params[k] for k in p if k in params})
    num_leaves = int(params["num_leaves"])
    rate = float(params["learning_rate"])
    binned = np.asarray(binned)
    num_bins = np.asarray(num_bins, np.int64)
    n, f = binned.shape
    bins = int(num_bins.max())
    offsets = np.arange(f, dtype=np.int64) * bins
    size = f * bins
    y = np.asarray(labels, np.float64)
    mean = y.mean()
    score = np.full(n, np.log(mean / (1.0 - mean)))
    for _ in range(trees):
        prob = 1.0 / (1.0 + np.exp(-score))
        grad, hess = prob - y, prob * (1.0 - prob)
        rows = {0: np.arange(n)}
        hists = {0: _histogram(binned, rows[0], grad, hess, offsets, size)}
        best = {0: _best_split(hists[0], num_bins, bins, p)}
        for new in range(1, num_leaves):
            leaf = max(best, key=lambda k: (best[k][0], -k))
            gain, feat, thr = best[leaf]
            if not np.isfinite(gain):
                break
            r = rows[leaf]
            goes_left = binned[r, feat] <= thr
            r_left, r_right = r[goes_left], r[~goes_left]
            small_is_left = len(r_left) <= len(r_right)
            small = _histogram(binned, r_left if small_is_left
                               else r_right, grad, hess, offsets, size)
            large = hists[leaf] - small
            rows[leaf], rows[new] = r_left, r_right
            hists[leaf], hists[new] = (small, large) if small_is_left \
                else (large, small)
            for k in (leaf, new):
                best[k] = _best_split(hists[k], num_bins, bins, p)
        for r in rows.values():
            score[r] += rate * -grad[r].sum() / (hess[r].sum()
                                                 + p["lambda_l2"])
    return score
