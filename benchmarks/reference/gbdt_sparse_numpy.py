"""Plain reference of the sparse one-hot configuration: leaf-wise
gradient-boosted trees with the binary log-loss on a table given as
each row's NON-DEFAULT entries (a column and a bin), in NumPy and
float64. No kernels, no bundles, no device, nothing of
``lightgbm_tpu``: it knows columns, not groups of them.

It is ``gbdt_cat_numpy.py``'s trainer (the same leaf-wise loop, the
same scoring: that file's ``_best_split`` with no column categorical)
with another, equally plain histogram. A dense ``rows x columns``
index of a 4,228-column table is 3.4 GB at 100,000 rows and a leaf's
dense histogram 26 MB, 6.6 GB for 255 leaves; here

* a leaf's histogram is one ``bincount`` over the stored entries of
  its rows, into a ragged layout (column ``c`` owns ``num_bins[c]``
  slots: 12.5 k slots for 16 numeric and 4,212 indicator columns);
* a column's default bin, which no row stores, is what the leaf's
  totals leave over after the column's stored bins, as any sparse
  learner fills it;
* the ragged histogram is laid out dense, ``[columns, bins, 3]``, only
  to be scored, and dropped: the columns of up to ``NARROW`` bins (the
  indicators) in one block of that width, the rest in one of the
  widest column's, each scored by ``_best_split``, the better taken
  (the lower column among equals). One block of 4,228 x 255 bins a
  leaf is 26 MB of which 99 % is padding, and most of this
  reference's time.

A column with one bin (nothing to split on) is never chosen. Among
candidates of exactly equal gain the first column wins, and a column
keeps ``gbdt_numpy``'s highest bin.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from .gbdt_cat_numpy import DEFAULTS, _best_split

NARROW = 8


def train(indptr, columns, bins, default_bin, num_bins, labels,
          params: Dict, trees: int, forest: Optional[List] = None,
          quantize=None, init_score=None) -> np.ndarray:
    """Raw training scores ``[rows]`` after ``trees`` boosting rounds.
    Row ``r`` stores the entries ``indptr[r]:indptr[r + 1]``: entry
    ``e`` says that column ``columns[e]`` is at bin ``bins[e]``, which
    is not ``default_bin[columns[e]]``; a column a row does not store
    is at its default bin. ``forest``, ``quantize`` and ``init_score``
    are ``gbdt_cat_numpy.train``'s."""
    p = dict(DEFAULTS)
    p.update({k: params[k] for k in p if k in params})
    num_leaves = int(params["num_leaves"])
    rate = float(params["learning_rate"])
    indptr = np.asarray(indptr, np.int64)
    columns = np.asarray(columns, np.int64)
    bins = np.asarray(bins, np.int64)
    default_bin = np.asarray(default_bin, np.int64)
    num_bins = np.asarray(num_bins, np.int64)
    n, f = len(indptr) - 1, len(num_bins)
    first = np.concatenate([[0], np.cumsum(num_bins)[:-1]])
    size = int(num_bins.sum())
    column_of_slot = np.repeat(np.arange(f), num_bins)
    bin_of_slot = np.arange(size) - first[column_of_slot]
    row_of_entry = np.repeat(np.arange(n), np.diff(indptr))
    slot_of_entry = first[columns] + bins
    no_category = np.zeros(f, bool)
    y = np.asarray(labels, np.float64)
    mean = y.mean()
    score = np.full(n, np.log(mean / (1.0 - mean))) if init_score is None \
        else np.array(init_score, np.float64)

    def histogram(rows, grad, hess):
        """Ragged ``[size, 3]`` sums of the leaf that holds ``rows``."""
        member = np.zeros(n, bool)
        member[rows] = True
        took = member[row_of_entry]
        slots, of = slot_of_entry[took], row_of_entry[took]
        out = np.empty((size, 3), np.float64)
        out[:, 0] = np.bincount(slots, grad[of], size)
        out[:, 1] = np.bincount(slots, hess[of], size)
        out[:, 2] = np.bincount(slots, minlength=size)
        total = np.array([grad[rows].sum(), hess[rows].sum(), len(rows)])
        stored = np.stack([np.bincount(column_of_slot, out[:, j], f)
                           for j in range(3)], axis=1)
        out[first + default_bin] += total[None, :] - stored
        return out

    blocks = [np.flatnonzero(pick) for pick in
              (num_bins <= NARROW, num_bins > NARROW) if pick.any()]

    def best_of(hist):
        found = []
        for cols in blocks:
            wide = int(num_bins[cols].max())
            dense = np.zeros((len(cols), wide, 3), np.float64)
            mine = np.isin(column_of_slot, cols)
            dense[np.searchsorted(cols, column_of_slot[mine]),
                  bin_of_slot[mine]] = hist[mine]
            best = _best_split(dense.reshape(-1, 3), num_bins[cols],
                               num_bins[cols], no_category[cols], wide, p)
            found.append(dict(best, feature=int(cols[best["feature"]])))
        return max(found, key=lambda s: (s["gain"], -s["feature"]))

    def bins_of(column):
        """The column's bin in every row."""
        out = np.full(n, default_bin[column])
        took = columns == column
        out[row_of_entry[took]] = bins[took]
        return out

    for _ in range(trees):
        prob = 1.0 / (1.0 + np.exp(-score))
        grad, hess = prob - y, prob * (1.0 - prob)
        if quantize is not None:
            grad, hess = quantize(grad), quantize(hess)
        rows = {0: np.arange(n)}
        hists = {0: histogram(rows[0], grad, hess)}
        best = {0: best_of(hists[0])}
        value = {0: -grad.sum() / (hess.sum() + p["lambda_l2"])}
        splits = []
        for new in range(1, num_leaves):
            leaf = max(best, key=lambda k: (best[k]["gain"], -k))
            split = best[leaf]
            if not split["gain"] > 0.0:
                break
            r = rows[leaf]
            goes_left = bins_of(split["feature"])[r] <= split["threshold"]
            r_left, r_right = r[goes_left], r[~goes_left]
            small_is_left = len(r_left) <= len(r_right)
            small = histogram(r_left if small_is_left else r_right,
                              grad, hess)
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
