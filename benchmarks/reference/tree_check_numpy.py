"""Plain reference for one grown tree, at the size it was grown: the
binary log-loss's gradients of the scores the tree was grown from,
in NumPy and float64, and every split of the tree checked against
them. No kernels, no device, no collectives: the rows are one table.

Two checks, both on the tree as the program grew it:

* **every split**, from the rows the tree sends each way: each row is
  routed down the tree by its bins (a numeric split sends a row left
  where its bin is at most the threshold), and for each split the
  reference sums the rows of each child, count, gradient and hessian,
  and computes the split's gain from them;
* **the search**, over the global rows: for the root and every split
  node of the first ``levels`` levels below it, the reference builds
  the node's histogram of the rows the tree sends there (the smaller
  child's directly, in row blocks, its sibling by subtraction, as the
  program does, but in float64) and finds the best split of every
  threshold of every feature.

It follows the reference's semantics for this case (numeric columns,
no missing values, no sampling, no L1 term, no output limit): a split
needs ``min_data_in_leaf`` rows and ``min_sum_hessian_in_leaf`` on
each side; its gain is ``GL^2 / (HL + l2) + GR^2 / (HR + l2) - G^2 /
(H + l2)``; a node's output is ``-G / (H + l2)``.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

BLOCK_ROWS = 1 << 21        # rows a histogram block reads at a time
DEFAULTS = {"min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
            "lambda_l2": 0.0, "min_gain_to_split": 0.0}


class GrownTree(NamedTuple):
    """What the check reads of the program's tree, as plain arrays.
    Node ``i < leaves - 1`` is a split; a child ``c >= 0`` is a split,
    ``~c`` a leaf. ``*_count``, ``*_weight`` (the hessian sum) and
    ``*_value`` (the output, times ``shrinkage``) are the program's
    record of each split node and leaf."""
    feature: np.ndarray         # [S] column of the table
    threshold: np.ndarray       # [S] bin: left iff bin <= threshold
    left: np.ndarray            # [S]
    right: np.ndarray           # [S]
    gain: np.ndarray            # [S]
    internal_count: np.ndarray  # [S]
    internal_weight: np.ndarray
    internal_value: np.ndarray
    leaf_count: np.ndarray      # [S + 1]
    leaf_weight: np.ndarray
    leaf_value: np.ndarray
    shrinkage: float


def gradients(scores: np.ndarray, labels: np.ndarray):
    """The binary log-loss's gradient and hessian of each row, float64,
    from raw scores and 0/1 labels."""
    p = 1.0 / (1.0 + np.exp(-np.asarray(scores, np.float64)))
    y = np.asarray(labels, np.float64)
    return p - y, p * (1.0 - p)


def _params(params: Dict[str, Any]) -> Dict[str, float]:
    p = dict(DEFAULTS)
    p.update({k: float(params[k]) for k in p if k in params})
    return p


def _child(tree: GrownTree, c: int):
    """``(count, hessian sum, output)`` the program recorded of a
    child."""
    if c >= 0:
        return (int(tree.internal_count[c]),
                float(tree.internal_weight[c]),
                float(tree.internal_value[c]) / tree.shrinkage)
    return (int(tree.leaf_count[~c]), float(tree.leaf_weight[~c]),
            float(tree.leaf_value[~c]) / tree.shrinkage)


def _gain(gl, hl, gr, hr, l2):
    """``(gain, terms)``: the split's gain and the sum of the children's
    two score terms, of which the gain is the small remainder."""
    terms = gl * gl / (hl + l2) + gr * gr / (hr + l2)
    g, h = gl + gr, hl + hr
    return terms - g * g / (h + l2), terms


def _histogram(binned, rows, grad, hess, bins: int) -> np.ndarray:
    """``[features, bins, 3]``: gradient, hessian and count sums of
    ``rows`` per (feature, bin), a block of rows at a time."""
    f = binned.shape[1]
    out = np.zeros((f, bins, 3), np.float64)
    for lo in range(0, len(rows), BLOCK_ROWS):
        r = rows[lo:lo + BLOCK_ROWS]
        block = np.ascontiguousarray(binned[r].T)      # [f, m]
        g, h = grad[r], hess[r]
        for j in range(f):
            col = block[j].astype(np.intp)
            out[j, :, 0] += np.bincount(col, g, bins)
            out[j, :, 1] += np.bincount(col, h, bins)
            out[j, :, 2] += np.bincount(col, minlength=bins)
    return out


def _gain_table(hist, num_bins, p):
    """``(gain [F, B-1], terms [F, B-1])`` of every threshold of every
    feature of a node; ``-inf`` where a split is not allowed."""
    f, bins, _ = hist.shape
    total = hist[0].sum(axis=0)
    left = np.cumsum(hist, axis=1)[:, :-1, :]          # bins <= t
    right = total[None, None, :] - left
    t = np.arange(bins - 1)[None, :]
    ok = (t <= num_bins[:, None] - 2) \
        & (left[..., 2] >= p["min_data_in_leaf"]) \
        & (right[..., 2] >= p["min_data_in_leaf"]) \
        & (left[..., 1] >= p["min_sum_hessian_in_leaf"]) \
        & (right[..., 1] >= p["min_sum_hessian_in_leaf"])
    with np.errstate(divide="ignore", invalid="ignore"):
        gain, terms = _gain(left[..., 0], left[..., 1], right[..., 0],
                            right[..., 1], p["lambda_l2"])
    gain = np.where(ok & (gain > p["min_gain_to_split"]), gain, -np.inf)
    return gain, terms


def check_tree(binned: np.ndarray, num_bins, grad: np.ndarray,
               hess: np.ndarray, tree: GrownTree, params: Dict[str, Any],
               *, levels: int, gain_rtol: float, gain_median_rtol: float,
               quantize=None) -> Dict[str, Any]:
    """Both checks of ``tree``, grown on ``binned [rows, features]``
    (bin indices) from the per-row ``grad`` and ``hess`` (float64).

    Pass conditions: every child's count equal; every split's gain
    within ``gain_rtol`` of its ``terms`` and the median of those
    readings within ``gain_median_rtol`` (the maximum is set by one
    leaf at the end of a chain of histogram subtractions, the median by
    the precision of the sums); and at each searched node the program's
    feature and threshold are the reference's best, or score within
    ``gain_rtol`` of the best's terms on the reference's histogram (a
    tie). The children's sums are readings, not conditions: each
    child's gradient sum against the sum of the absolute gradients of
    its rows (the scale a float32 sum's rounding goes by; a child's sum
    can cancel to nothing), its hessian sum against itself. A small
    child taken from its parent by subtraction carries the parent's
    rounding, so its hessian reads high whatever the precision.
    ``quantize``, if given, is applied to ``grad`` and ``hess`` before
    anything is summed: the reading in a lower precision that the
    tolerances are set against.
    """
    p = _params(params)
    l2 = p["lambda_l2"]
    binned = np.asarray(binned)
    num_bins = np.asarray(num_bins, np.int64)
    bins = int(num_bins.max())
    if quantize is not None:
        grad, hess = quantize(grad), quantize(hess)
    absg = np.abs(grad)
    splits = len(tree.feature)
    rows: List[Optional[np.ndarray]] = [None] * splits
    rows[0] = np.arange(binned.shape[0], dtype=np.int64)
    depth = np.zeros(splits, np.int64)
    count_bad = set()       # splits whose children's counts differ
    gain_err = np.zeros(splits)
    grad_err = np.zeros(splits)
    hess_err = np.zeros(splits)
    hists: Dict[int, np.ndarray] = {}
    searched, ties, search_bad = 0, 0, []
    for i in range(splits):         # a parent comes before its children
        r = rows[i]
        rows[i] = None
        goes_right = (binned[r, tree.feature[i]]
                      > tree.threshold[i]).astype(np.intp)
        sides = (r[goes_right == 0], r[goes_right == 1])
        # each child's sums: [left, right]
        g2 = np.bincount(goes_right, grad[r], 2)
        h2 = np.bincount(goes_right, hess[r], 2)
        a2 = np.bincount(goes_right, absg[r], 2)
        sums = []
        for k, (c, side) in enumerate(zip((tree.left[i], tree.right[i]),
                                          sides)):
            g, h = float(g2[k]), float(h2[k])
            cnt, h_prog, out_prog = _child(tree, int(c))
            if cnt != len(side):
                count_bad.add(i)
            g_prog = -out_prog * (h_prog + l2)
            grad_err[i] = max(grad_err[i],
                              abs(g_prog - g) / max(float(a2[k]), 1e-300))
            hess_err[i] = max(hess_err[i],
                              abs(h_prog - h) / max(h, 1e-300))
            sums.append((g, h))
            if c >= 0:
                rows[c] = side
                depth[c] = depth[i] + 1
        gain, terms = _gain(sums[0][0], sums[0][1], sums[1][0],
                            sums[1][1], l2)
        gain_err[i] = abs(float(tree.gain[i]) - gain) / max(terms, 1e-300)
        # the search, on the first ``levels`` levels below the root
        if depth[i] <= levels:
            if i not in hists:
                hists[i] = _histogram(binned, r, grad, hess, bins)
            table, table_terms = _gain_table(hists[i], num_bins, p)
            f_best, t_best = np.unravel_index(int(np.argmax(table)),
                                              table.shape)
            f, t = int(tree.feature[i]), int(tree.threshold[i])
            searched += 1
            if (f, t) != (int(f_best), int(t_best)):
                gap = table[f_best, t_best] - table[f, t]
                if gap <= gain_rtol * table_terms[f_best, t_best]:
                    ties += 1
                else:
                    search_bad.append(i)
            # the children's histograms, where they are searched too
            kids = [(int(c), s) for c, s in zip(
                (tree.left[i], tree.right[i]), sides) if c >= 0]
            if depth[i] + 1 <= levels and kids:
                small = min(kids, key=lambda k: len(k[1]))
                hists[small[0]] = _histogram(binned, small[1], grad, hess,
                                             bins)
                for c, _ in kids:
                    if c != small[0]:
                        hists[c] = hists[i] - hists[small[0]]
            del hists[i]
    out = {"splits": splits,
           "count_mismatches": len(count_bad),
           "gain_err_max": float(gain_err.max(initial=0.0)),
           "gain_err_median": float(np.median(gain_err)) if splits else 0.0,
           "grad_err_max": float(grad_err.max(initial=0.0)),
           "hess_err_max": float(hess_err.max(initial=0.0)),
           "searched_nodes": searched, "search_ties": ties,
           "search_mismatches": len(search_bad)}
    out["ok"] = bool(splits > 0 and not count_bad and not search_bad
                     and out["gain_err_max"] <= gain_rtol
                     and out["gain_err_median"] <= gain_median_rtol)
    return out
