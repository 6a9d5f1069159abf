"""A boosted forest of leaf-wise shape, from a seed, without training.

The serving cells need a model of the published size (500 trees of 255
leaves) in every run, and training one would take longer than the run
and would tie the serving numbers to the trainer. So the trees are
drawn: each tree starts as one leaf holding all rows and, until it has
``num_leaves`` leaves, the leaf holding the most rows is split at a
feature and a bin threshold drawn uniformly from those that leave both
children non-empty. Rows are taken to fill bin space evenly and
independently per feature (the bins are equal-frequency), so a leaf's
share of the rows is the volume of its box. Thresholds drawn uniformly
split unevenly, which gives the uneven depth of leaf-wise growth and
not the log2(num_leaves) of a balanced tree. The tree-shape process is
an assumption (listed in the configuration file); the sizes are the
published ones.

All trees are grown at once, one split per step, as array operations
over the tree axis. Node numbering follows the reference's
``Tree::Split``: split ``i`` makes node ``i``; the leaf that is split
keeps its index as the left child and the new leaf ``i + 1`` is the
right child; a child pointer ``>= 0`` is a node and ``< 0`` is
``~leaf``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def make(seed: int, trees: int, num_leaves: int, num_bins: np.ndarray,
         leaf_scale: float) -> Dict[str, np.ndarray]:
    """Arrays over ``[trees, ...]``: ``split_feature``,
    ``threshold_bin``, ``left_child``, ``right_child`` over the
    ``num_leaves - 1`` nodes; ``leaf_value``, ``leaf_share`` (of the
    rows), ``leaf_parent``, ``leaf_depth`` over the leaves;
    ``node_share`` over the nodes."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7ee5]))
    num_bins = np.asarray(num_bins, np.int64)
    t_n, l_n, f_n = trees, num_leaves, len(num_bins)
    t_idx = np.arange(t_n)
    lo = np.zeros((t_n, l_n, f_n), np.int64)
    hi = np.zeros((t_n, l_n, f_n), np.int64)
    hi[:, 0, :] = num_bins - 1
    share = np.zeros((t_n, l_n))
    share[:, 0] = 1.0
    parent = np.full((t_n, l_n), -1, np.int64)
    depth = np.zeros((t_n, l_n), np.int64)
    feat = np.zeros((t_n, l_n - 1), np.int64)
    thr = np.zeros((t_n, l_n - 1), np.int64)
    left = np.zeros((t_n, l_n - 1), np.int64)
    right = np.zeros((t_n, l_n - 1), np.int64)
    node_share = np.zeros((t_n, l_n - 1))
    for i in range(l_n - 1):
        leaf = share.argmax(axis=1)
        width = hi[t_idx, leaf] - lo[t_idx, leaf]          # [T, F]
        if (width.max(axis=1) <= 0).any():
            raise ValueError("a leaf of one bin cell cannot be split; "
                             "fewer leaves or more bins")
        # a uniform draw among the features that can still be split
        f = np.where(width > 0, rng.random((t_n, f_n)), -1.0).argmax(1)
        w = width[t_idx, f]
        base = lo[t_idx, leaf, f]
        cut = base + np.minimum((rng.random(t_n) * w).astype(np.int64),
                                w - 1)                     # in [lo, hi-1]
        new = i + 1
        lo[:, new] = lo[t_idx, leaf]
        hi[:, new] = hi[t_idx, leaf]
        hi[t_idx, leaf, f] = cut
        lo[t_idx, new, f] = cut + 1
        whole = share[t_idx, leaf]
        left_part = whole * (cut - base + 1) / (w + 1)
        node_share[:, i] = whole
        share[t_idx, leaf] = left_part
        share[:, new] = whole - left_part
        feat[:, i], thr[:, i] = f, cut
        left[:, i], right[:, i] = ~leaf, ~new
        # the split leaf's parent now points at node i
        p = parent[t_idx, leaf]
        has = p >= 0
        was_left = np.zeros(t_n, bool)
        was_left[has] = left[t_idx[has], p[has]] == ~leaf[has]
        left[t_idx[has & was_left], p[has & was_left]] = i
        right[t_idx[has & ~was_left], p[has & ~was_left]] = i
        d = depth[t_idx, leaf] + 1
        parent[t_idx, leaf] = i
        parent[:, new] = i
        depth[t_idx, leaf] = d
        depth[:, new] = d
    return {"split_feature": feat, "threshold_bin": thr,
            "left_child": left, "right_child": right,
            "leaf_value": rng.normal(0.0, leaf_scale, (t_n, l_n)),
            "leaf_share": share, "node_share": node_share,
            "leaf_parent": parent, "leaf_depth": depth}
