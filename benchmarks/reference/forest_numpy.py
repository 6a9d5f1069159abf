"""Plain reference of forest prediction: every tree walked in value
space, leaf values summed in float64 in tree order. No bins, no
batching, no device.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def predict_raw(forest: Dict[str, np.ndarray], threshold_value: np.ndarray,
                x: np.ndarray) -> np.ndarray:
    """Raw scores ``[rows]`` of ``x [rows, features]``. ``forest`` is
    the generator's arrays; ``threshold_value [trees, nodes]`` holds
    each node's threshold as a feature value (a row goes left when its
    value is ``<=`` it)."""
    x = np.asarray(x, np.float64)
    rows = np.arange(len(x))
    out = np.zeros(len(x), np.float64)
    for t in range(len(forest["split_feature"])):
        feat = forest["split_feature"][t]
        left = forest["left_child"][t]
        right = forest["right_child"][t]
        node = np.zeros(len(x), np.int64)
        live = np.ones(len(x), bool)
        leaf = np.zeros(len(x), np.int64)
        while live.any():
            r = rows[live]
            nd = node[r]
            go_left = x[r, feat[nd]] <= threshold_value[t, nd]
            child = np.where(go_left, left[nd], right[nd])
            at_leaf = child < 0
            leaf[r[at_leaf]] = ~child[at_leaf]
            node[r[~at_leaf]] = child[~at_leaf]
            live[r[at_leaf]] = False
        out += forest["leaf_value"][t][leaf]
    return out


def sigmoid(raw: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-np.asarray(raw, np.float64)))
