"""The benchmark's own arithmetic: percentiles, AUC and log-loss.

Plain NumPy, so that no number the benchmark reports depends on the
program's metric code.
"""

from __future__ import annotations

import numpy as np

# a percentile wants ten samples beyond it (choosing-metrics guide)
SAMPLES_BEYOND = 10


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), linear between order
    statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def enough_for(count: int, q: float) -> bool:
    """True when ``count`` samples leave ten beyond the ``q``-th
    percentile."""
    return count * (100.0 - q) / 100.0 >= SAMPLES_BEYOND


def auc(labels, scores) -> float:
    """Area under the ROC curve by ranks, ties sharing their mean
    rank."""
    y = np.asarray(labels, np.float64).ravel() > 0.5
    s = np.asarray(scores, np.float64).ravel()
    order = np.argsort(s, kind="mergesort")
    sorted_s = s[order]
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.arange(1, len(s) + 1, dtype=np.float64)
    # mean rank within each run of equal scores
    starts = np.flatnonzero(np.r_[True, sorted_s[1:] != sorted_s[:-1]])
    ends = np.r_[starts[1:], len(s)]
    mean_rank = (starts + 1 + ends) / 2.0
    ranks[order] = np.repeat(mean_rank, ends - starts)
    pos = int(y.sum())
    neg = len(y) - pos
    if pos == 0 or neg == 0:
        raise ValueError("AUC needs both classes")
    return float((ranks[y].sum() - pos * (pos + 1) / 2.0) / (pos * neg))


def logloss(labels, raw_scores) -> float:
    """Mean binary cross-entropy of raw (log-odds) scores."""
    y = np.asarray(labels, np.float64).ravel()
    s = np.asarray(raw_scores, np.float64).ravel()
    # log(1 + exp(-z)) with z = s for y = 1 and -s for y = 0
    z = np.where(y > 0.5, s, -s)
    return float(np.mean(np.logaddexp(0.0, -z)))
