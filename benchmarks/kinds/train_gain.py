"""Training cells on a numeric table whose check (a) holds the
precision, not only the rule.

The step, the window, the fixed-work rate, the counters, the facts and
the checks (b) and (c) are those of ``kinds/train.py`` (its docstring
describes them): this runner calls its ``run``. It differs in check
(a) alone. On a table of thousands of weak columns two candidate
splits of a small leaf can tie exactly, float32 sums in another order
than the reference's take the other one, and the two models then part
in AUC and log-loss by more than a whole lower precision moves them
(``PERF.md``, PR 31), so no limit on those two numbers tells float32
from bfloat16. Check (a) here is the one ``kinds/train_cat.py`` makes
for its table, without the categories:

* both sides start from the same seeded scores
  (``check.init_score_sd``), so the first tree's gradients take a
  value a row;
* the plain reference is ``benchmarks/reference/gbdt_cat_numpy.py``
  with no column categorical, which scores numeric columns as
  ``gbdt_numpy.py`` does and hands back its trees;
* besides AUC and log-loss (``auc_tol``, ``logloss_tol``: the rule),
  the gain of every split of the first tree that both trees made on
  the same rows is compared with the reference's, and the median
  difference is held to ``check.gain_median_rtol``: the precision. A
  tie that falls the other way costs its subtree, not the check.

Before any data is made the run grows one tree on ``PROBE_ROWS`` rows
of the cell's width, so that a program that cannot take the width
fails at once (``_require_width``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from .. import stats
from ..spec import SpecError
from . import train
from .train_cat import INIT_SCORE_SEED, _first_tree_gains

PROBE_ROWS = 512


def _check_against_reference(lgb, ds, params, check) -> Dict[str, Any]:
    """(a): the cell's path and the plain reference on the first
    ``check.rows`` rows, from the same seeded scores."""
    from ..reference import gbdt_cat_numpy
    rows = min(int(check["rows"]), ds._inner.num_data)
    trees = int(check["trees"])
    # as the program holds them
    init = (np.random.default_rng(INIT_SCORE_SEED).standard_normal(rows)
            * float(check["init_score_sd"])).astype(np.float32)
    t0 = time.perf_counter()
    sub = ds.subset(np.arange(rows)).construct()
    sub.set_init_score(init)
    small = lgb.Booster(dict(params), sub)
    small._gbdt.train(1)
    small._gbdt.train(trees)
    got = train._score_head(small._gbdt, rows)
    t1 = time.perf_counter()
    inner = sub._inner
    labels = np.asarray(inner.metadata.label)
    forest: List[Dict[str, Any]] = []
    want = gbdt_cat_numpy.train(
        inner.binned, inner.num_bins_array(), labels, params, trees,
        forest=forest, init_score=init)
    out = {"rows": rows, "trees": trees,
           "auc": stats.auc(labels, got),
           "auc_reference": stats.auc(labels, want),
           "logloss": stats.logloss(labels, got),
           "logloss_reference": stats.logloss(labels, want),
           "learner": type(small._gbdt.learner).__name__,
           "program_s": round(t1 - t0, 2),
           "reference_s": round(time.perf_counter() - t1, 2)}
    out.update(_first_tree_gains(small._gbdt.models[0],
                                 forest[0]["splits"]))
    out["ok"] = bool(
        np.isfinite(got).all()
        and len(small._gbdt.models) == trees
        and abs(out["auc"] - out["auc_reference"]) <= check["auc_tol"]
        and abs(out["logloss"] - out["logloss_reference"])
        <= check["logloss_tol"]
        and out["gain_err_median"] <= check["gain_median_rtol"])
    return out


def _require_width(lgb, params, features: int) -> None:
    """Grows one tree on a few rows of the cell's width through the
    cell's own path, before any data is made: a program that cannot
    take the width (the split-step megakernel's static scope, until
    PR 31) raises here within seconds, not after it has binned
    ``rows x features`` values."""
    x = np.random.default_rng(0).standard_normal(
        (PROBE_ROWS, features)).astype(np.float32)
    probe = lgb.Booster(dict(params), lgb.Dataset(
        x, label=(x[:, 0] > 0).astype(np.float32), params=dict(params)))
    probe._gbdt.train(1)
    if len(probe._gbdt.models) != 1:
        raise SpecError(f"no tree grown on a table of {features} columns")


def run(ctx) -> Dict[str, Any]:
    import lightgbm_tpu as lgb
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    _require_width(lgb, dict(cfg["params"], **mix.get("params", {})),
                   int(cfg["features"]))
    # kinds/train.py's run, whole, with this module's check (a) where
    # it looks its own up: that file is the accepted benchmark's and
    # has no argument for it
    plain = train._check_against_reference
    train._check_against_reference = _check_against_reference
    try:
        return train.run(ctx)
    finally:
        train._check_against_reference = plain
