"""Training cells on a learning-to-rank table: rows in ragged query
groups, relevance grades as labels, lambdarank's pairwise gradients.

The step, the window, the fixed-work rate, the counters, the facts and
the checks (b) and (c) are those of ``kinds/train.py`` (its docstring
describes them): this runner calls its ``run``, as
``kinds/train_gain.py`` does. It differs in five places:

* before any data is made, ``_require_ragged_layout`` trains one tree
  on a ``PROBE_ROWS``-row table of the cell's width whose queries hold
  1 to 1,251 documents, through the cell's own path, and raises unless
  the program's counters say its query layout follows the documents
  (``objective.rank_slots`` at most ``PROBE_SLOTS_PER_DOC`` x
  ``objective.rank_docs``): a program that pads every query to the
  longest, or counts no such thing, fails at once and does not spend
  minutes on a table it cannot train at a usable speed;
* the dataset is made with its query groups: the generator returns
  ``(table, grades, query sizes)``; ``datacache.binned_dataset`` knows
  no group, so the sizes, which depend on the configuration's
  ``table_seed`` alone, are derived again and set on the table it
  hands back, cached or new;
* check (a) is against ``benchmarks/reference/gbdt_rank_numpy.py`` on
  the first ``check.queries`` WHOLE queries, from the same seeded
  scores (``check.init_score_sd``): the first iteration's gradients
  and hessians element by element (``grad_rtol``, ``hess_rtol``: the
  precision and the formula), the first tree's gains split by split
  (``gain_median_rtol``, ``kinds/train_cat.py``'s comparison), and the
  in-sample NDCG@10 of both models (``ndcg_tol``: the rule);
* check (b) judges the window's model by NDCG@10 over the first
  ``check.ndcg_queries`` queries (at least ``check.min_ndcg`` and no
  lower than after warm-up), where ``kinds/train.py`` reads an AUC of
  a binary label;
* the ``check_path`` line adds ``objective``, ``rank_classes``,
  ``rank_slots``, ``rank_docs``; the run is ``correct`` only where the
  objective is lambdarank and the layout kept to the documents at the
  cell's size too. ``facts["rank"]`` carries the counters for the
  readers.
"""

from __future__ import annotations

import dataclasses
import time
import types
from typing import Any, Dict, List

import numpy as np

from ..spec import SpecError, load_module
from . import train
from .train_cat import INIT_SCORE_SEED, _first_tree_gains

PROBE_ROWS = 4000
PROBE_SLOTS_PER_DOC = 2.0
NDCG_AT = 10
RANK_COUNTERS = ("queries", "docs", "slots", "pair_slots", "doc_pairs",
                 "classes")


def _rank_facts(tel) -> Dict[str, int]:
    """What the program's counters say of the newest ranking
    objective's query layout; raises on a program that counts none."""
    missing = [c for c in RANK_COUNTERS
               if "objective.rank_" + c not in tel.counters]
    if missing:
        raise SpecError(f"this program counts no objective.rank_* "
                        f"{missing}: it cannot say what its query "
                        "layout came to")
    return {c: int(tel.counters["objective.rank_" + c])
            for c in RANK_COUNTERS}


def _sizes(cfg, rows: int) -> np.ndarray:
    gen = load_module("generators", cfg["generator"]["name"])
    p = cfg["generator"].get("params", {})
    return gen.query_sizes(rows, **{k: p[k] for k in
                                    ("table_seed", "mean_query", "longest")
                                    if k in p})


def _require_ragged_layout(lgb, tel, params, features: int,
                           learner: str) -> None:
    """One tree on a small ragged table of the cell's width through
    the cell's own path, before any data is made."""
    rng = np.random.default_rng(0)
    sizes = [1251, 1]
    while sum(sizes) < PROBE_ROWS - 300:
        sizes.append(int(rng.integers(2, 300)))
    sizes.append(PROBE_ROWS - sum(sizes))
    x = rng.standard_normal((PROBE_ROWS, features)).astype(np.float32)
    y = np.clip(np.round(x[:, 0] + 1.0), 0, 4).astype(np.float32)
    probe = lgb.Booster(dict(params), lgb.Dataset(
        x, label=y, group=sizes, params=dict(params)))
    facts = _rank_facts(tel)
    if facts["docs"] != PROBE_ROWS \
            or facts["slots"] > PROBE_SLOTS_PER_DOC * facts["docs"]:
        raise SpecError(
            "this program's query layout does not follow the documents: "
            f"{facts['slots']} slots for {facts['docs']} documents in "
            f"queries of 1 to 1,251 (more than {PROBE_SLOTS_PER_DOC} a "
            "document); at the cell's size it would pad every query to "
            "the longest")
    probe._gbdt.train(1)
    grown_by = type(probe._gbdt.learner).__name__
    if len(probe._gbdt.models) != 1 or grown_by != learner:
        raise SpecError(f"no tree grown by {learner} on a ragged table "
                        f"of {features} columns, but by {grown_by}")


def _check_against_reference(lgb, ds, params, check,
                             reference=None) -> Dict[str, Any]:
    """(a): the cell's path and the plain reference on the first
    ``check.queries`` whole queries, from the same seeded scores."""
    from ..reference import gbdt_rank_numpy
    reference = reference or gbdt_rank_numpy.train
    sizes = np.asarray(ds.get_group(), np.int64)
    sizes = sizes[:min(int(check["queries"]), len(sizes))]
    rows, trees = int(sizes.sum()), int(check["trees"])
    # as the program holds them
    init = (np.random.default_rng(INIT_SCORE_SEED).standard_normal(rows)
            * float(check["init_score_sd"])).astype(np.float32)
    t0 = time.perf_counter()
    sub = ds.subset(np.arange(rows)).construct()
    sub.set_init_score(init)
    small = lgb.Booster(dict(params), sub)
    gbdt = small._gbdt
    # the first iteration's gradients, as the program computes them
    # from the scores it holds
    grad, hess = (np.asarray(a, np.float64) for a in gbdt._grad_fn(
        gbdt.train_score[:, 0], *getattr(gbdt, "_grad_operands", ())))
    gbdt.train(1)
    gbdt.train(trees)
    got = train._score_head(gbdt, rows)
    t1 = time.perf_counter()
    inner = sub._inner
    if not np.array_equal(np.diff(inner.metadata.query_boundaries), sizes):
        raise SpecError("the subset does not end on a query boundary")
    labels = np.asarray(inner.metadata.label)
    forest: List[Dict[str, Any]] = []
    first: List[Any] = []
    want = reference(inner.binned, inner.num_bins_array(), labels, sizes,
                     params, trees, forest=forest, init_score=init,
                     first_gradients=first)

    def worst(mine, theirs):
        # an element's error against the largest element of its query:
        # a document whose pairs nearly cancel is held to its query's
        # scale, not to its own
        top = np.maximum.reduceat(np.abs(theirs),
                                  np.cumsum(sizes) - sizes)
        scale = np.repeat(np.maximum(top, 1e-30), sizes)
        return float((np.abs(mine - theirs) / scale).max())
    out = {"rows": rows, "queries": len(sizes), "trees": trees,
           "grad_err": worst(grad, first[0][0]),
           "hess_err": worst(hess, first[0][1]),
           "ndcg": gbdt_rank_numpy.ndcg_at(got, labels, sizes, NDCG_AT,
                                           params),
           "ndcg_reference": gbdt_rank_numpy.ndcg_at(
               want, labels, sizes, NDCG_AT, params),
           "learner": type(gbdt.learner).__name__,
           "program_s": round(t1 - t0, 2),
           "reference_s": round(time.perf_counter() - t1, 2)}
    out.update(_first_tree_gains(gbdt.models[0], forest[0]["splits"]))
    out["ok"] = bool(
        np.isfinite(got).all() and len(gbdt.models) == trees
        and out["grad_err"] <= check["grad_rtol"]
        and out["hess_err"] <= check["hess_rtol"]
        and out["gain_err_median"] <= check["gain_median_rtol"]
        and abs(out["ndcg"] - out["ndcg_reference"]) <= check["ndcg_tol"])
    return out


class _RankReport:
    """The context ``kinds/train.py``'s run is handed: the cell with
    check (b)'s limits read from the ranking check, and the lines that
    name an AUC renamed to what they hold."""

    RENAMED = {"auc": "ndcg10", "auc_warm": "ndcg10_warm",
               "auc_end": "ndcg10_end"}

    def __init__(self, ctx, cell, path: Dict[str, Any]):
        self._ctx, self.cell, self._path = ctx, cell, path

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def info(self, what: str, **fields) -> None:
        fields = {self.RENAMED.get(k, k): v for k, v in fields.items()}
        if what == "check_path":
            fields.update(self._path)
            fields["ok"] = bool(fields["ok"] and self._path["rank_ok"])
        self._ctx.info(what, **fields)


def run(ctx) -> Dict[str, Any]:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability.telemetry import get_telemetry

    from ..reference import gbdt_rank_numpy
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    params = dict(cfg["params"], **mix.get("params", {}))
    tel = get_telemetry()
    tel.ensure_ring()               # counters only, no sink
    _require_ragged_layout(lgb, tel, params, int(cfg["features"]),
                           mix["expect"]["learner"])
    sizes = _sizes(cfg, int(mix["rows"]))
    check = cfg["check"]
    head = sizes[:min(int(check["ndcg_queries"]), len(sizes))]
    # kinds/train.py's check (b) reads ``auc_rows`` and ``min_auc``
    cell = dataclasses.replace(ctx.cell, config=dict(cfg, check=dict(
        check, auc_rows=int(head.sum()), min_auc=check["min_ndcg"])))
    path: Dict[str, Any] = {}
    rank: Dict[str, int] = {}

    def ndcg(labels, scores):
        return gbdt_rank_numpy.ndcg_at(scores, labels, head, NDCG_AT,
                                       params)

    def grouped(lgb, made_from, dataset_params, make_xy, cache_dir):
        ds, info = plain["binned_dataset"](
            lgb, made_from, dataset_params,
            make_xy=lambda: make_xy()[:2], cache_dir=cache_dir)
        ds.set_group(sizes)
        return ds, info

    def checked(lgb, ds, params, check):
        # the first thing ``train.run`` does after the window with the
        # dataset in hand: the counters are still the window's booster's
        rank.update(_rank_facts(tel))
        path.update(
            objective=params["objective"],
            rank_classes=rank["classes"], rank_slots=rank["slots"],
            rank_docs=rank["docs"],
            rank_ok=bool(params["objective"] == "lambdarank"
                         and rank["docs"] == int(mix["rows"])
                         and rank["slots"] <= PROBE_SLOTS_PER_DOC
                         * rank["docs"]))
        return _check_against_reference(lgb, ds, params, check)

    # kinds/train.py's run, whole, with this module's dataset, check
    # (a) and quality measure where it looks its own up: that file is
    # the accepted benchmark's and has no argument for them
    plain = {"binned_dataset": train.binned_dataset,
             "_check_against_reference": train._check_against_reference,
             "stats": train.stats}
    train.binned_dataset = grouped
    train._check_against_reference = checked
    train.stats = types.SimpleNamespace(auc=ndcg)
    try:
        obs = train.run(_RankReport(ctx, cell, path))
    finally:
        for name, value in plain.items():
            setattr(train, name, value)
    obs["facts"]["rank"] = rank
    obs["correct"] = bool(obs["correct"] and path["rank_ok"])
    return obs
