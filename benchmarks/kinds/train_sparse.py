"""Training cells on a sparse one-hot table that arrives as a scipy
CSR and is bundled (EFB) by the program into a few physical columns.

The step, the window, the fixed-work rate, the counters, the facts and
the checks (b) and (c) are those of ``kinds/train.py`` (its docstring
describes them): this runner calls its ``run``, as
``kinds/train_gain.py`` does, with the CSR its generator returns handed
to ``lgb.Dataset``. It differs in four places:

* before any data is made, ``_require_bundled_width`` builds a small
  one-hot CSR of the cell's width and grows one tree on it through the
  cell's own path: a program that bundles such a table with
  conflicts, sends it to a multi-val layout no segment kernel reads,
  or cannot compile its bundled split body at the width raises at
  once, not after it has made and binned the whole table;
* check (a) is ``kinds/train_gain.py``'s (seeded start, AUC and
  log-loss for the rule, the first tree's gains split by split for the
  precision) against a reference that knows nothing of bundles:
  ``benchmarks/reference/gbdt_sparse_numpy.py`` on PER-COLUMN bins of
  all the table's columns. Those bins are made here from the raw
  CSR's values and the dataset's bin boundaries with ``searchsorted``,
  never through the program's bundle plan, its decoder or its binned
  matrix, so that a wrong offset, a value lost to a conflict or a
  wrong default-bin reconstruction shows as another tree;
* the ``check_path`` line adds what the program's counters say of the
  bundling: ``bundled`` (the bundled split body entered a grow
  program's trace), ``bundle_columns``, ``bundle_conflict_rows`` and
  ``multival_features``; the run is ``correct`` only where the table
  was bundled without a conflict row and without a multi-val feature;
* ``facts["features"]`` is the PHYSICAL column count the dataset
  reports, since that is the row the kernels move and the readers of
  ``grow_kernels_roofline`` and ``train_hbm_floor_share`` reckon bytes
  from it; ``facts["logical_features"]`` holds the table's columns.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from .. import stats
from ..spec import SpecError, load_module
from . import train
from .train_cat import INIT_SCORE_SEED, _first_tree_gains

BUNDLE_COUNTERS = {"logical_features": "data.bundle_features",
                   "bundle_columns": "data.bundle_columns",
                   "bundle_conflict_rows": "data.bundle_conflict_rows",
                   "multival_features": "data.multival_features"}
BUNDLED_TRACES = "learner.bundled_traces"
# the host spans of the sparse construction, as the program's
# vocabulary (observability/scopes.py) names them
BUNDLE_SPANS = ("DATA_BUNDLE_PLAN", "DATA_EXTRACT")
PROBE_ROUNDS = 2        # rows of the probe a value of the widest column


def _bundle_facts(tel) -> Dict[str, Any]:
    """What the program's counters say of the newest dataset's
    bundling; raises on a program that has no such counters."""
    missing = [c for c in BUNDLE_COUNTERS.values()
               if c not in tel.counters]
    if missing:
        raise SpecError(f"this program counts no {missing}: it cannot "
                        "say how it bundled the table")
    return {k: int(tel.counters[c]) for k, c in BUNDLE_COUNTERS.items()}


def _span_seconds(tel) -> Dict[str, float]:
    """Seconds under each construction span so far (a span opened
    inside another is held under a longer path)."""
    try:
        from lightgbm_tpu.observability import scopes
        names = [getattr(scopes, c) for c in BUNDLE_SPANS]
    except (ImportError, AttributeError):
        return {}
    return {name: sum(v[0] for k, v in tel.spans.items()
                      if k.split("/")[-1] == name) for name in names}


def _probe_table(features: int, numeric: int, cards: List[int]):
    """A small one-hot CSR of the cell's width in which every column
    holds a value: row ``r`` has value ``r mod c`` of a categorical
    with ``c`` values."""
    import scipy.sparse as sp
    rows = PROBE_ROUNDS * max(cards)
    rng = np.random.default_rng(0)
    per_row = numeric + len(cards)
    data = np.ones((rows, per_row), np.float32)
    data[:, :numeric] = rng.standard_normal((rows, numeric))
    columns = np.empty((rows, per_row), np.int32)
    columns[:, :numeric] = np.arange(numeric)
    base = numeric + np.concatenate([[0], np.cumsum(cards)[:-1]])
    for k, c in enumerate(cards):
        columns[:, numeric + k] = base[k] + np.arange(rows) % c
    x = sp.csr_matrix(
        (data.ravel(), columns.ravel(),
         np.arange(rows + 1, dtype=np.int32) * per_row),
        shape=(rows, features))
    return x, (data[:, 0] > 0).astype(np.float32)


def _require_bundled_width(lgb, tel, params, cfg, learner: str) -> None:
    """Grows one tree on a one-hot CSR of the cell's width through the
    cell's own path, before any data is made."""
    gen = load_module("generators", cfg["generator"]["name"])
    cards = list(cfg["generator"].get("params", {}).get(
        "cards", gen.CARDS))
    x, y = _probe_table(int(cfg["features"]), gen.NUMERIC, cards)
    # every column of the probe is to be a feature, few rows as it has
    probe_params = dict(params, min_data_in_bin=1, min_data_in_leaf=1,
                        feature_pre_filter=False)
    ds = lgb.Dataset(x, label=y, params=dict(probe_params)).construct()
    facts = _bundle_facts(tel)
    if facts["bundle_conflict_rows"] or facts["multival_features"] \
            or facts["bundle_columns"] >= facts["logical_features"]:
        raise SpecError(
            "this program does not bundle a one-hot table of "
            f"{cfg['features']} columns losslessly into physical "
            f"columns: {facts}")
    probe = lgb.Booster(dict(probe_params), ds)
    probe._gbdt.train(1)
    grown_by = probe._gbdt.learner
    if len(probe._gbdt.models) != 1 or type(grown_by).__name__ != learner \
            or not getattr(grown_by, "bundled", False):
        raise SpecError(
            f"no tree grown by {learner}'s bundled split body on a "
            f"table of {cfg['features']} columns, but by "
            f"{type(grown_by).__name__}")


def _column_bins(inner, x):
    """``(indptr, columns, bins, default_bin, num_bins)`` of the CSR
    ``x`` for the plain reference: every column of the TABLE (a column
    the dataset found constant has one bin) binned by the dataset's
    bin boundaries, the entries at a column's default bin (the bin of
    the value 0, which a CSR does not store) left out. Plain numpy on
    the raw values; nothing of the program's bundling is read."""
    features = x.shape[1]
    num_bins = np.ones(features, np.int64)
    default_bin = np.zeros(features, np.int64)
    x = x.tocsr()
    order = np.argsort(x.indices, kind="stable")
    col_sorted = x.indices[order]
    starts = np.searchsorted(col_sorted, np.arange(features + 1))
    bins = np.zeros(x.nnz, np.int64)
    keep = np.zeros(x.nnz, bool)
    for column in range(features):
        used = inner.inner_feature_index(column)
        if used < 0:
            continue
        mapper = inner.feature_mapper(used)
        if mapper.bin_type != "numerical" \
                or mapper.missing_type != "None":
            raise SpecError(f"column {column} is {mapper.bin_type} with "
                            f"missing type {mapper.missing_type}: this "
                            "check bins plain numeric columns")
        bounds = np.asarray(mapper.bin_upper_bound[:mapper.num_bin],
                            np.float64)
        num_bins[column] = mapper.num_bin

        def to_bin(values):
            return np.minimum(np.searchsorted(bounds, values, side="left"),
                              len(bounds) - 1)
        default_bin[column] = to_bin(np.zeros(1))[0]
        mine = order[starts[column]:starts[column + 1]]
        bins[mine] = to_bin(x.data[mine].astype(np.float64))
        keep[mine] = bins[mine] != default_bin[column]
    row_of_entry = np.repeat(np.arange(x.shape[0]), np.diff(x.indptr))
    indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(row_of_entry[keep],
                                    minlength=x.shape[0]))])
    return indptr, x.indices[keep], bins[keep], default_bin, num_bins


def _check_against_reference(lgb, ds, params, check, head) -> Dict[str, Any]:
    """(a): the cell's path on the first ``check.rows`` rows of its
    bundled table, and the plain reference on the same rows' raw
    values (``head(rows)`` makes them again), from the same seeded
    scores."""
    from ..reference import gbdt_sparse_numpy
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
    labels = np.asarray(sub._inner.metadata.label)
    x, y = head(rows)
    if x.shape[0] != rows or not np.array_equal(y, labels):
        raise SpecError("the generator's head is not the table's head")
    forest: List[Dict[str, Any]] = []
    want = gbdt_sparse_numpy.train(
        *_column_bins(ds._inner, x), labels, params, trees,
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


class _PathReport:
    """The context ``kinds/train.py``'s run is handed, with the
    bundling added to its ``check_path`` line."""

    def __init__(self, ctx, bundle: Dict[str, Any]):
        self._ctx, self._bundle = ctx, bundle

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def info(self, what: str, **fields) -> None:
        if what == "check_path":
            fields.update(self._bundle)
            fields["ok"] = bool(fields["ok"] and self._bundle["bundle_ok"])
        self._ctx.info(what, **fields)


def run(ctx) -> Dict[str, Any]:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability.telemetry import get_telemetry
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    params = dict(cfg["params"], **mix.get("params", {}))
    tel = get_telemetry()
    tel.ensure_ring()               # counters only, no sink
    _require_bundled_width(lgb, tel, params, cfg,
                           mix["expect"]["learner"])
    gen_spec = cfg["generator"]
    gen = load_module("generators", gen_spec["name"])
    spans0 = _span_seconds(tel)
    traces0 = tel.counters.get(BUNDLED_TRACES, 0)
    bundle: Dict[str, Any] = {}

    def head(rows):
        return gen.make(ctx.seed, int(mix["rows"]), int(cfg["features"]),
                        head=rows, **gen_spec.get("params", {}))

    def check(lgb, ds, params, check):
        # the first thing ``train.run`` does after the window with the
        # dataset in hand: the counters are still the window's table's
        bundle.update(_bundle_facts(tel))
        bundle["bundled"] = bool(
            tel.counters.get(BUNDLED_TRACES, 0) > traces0)
        bundle["bundle_ok"] = bool(
            bundle["bundled"] and bundle["bundle_conflict_rows"] == 0
            and bundle["multival_features"] == 0
            and bundle["bundle_columns"] < bundle["logical_features"])
        return _check_against_reference(lgb, ds, params, check, head)

    # kinds/train.py's run, whole, with this module's check (a) where
    # it looks its own up: that file is the accepted benchmark's and
    # has no argument for it
    plain = train._check_against_reference
    train._check_against_reference = check
    try:
        obs = train.run(_PathReport(ctx, bundle))
    finally:
        train._check_against_reference = plain
    spans = _span_seconds(tel)
    facts = obs["facts"]
    facts["logical_features"] = bundle["logical_features"]
    facts["features"] = bundle["bundle_columns"]
    # a table loaded from the data cache was bundled by an earlier run:
    # the spans then hold nothing of this run's set-up
    facts["bundle_s"] = sum(spans[k] - spans0.get(k, 0.0) for k in spans) \
        if spans else None
    ctx.info("bundle", seconds={k: round(spans[k] - spans0.get(k, 0.0), 3)
                                for k in spans},
             hbm_floor_share_pct=load_module(
                 "layers", "train_hbm_floor_share").read(facts), **bundle)
    obs["correct"] = bool(obs["correct"] and bundle["bundle_ok"])
    return obs
