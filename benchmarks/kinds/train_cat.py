"""Training cells on a table with categorical columns.

The step, the window, the fixed-work rate, the counters and the checks
(b) and (c) are those of ``kinds/train.py`` (its docstring describes
them); this runner differs where a categorical table has to:

* the configuration names its categorical columns in ``params``
  (``categorical_feature``), the way a user's config file does, and the
  run refuses at once a program that does not read them there, or a
  binned table whose columns came out otherwise;
* check (a) is against ``benchmarks/reference/gbdt_cat_numpy.py``,
  which is told which columns' bins are categories and how many of a
  column's bins are (``gbdt_numpy.py`` scores every column as ordered);
  besides AUC and log-loss it compares the gain of every split of the
  first tree that both trees made on the same rows with the
  reference's (``check.gain_median_rtol``);
* each traced tree's facts hold its count of categorical splits, and
  the window's model must have at least ``check.min_cat_split_share``
  of its splits categorical, so that the cell is about what it says;
* the path report says whether the bitset (LUT) partition and the
  categorical scan entered a grow program's trace, from the program's
  trace-time counters.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from .. import stats
from ..datacache import binned_dataset
from ..layers import train_hbm_floor_share
from ..spec import SpecError, load_module
from ..tracing import TraceWindow
from .train import WINDOW_COUNTERS, _score_head, _tree_facts

INIT_SCORE_SEED = 27
ROUTE_COUNTERS = {"megakernel": "learner.megakernel_traces",
                  "lut_partition": "learner.lut_partition_traces",
                  "cat_scan": "learner.cat_scan_traces"}


def _cat_splits(tree) -> int:
    """Splits of one host tree that test membership in a category set
    (bit 0 of the node's decision type)."""
    n = max(int(tree.num_leaves) - 1, 0)
    return int((np.asarray(tree.decision_type[:n]).astype(np.int64)
                & 1).sum())


def _column_kinds(inner):
    """``(categorical [features] bool, category_bins [features])`` of a
    binned table: which columns' bins are categories, and how many of a
    column's bins are (its last bin is none where the table keeps one
    for rare, unseen and missing values)."""
    mappers = [inner.feature_mapper(i) for i in range(inner.num_features)]
    categorical = np.array([m.bin_type == "categorical" for m in mappers])
    category_bins = np.array(
        [m.num_bin - (0 if m.missing_type == "None" else 1)
         for m in mappers], np.int64)
    return categorical, category_bins


def _left_bins(bitset) -> List[int]:
    return [w * 32 + b for w, word in enumerate(bitset)
            for b in range(32) if int(word) >> b & 1]


def _same_split(tree, i, ref) -> bool:
    """Split ``i`` of a host tree and a reference split are one split:
    same rows in the leaf, same column, same threshold or category
    set."""
    if int(tree.split_feature[i]) != ref["feature"] \
            or int(round(float(tree.internal_count[i]))) != ref["rows"]:
        return False
    if "left_bins" in ref:
        return bool(int(tree.decision_type[i]) & 1) \
            and _left_bins(tree.cat_bitsets[i]) == ref["left_bins"]
    return not int(tree.decision_type[i]) & 1 \
        and int(tree.threshold_bin[i]) == ref["threshold"]


def _first_tree_gains(tree, ref_splits) -> Dict[str, Any]:
    """The first tree against the reference's, leaf by leaf. Both start
    from the same scores, so the two roots hold the same rows; and the
    two children of a split hold the same rows in both trees wherever
    the split is the same split in both (``_same_split``), in whatever
    order the two trees came to make it. Wherever both trees split
    such a leaf, the two searches saw the same histogram and the two
    gains differ by the arithmetic alone, whichever of two nearly equal
    candidates each took. Under a split that differs (a near-tie
    between two category sets) nothing is compared: those are other
    leaves. Each compared split's reading is the difference of the two
    gains as a share of the reference's ``terms`` (the children's two
    score terms, of which a gain is the small remainder above the
    parent's: the rounding goes by their size, not by the gain's).
    ``gain_err_median`` is the reading that tells float32 histograms,
    in the root's histogram and in the split loop's segment histograms
    and scans, from a lower precision, which AUC and log-loss of five
    255-leaf trees cannot (a near-tie that falls the other way moves
    them more). ``gain_err_max`` is printed and held to nothing: one
    small leaf at the end of a chain of histogram subtractions sets it
    (PERF.md, PR 27)."""
    n = int(tree.num_leaves) - 1
    # path from the root ("LRL") -> split, in each tree
    mine, stack = {}, [(0, "")] if n > 0 else []
    while stack:
        i, path = stack.pop()
        mine[path] = i
        for side, child in (("L", tree.left_child[i]),
                            ("R", tree.right_child[i])):
            if child >= 0:
                stack.append((int(child), path + side))
    theirs, leaf_path = {}, {0: ""}
    for i, ref in enumerate(ref_splits):
        path = leaf_path[ref["leaf"]]
        theirs[path] = ref
        leaf_path[ref["leaf"]], leaf_path[i + 1] = path + "L", path + "R"
    err, rows, todo = [], [], [""]
    while todo:
        path = todo.pop()
        if path not in mine or path not in theirs:
            continue
        i, ref = mine[path], theirs[path]
        err.append(abs(float(tree.split_gain[i]) - ref["gain"])
                   / ref["terms"])
        rows.append(ref["rows"])
        if _same_split(tree, i, ref):
            todo += [path + "L", path + "R"]
    if not err:
        return {"gain_err_max": float("inf"),
                "gain_err_median": float("inf"),
                "first_tree_compared_splits": 0, "first_tree_splits": n}
    worst = int(np.argmax(err))
    return {"gain_err_max": err[worst], "gain_err_max_rows": rows[worst],
            "gain_err_median": float(np.median(err)),
            "gain_err_mean": float(np.mean(err)),
            "gain_err_root": err[0],
            "first_tree_compared_splits": len(err),
            "first_tree_splits": n}


def _require_params_route(lgb, params) -> List[int]:
    """The categorical columns ``params`` names. Raises, before any
    data is made, on a program whose ``Dataset`` does not read them
    from ``params``: such a program would train the table as numeric."""
    want = [int(c) for c in
            str(params["categorical_feature"]).split(",")]
    probe = np.tile(np.arange(8, dtype=np.float64)[:, None], (8, 2))
    inner = lgb.Dataset(
        probe, label=np.arange(64) % 2,
        params={"categorical_feature": "0", "min_data_in_bin": 1,
                "verbosity": -1}).construct()._inner
    if inner.feature_mapper(0).bin_type != "categorical":
        raise SpecError(
            "this program's lgb.Dataset ignores categorical_feature in "
            "params; the cell cannot state its categorical columns")
    return want


def _check_against_reference(lgb, ds, params, check) -> Dict[str, Any]:
    """(a): the cell's path and the plain reference on the first
    ``check.rows`` rows. Both start from the same seeded scores
    (``check.init_score_sd``), so the first tree's gradients take a
    value a row: from the labels' log-odds they take two values and its
    hessians one, and a gain then sees of a lower precision only the
    difference between those two values' roundings, which is nothing
    on about half of all tables (``_first_tree_gains`` holds the
    precision; PERF.md, PR 27)."""
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
    got = _score_head(small._gbdt, rows)
    t1 = time.perf_counter()
    inner = sub._inner
    labels = np.asarray(inner.metadata.label)
    categorical, category_bins = _column_kinds(inner)
    forest: List[Dict[str, Any]] = []
    want = gbdt_cat_numpy.train(
        inner.binned, inner.num_bins_array(), labels, params, trees,
        categorical=categorical, category_bins=category_bins,
        forest=forest, init_score=init)
    out = {"rows": rows, "trees": trees,
           "auc": stats.auc(labels, got),
           "auc_reference": stats.auc(labels, want),
           "logloss": stats.logloss(labels, got),
           "logloss_reference": stats.logloss(labels, want),
           "cat_splits": sum(_cat_splits(t) for t in small._gbdt.models),
           "cat_splits_reference": sum(
               "left_bins" in s for t in forest for s in t["splits"]),
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


def run(ctx) -> Dict[str, Any]:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability.telemetry import get_telemetry
    from lightgbm_tpu.utils.sync import fetch_one

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    params = dict(cfg["params"], **mix.get("params", {}))
    rows, block = int(mix["rows"]), int(mix["block"])
    features = int(cfg["features"])
    check = cfg["check"]
    gen_spec = cfg["generator"]
    gen = load_module("generators", gen_spec["name"])
    cat_columns = _require_params_route(lgb, params)

    tel = get_telemetry()
    tel.ensure_ring()               # counters only, no sink
    route0 = {k: tel.counters.get(c, 0) for k, c in ROUTE_COUNTERS.items()}
    ds, ds_info = binned_dataset(
        lgb,
        made_from={"config": ctx.cell.config_name,
                   "generator": gen_spec["name"],
                   "generator_params": gen_spec.get("params", {}),
                   "rows": rows, "features": features, "seed": ctx.seed},
        dataset_params=params,
        make_xy=lambda: gen.make(ctx.seed, rows, features,
                                 **gen_spec.get("params", {})),
        cache_dir=ctx.cache_dir)
    categorical, category_bins = _column_kinds(ds._inner)
    if np.flatnonzero(categorical).tolist() != cat_columns:
        raise SpecError(
            f"the binned table's categorical columns are "
            f"{np.flatnonzero(categorical).tolist()}, the configuration "
            f"names {cat_columns}")
    ctx.info("dataset", num_bins=ds._inner.num_bins_array().tolist(),
             category_bins=category_bins[categorical].tolist(),
             **{k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in ds_info.items()})
    t_ds = time.perf_counter()
    bst = lgb.Booster(dict(params), ds)
    gbdt = bst._gbdt
    labels = np.asarray(ds._inner.metadata.label)
    auc_rows = min(int(check["auc_rows"]), rows)

    def step() -> None:
        gbdt.train(gbdt.iter + block)
        fetch_one(gbdt.train_score[:1])

    gbdt.train(1)                   # the first iteration's own path
    step()                          # compiles the window's one program
    auc_warm = stats.auc(labels[:auc_rows], _score_head(gbdt, auc_rows))
    warm_trees = len(gbdt.models)
    ctx.info("warm", learner=type(gbdt.learner).__name__,
             trees=warm_trees, auc=round(auc_warm, 6),
             booster_and_warm_s=round(time.perf_counter() - t_ds, 2))

    # ---- the window ---------------------------------------------------
    tracer = TraceWindow(ctx) if ctx.trace else None
    trace_steps = int(mix.get("trace_steps", 2))
    before = {k: tel.counters.get(k, 0) for k in WINDOW_COUNTERS}
    ctx.start_window()
    compiles0 = ctx.compiles.compiles
    durations: List[float] = []
    ends: List[float] = []
    traced: List[int] = []
    t0 = time.perf_counter()
    while True:
        i = len(durations)
        # the first step runs untraced; the next trace_steps are traced
        if tracer is not None and i == 1:
            tracer.start()
        t_step = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            step()
        now = time.perf_counter()
        durations.append(now - t_step)
        ends.append(now - t0)
        if tracer is not None and tracer.running:
            traced.append(i)
            if len(traced) == trace_steps:
                tracer.stop()
        if now - t0 >= ctx.seconds and (tracer is None or tracer.done):
            break
    steps = len(durations)
    # from the window's start to the barrier of the last measured step;
    # in a traced run this holds the time the profiler took to stop,
    # and no end-to-end metric is reported
    measured = min(int(mix.get("measure_steps", steps)), steps)
    window_s = ends[measured - 1]
    compiles_in_window = ctx.compiles.compiles - compiles0
    counters = {k: tel.counters.get(k, 0) - v for k, v in before.items()}
    rate = rows * block * measured / window_s / 1e6
    untraced = [d for i, d in enumerate(durations) if i not in traced]
    rate_untraced = rows * block * len(untraced) / sum(untraced) / 1e6

    # ---- correctness, outside the window -------------------------------
    models = list(gbdt.models)
    leaves = [int(t.num_leaves) for t in models]
    head = _score_head(gbdt, auc_rows)
    finite = bool(np.isfinite(head).all())
    auc_end = stats.auc(labels[:auc_rows], head) if finite else float("nan")
    window_trees = models[warm_trees:]
    window_splits = sum(n - 1 for n in leaves[warm_trees:])
    cat_share = sum(_cat_splits(t) for t in window_trees) \
        / max(window_splits, 1)
    full = {"trees": len(models), "min_leaves": min(leaves),
            "auc_warm": round(auc_warm, 6), "auc_end": round(auc_end, 6),
            "window_splits": window_splits,
            "cat_split_share": round(cat_share, 4)}
    full["ok"] = bool(
        finite and len(models) == warm_trees + block * steps
        and min(leaves) > 1 and auc_end >= check["min_auc"]
        and auc_end >= auc_warm
        and cat_share >= check["min_cat_split_share"])
    ln = gbdt.learner
    expect = mix["expect"]
    path = {"learner": type(ln).__name__,
            "num_shards": int(getattr(ln, "num_shards", 1)),
            "fused_block_hits": int(counters["fused.block_hits"]),
            "compiles_in_window": int(compiles_in_window)}
    # counted when a grow loop is traced, so in set-up
    path.update({k: bool(tel.counters.get(c, 0) > route0[k])
                 for k, c in ROUTE_COUNTERS.items()})
    path["ok"] = bool(
        all(path[k] == expect[k] for k in expect)
        and path["fused_block_hits"] == steps
        and compiles_in_window == 0)
    ref = _check_against_reference(lgb, ds, params, check)
    ctx.info("check_full_size", **full)
    ctx.info("check_path", expect=expect, **path)
    ctx.info("check_reference", **ref)

    def tree_facts(tree):
        return dict(_tree_facts(tree), cat_splits=_cat_splits(tree))

    trace = tracer.trace if tracer is not None else None
    facts = {
        "kind": "train", "rows": rows, "features": features,
        "block": block, "chips": ctx.cell.chips, "steps": steps,
        "window_s": window_s, "counters": counters,
        "trees_in_window": block * steps,
        "rate_untraced_mrow_iters_per_s": rate_untraced,
        "dataset_construct_s": ds_info["seconds"],
        "traced_trees": [
            tree_facts(models[warm_trees + i * block + j])
            for i in traced for j in range(block)],
        "trace": trace, "device_kind": ctx.device["kind"],
    }
    ctx.info("window", steps=steps, measured_steps=measured,
             trees=block * steps, measured_s=round(window_s, 3),
             step_s=[round(float(d), 3) for d in durations],
             s_per_tree=round(window_s / (block * measured), 4),
             mrow_iters_per_s=round(rate, 4),
             # a utilisation, printed beside the rate
             hbm_floor_share_pct=train_hbm_floor_share.read(facts),
             counters=counters)
    return {
        "correct": bool(full["ok"] and path["ok"] and ref["ok"]),
        "attempted": steps,
        "failed": 0 if finite else steps,
        "end_to_end": {"train_mrow_iters_per_s": rate},
        "facts": facts,
    }
