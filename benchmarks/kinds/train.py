"""Training cells: boosting steps on a table, for the window's length.

One general runner for every ``"kind": "train"`` mix. The mix file
gives the rows, the trees per timed step (``block``), the learner's
parameters and the path the cell is about (``expect``); the
configuration file gives the table's shape, the parameters passed to
the program verbatim, the data generator and the tolerances.

A **step** is what ``lightgbm_tpu.engine.train``'s fast path calls,
``booster._gbdt.train(iter + block)``, followed by a fetch of one
element of the training score as the barrier. Set-up trains one
iteration (the program's first iteration takes a path of its own) and
one step, so the window's only program is compiled; the window then
repeats steps until ``--seconds`` have passed. The rate is taken over
the window's first ``measure_steps`` steps (all of them, if fewer were
completed): later trees cost less than earlier ones, so a rate over
however many steps happened to fit would move with the step count, and
a fixed amount of work does not.

Correctness, outside the window:

(a) against the plain reference at a size it can hold: the cell's own
    path (same learner, parameters and chips) and
    ``benchmarks/reference/gbdt_numpy.py`` each train ``check.trees``
    trees on the first ``check.rows`` rows of the cell's binned table;
    their in-sample AUC and log-loss must agree within the tolerances
    the configuration states;
(b) on the window's own model at full size: every tree has more than
    one leaf, training never stopped early, scores are finite, and the
    in-sample AUC on the first ``check.auc_rows`` rows is at least
    ``check.min_auc`` and no lower than after warm-up, computed from
    the scores the booster already holds;
(c) the path is the one the cell is about: learner class, shards,
    megakernel on or off as the trace-time counter says, one fused
    block per step, and nothing compiled inside the window.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from .. import stats
from ..datacache import binned_dataset
from ..layers import train_hbm_floor_share
from ..spec import load_module
from ..tracing import TraceWindow

WINDOW_COUNTERS = ("host.dispatches", "host.syncs", "fused.block_hits",
                   "learner.trees", "learner.row_iters")


def _tree_facts(tree) -> Dict[str, Any]:
    """Leaves and per-split row counts of one host tree: the rows each
    split partitions and the rows of its smaller child."""
    n = max(int(tree.num_leaves) - 1, 0)
    internal = np.asarray(tree.internal_count[:n], np.float64)
    leaf = np.asarray(tree.leaf_count, np.float64)

    def child_rows(child):
        return np.where(child >= 0, internal[np.maximum(child, 0)],
                        leaf[np.maximum(~child, 0)])
    left = child_rows(np.asarray(tree.left_child[:n]))
    right = child_rows(np.asarray(tree.right_child[:n]))
    return {"leaves": int(tree.num_leaves),
            "split_rows": internal.tolist(),
            "smaller_child_rows": np.minimum(left, right).tolist()}


def _score_head(gbdt, rows: int) -> np.ndarray:
    return np.asarray(gbdt.train_score[:rows, 0], np.float64)


def _check_against_reference(lgb, ds, params, check) -> Dict[str, Any]:
    """(a): the cell's path and the plain reference on the first
    ``check.rows`` rows."""
    from ..reference import gbdt_numpy
    rows = min(int(check["rows"]), ds._inner.num_data)
    trees = int(check["trees"])
    t0 = time.perf_counter()
    sub = ds.subset(np.arange(rows)).construct()
    small = lgb.Booster(dict(params), sub)
    small._gbdt.train(1)
    small._gbdt.train(trees)
    got = _score_head(small._gbdt, rows)
    t1 = time.perf_counter()
    inner = sub._inner
    labels = np.asarray(inner.metadata.label)
    want = gbdt_numpy.train(inner.binned, inner.num_bins_array(), labels,
                            params, trees)
    out = {"rows": rows, "trees": trees,
           "auc": stats.auc(labels, got),
           "auc_reference": stats.auc(labels, want),
           "logloss": stats.logloss(labels, got),
           "logloss_reference": stats.logloss(labels, want),
           "learner": type(small._gbdt.learner).__name__,
           "program_s": round(t1 - t0, 2),
           "reference_s": round(time.perf_counter() - t1, 2)}
    out["ok"] = bool(
        np.isfinite(got).all()
        and len(small._gbdt.models) == trees
        and abs(out["auc"] - out["auc_reference"]) <= check["auc_tol"]
        and abs(out["logloss"] - out["logloss_reference"])
        <= check["logloss_tol"])
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

    tel = get_telemetry()
    tel.ensure_ring()               # counters only, no sink
    megakernel0 = tel.counters.get("learner.megakernel_traces", 0)
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
    ctx.info("dataset", **{k: (round(v, 3) if isinstance(v, float) else v)
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
    full = {"trees": len(models), "min_leaves": min(leaves),
            "auc_warm": round(auc_warm, 6), "auc_end": round(auc_end, 6)}
    full["ok"] = bool(
        finite and len(models) == warm_trees + block * steps
        and min(leaves) > 1 and auc_end >= check["min_auc"]
        and auc_end >= auc_warm)
    ln = gbdt.learner
    expect = mix["expect"]
    path = {"learner": type(ln).__name__,
            "num_shards": int(getattr(ln, "num_shards", 1)),
            # counted when a grow loop is traced, so in set-up
            "megakernel": bool(tel.counters.get(
                "learner.megakernel_traces", 0) > megakernel0),
            "fused_block_hits": int(counters["fused.block_hits"]),
            "compiles_in_window": int(compiles_in_window)}
    path["ok"] = bool(
        all(path[k] == expect[k] for k in expect)
        and path["fused_block_hits"] == steps
        and compiles_in_window == 0)
    ref = _check_against_reference(lgb, ds, params, check)
    ctx.info("check_full_size", **full)
    ctx.info("check_path", expect=expect, **path)
    ctx.info("check_reference", **ref)

    trace = tracer.trace if tracer is not None else None
    facts = {
        "kind": "train", "rows": rows, "features": features,
        "block": block, "chips": ctx.cell.chips, "steps": steps,
        "window_s": window_s, "counters": counters,
        "trees_in_window": block * steps,
        "rate_untraced_mrow_iters_per_s": rate_untraced,
        "dataset_construct_s": ds_info["seconds"],
        "traced_trees": [
            _tree_facts(models[warm_trees + i * block + j])
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
