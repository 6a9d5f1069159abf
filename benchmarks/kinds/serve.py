"""Serving cells: an open loop of prediction requests against one
``ServingEngine``, for the window's length.

One general runner for every ``"kind": "serve"`` mix. The mix file
gives the rate, the arrival process, the request sizes, the pool the
rows are drawn from, the time-out and the engine's settings; the
configuration gives the served model's size. The model is drawn from
the seed by ``generators/leafwise_forest.py`` and installed, as the
program's own ``Tree`` objects, in a booster built on the pool's
binned ``Dataset`` (the device route needs the bin mappers, so a model
loaded from text would be served from the host); nothing is trained,
so no trainer change moves these cells.

Correctness, outside the window: every request came back finite and
by the device route, nothing was shed, timed out or fell back to the
host, no bucket was compiled in the window, and a seeded sample of
replies from every size class agrees with the plain reference
(``reference/forest_numpy.py``, float64, value space) within the
float32 accumulation bound the configuration states.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

import numpy as np

from .. import stats
from ..openloop import Schedule, drive, make_schedule, mean_rows
from ..spec import load_module
from ..tracing import TraceWindow


def _program_trees(forest, inner):
    """The generated forest as the program's host trees, bound to the
    pool's ``Dataset`` (bin mappers, column layout)."""
    from lightgbm_tpu.models.tree import Tree, TreeArrays
    from lightgbm_tpu.ops.split import MAX_CAT_WORDS
    t_n, nodes = forest["split_feature"].shape
    rows = float(inner.num_data)
    zeros_n = np.zeros(nodes)
    trees = []
    for t in range(t_n):
        share = forest["leaf_share"][t]
        trees.append(Tree(TreeArrays(
            num_leaves=nodes + 1,
            split_feature=forest["split_feature"][t],
            threshold_bin=forest["threshold_bin"][t],
            decision_type=np.zeros(nodes, np.int32),
            left_child=forest["left_child"][t],
            right_child=forest["right_child"][t],
            split_gain=np.ones(nodes), internal_value=zeros_n,
            internal_weight=forest["node_share"][t] * rows,
            internal_count=forest["node_share"][t] * rows,
            leaf_value=forest["leaf_value"][t],
            leaf_weight=share * rows, leaf_count=share * rows,
            leaf_parent=forest["leaf_parent"][t],
            leaf_depth=forest["leaf_depth"][t],
            cat_bitsets=np.zeros((nodes, MAX_CAT_WORDS), np.uint32)),
            dataset=inner))
    return trees


def _threshold_values(forest, inner) -> np.ndarray:
    """Each node's threshold as a feature value: the upper bound of
    its bin in the pool's mappers."""
    bounds = [np.asarray(inner.feature_mapper(f).bin_upper_bound,
                         np.float64) for f in range(inner.num_features)]
    feat, thr = forest["split_feature"], forest["threshold_bin"]
    out = np.empty(feat.shape, np.float64)
    for f, ub in enumerate(bounds):
        pick = feat == f
        out[pick] = ub[thr[pick]]
    return out


class _Server:
    """The engine with the pool, as the load generator sees it."""

    def __init__(self, engine, pool: np.ndarray, sched: Schedule,
                 timeout_ms: float):
        self.engine, self.pool, self.sched = engine, pool, sched
        self.timeout_ms = timeout_ms

    def submit(self, i: int):
        lo = int(self.sched.offset[i])
        return self.engine.submit(self.pool[lo:lo + int(self.sched.rows[i])],
                                  timeout_ms=self.timeout_ms)

    def wait(self, fut):
        reply = np.asarray(fut.result(timeout=self.timeout_ms / 1e3 + 30.0))
        meta = fut.meta
        ok = bool(np.isfinite(reply).all()
                  and meta.get("route") == "device")
        return ok, (reply, meta.get("queue_ms"), meta.get("compute_ms"),
                    meta.get("batch_rows"), meta.get("route"))


def _build(ctx):
    """Pool, model and engine: everything before the window."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import ServingConfig, ServingEngine

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    params = dict(cfg["params"])
    features = int(cfg["features"])
    gen_spec = cfg["generator"]
    gen = load_module("generators", gen_spec["name"])
    t0 = time.perf_counter()
    pool, pool_y = gen.make(ctx.seed, int(mix["pool_rows"]), features,
                            **gen_spec.get("params", {}))
    ds = lgb.Dataset(pool, label=pool_y, params=params).construct()
    inner = ds._inner
    bst = lgb.Booster(params, ds)
    t1 = time.perf_counter()
    model = cfg["served_model"]
    forest_gen = load_module("generators", model["generator"])
    forest = forest_gen.make(ctx.seed, int(model["trees"]),
                             int(params["num_leaves"]),
                             inner.num_bins_array(),
                             float(model["leaf_scale"]))
    bst._gbdt.init_from_models(_program_trees(forest, inner))
    t2 = time.perf_counter()
    eng_cfg = dict(mix["engine"])
    for key in ("buckets", "warmup_kinds"):
        if key in eng_cfg:
            eng_cfg[key] = tuple(eng_cfg[key])
    engine = ServingEngine(bst, ServingConfig(**eng_cfg))
    mv = engine.registry.current()
    if not (mv.device_ready and mv.stacked is not None):
        raise RuntimeError("the registry declined to pin the model on "
                           "the device")
    ctx.info("serve_setup", pool_s=round(t1 - t0, 2),
             model_s=round(t2 - t1, 2),
             engine_warm_s=round(time.perf_counter() - t2, 2),
             trees=len(bst._gbdt.models),
             max_depth=int(forest["leaf_depth"].max()),
             mean_leaf_depth=round(float(
                 (forest["leaf_depth"] * forest["leaf_share"]).sum(1)
                 .mean()), 2),
             stacked_mb=round(mv.stacked.nbytes() / 1e6, 2),
             buckets=list(engine.config.buckets))
    return pool, forest, inner, engine


def _window(ctx, engine, pool, seconds: float, rate_rps: float,
            tracer=None) -> Dict[str, Any]:
    """One open-loop window at ``rate_rps``; the engine's counters are
    read before and after."""
    mix = ctx.cell.traffic
    sched = make_schedule(ctx.seed, seconds, rate_rps, mix["arrival"],
                          mix["sizes"], len(pool))
    server = _Server(engine, pool, sched, float(mix["timeout_ms"]))
    before = engine.stats()
    traced: Dict[str, Any] = {}
    helper = None
    if tracer is not None:
        # a thread of its own opens and closes the profiler, so that
        # neither the sender nor the collector waits for it
        at = min(float(mix["trace"]["at_s"]), 0.25 * seconds)
        length = min(float(mix["trace"]["for_s"]), 0.5 * seconds)

        def trace_part():
            time.sleep(at)
            traced["batches0"] = engine.stats()["batches"]
            tracer.start()
            time.sleep(length)
            traced["batches1"] = engine.stats()["batches"]
            tracer.stop()
        helper = threading.Thread(target=trace_part, name="bench-tracer")
    compiles0 = ctx.compiles.compiles
    if helper is not None:
        helper.start()
    out = drive(sched, server.submit, server.wait)
    if helper is not None:
        helper.join()
    after = engine.stats()
    delta = {k: after[k] - before[k] for k in
             ("requests", "batches", "shed", "timeouts", "fallbacks",
              "errors", "bucket_misses")}
    return {"sched": sched, "out": out, "stats": delta,
            "compiles": ctx.compiles.compiles - compiles0,
            "traced_batches": traced.get("batches1", 0)
            - traced.get("batches0", 0)}


def _summary(w: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    out, sched = w["out"], w["sched"]
    lat = out.latency_ms
    span = max(seconds, float(np.nanmax(out.done_s))) if len(lat) \
        else seconds
    rows_done = int(sched.rows[out.ok].sum())
    third = len(lat) // 3
    return {"sent": len(sched.due_s), "completed": int(out.ok.sum()),
            "rows_completed": rows_done, "span_s": span,
            "p50_ms": stats.percentile(lat, 50) if len(lat) else None,
            "p99_ms": stats.percentile(lat, 99) if len(lat) else None,
            "krows_per_s": rows_done / span / 1e3,
            # a queue that grows shows as a last third slower than the
            # first
            "p50_first_third_ms": stats.percentile(lat[:third], 50)
            if third else None,
            "p50_last_third_ms": stats.percentile(lat[-third:], 50)
            if third else None,
            "late_p99_ms": stats.percentile(out.late_ms, 99)
            if len(sched.due_s) else None,
            "errors": dict(out.errors), "engine": w["stats"]}


def sweep(ctx) -> Dict[str, Any]:
    """Find the knee once: short windows at rising rates. Never run by
    the driver; the rate it finds is written into the mix file by
    hand, with this table in ``PERF.md``."""
    pool, _forest, _inner, engine = _build(ctx)
    spec = ctx.cell.traffic["sweep"]
    table: List[Dict[str, Any]] = []
    try:
        for rate in spec["rates_rps"]:
            w = _window(ctx, engine, pool, float(spec["seconds"]),
                        float(rate))
            row = dict(rate_rps=rate,
                       **_summary(w, float(spec["seconds"])))
            row["offered_krows_per_s"] = \
                float(w["sched"].rows.sum()) / float(spec["seconds"]) / 1e3
            table.append(row)
            ctx.info("sweep", **row)
            time.sleep(1.0)             # let the queue drain
    finally:
        engine.stop()
    return {"table": table}


def run(ctx) -> Dict[str, Any]:
    from ..reference import forest_numpy
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    pool, forest, inner, engine = _build(ctx)
    tracer = TraceWindow(ctx) if ctx.trace else None
    try:
        ctx.start_window()
        w = _window(ctx, engine, pool, ctx.seconds,
                    float(mix["rate_rps"]), tracer)
    finally:
        engine.stop()
    out, sched = w["out"], w["sched"]
    s = _summary(w, ctx.seconds)
    ctx.info("window", rate_rps=mix["rate_rps"],
             mean_rows_per_request=round(mean_rows(mix["sizes"]), 3), **s)

    # ---- correctness, outside the window -------------------------------
    check = cfg["check"]
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 0xc4ec]))
    classes = np.unique(sched.klass)
    per_class = -(-int(check["serve_sample"]) // len(classes))
    sample: List[int] = []
    for k in classes:
        idx = np.flatnonzero(out.ok & (sched.klass == k))
        sample += list(rng.choice(idx, min(per_class, len(idx)),
                                  replace=False))
    x = np.concatenate([pool[sched.offset[i]:sched.offset[i]
                             + sched.rows[i]] for i in sample]) \
        if sample else np.zeros((0, pool.shape[1]))
    got = np.concatenate([out.replies[i][0] for i in sample]) \
        if sample else np.zeros(0)
    raw = forest_numpy.predict_raw(forest, _threshold_values(forest, inner), x)
    want = forest_numpy.sigmoid(raw)
    trees = len(forest["split_feature"])
    # T float32 additions of values below max|raw| round by at most
    # T * eps * max|raw| in all, and |d sigmoid| <= |d raw| / 4
    bound = trees * float(np.finfo(np.float32).eps) \
        * max(float(np.abs(raw).max(initial=0.0)), 1.0)
    diff = float(np.abs(got - want).max(initial=0.0))
    answers = {"sampled_requests": len(sample), "sampled_rows": len(x),
               "classes_covered": len(classes), "max_abs_diff": diff,
               "bound": bound}
    answers["ok"] = bool(sample and diff <= bound)
    failed = int(len(sched.due_s) - out.ok.sum())
    enough = stats.enough_for(int(out.ok.sum()), 99)
    served = {"failed": failed, "engine": w["stats"],
              "compiles_in_window": w["compiles"],
              "enough_for_p99": enough}
    served["ok"] = bool(
        failed == 0 and enough and w["compiles"] == 0
        and all(w["stats"][k] == 0 for k in
                ("shed", "timeouts", "fallbacks", "errors",
                 "bucket_misses")))
    ctx.info("check_answers", **answers)
    ctx.info("check_served", **served)

    done = [r for r, ok in zip(out.replies, out.ok) if ok]
    return {
        "correct": bool(answers["ok"] and served["ok"]),
        "attempted": len(sched.due_s),
        "failed": failed,
        "end_to_end": {"serve_p50_ms": s["p50_ms"],
                       "serve_p99_ms": s["p99_ms"],
                       "serve_krows_per_s": s["krows_per_s"]},
        "facts": {
            "kind": "serve", "chips": ctx.cell.chips,
            "queue_ms": [r[1] for r in done],
            "compute_ms": [r[2] for r in done],
            "batch_rows": [r[3] for r in done],
            "late_ms": out.late_ms.tolist(),
            "batches": w["stats"]["batches"],
            "traced_batches": w["traced_batches"],
            "trace": tracer.trace if tracer is not None else None,
            "device_kind": ctx.device["kind"],
        },
    }
