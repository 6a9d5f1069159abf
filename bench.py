"""Benchmark: Higgs-like binary GBDT training throughput on one chip.

Prints ONE JSON line per successful measurement; the LAST line is the
headline result (the driver parses the last valid JSON line).

Baseline: the reference's published Higgs run — 10.5M rows x 28 features,
500 iterations, num_leaves=255, lr=0.1 in 238.505 s on 2x E5-2670v3
(docs/Experiments.rst:103-117) = 22.01M row-iterations/second. We measure
the same quantity (rows * boosting-iterations / wall-clock second) on a
synthetic Higgs-shaped problem and vs_baseline = our_throughput / 22.01e6
(>1 means faster than the reference CPU run).

Fail-fast strategy (round-4 redesign): sizes ESCALATE smallest-first
(500k -> 2M -> 10.5M). The 500k attempt gets a short timeout so a valid
JSON line exists within minutes even on a cold cache; each larger size
only runs if wall budget remains (BENCH_BUDGET_S, default 1500 s total).
Every success prints immediately, so a timeout or OOM at a larger size
never erases the smaller-size number. BENCH_ROWS pins a single size.
"""

import json
import os
import subprocess
import sys
import time

BASELINE_ROW_ITERS_PER_S = 10_500_000 * 500 / 238.505

# ---------------------------------------------------------------------
# fixed-config CPU baseline (ROADMAP item 5): ONE pinned configuration,
# measured steady-state (warmup absorbs every compile), so the CPU
# number is comparable round over round. The r02->r05 history mixed
# 2-iteration micro-runs at drifting shapes and was pure noise.
# Changing ANY of these constants requires bumping the config id.
CPU_BASELINE = {"rows": 50_000, "features": 28, "leaves": 63,
                "iters": 10}
CPU_BASELINE_ID = "cpu-fixed-v1-50k-28f-63l-10it"
CPU_BASELINE_TIMEOUT_S = 420

# linear-tree convergence probe (ROADMAP item 4): iterations for
# linear_tree=true to reach the constant-leaf model's validation loss
# on dense numeric regression, recorded in the bench JSON
LINEAR_CONV_TIMEOUT_S = 300

# >=100-iteration fixed-config quality gate (VERDICT r5 weak #5):
# quality_ok now means "within `tolerance` AUC of the committed
# baseline accuracy at matched params" (BENCH_QUALITY_BASELINE.json),
# not the old 3-iteration sanity floor. Changing iters/shape requires
# a new id + re-committed baseline.
QUALITY_GATE = {"iters": 100, "tolerance": 0.002}
QUALITY_GATE_ID = "cpu-fixed-quality-v1-50k-28f-63l-100it"
QUALITY_BASELINE_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "BENCH_QUALITY_BASELINE.json")
QUALITY_TIMEOUT_S = 900

# compiled-HLO dispatch census (tools/hlo_census.py): per-split op
# count of the grow programs, gated against the committed budget and
# chained round-over-round by tools/bench_trend.py
CENSUS_TIMEOUT_S = 240

# multiboost sweep dryrun (tools/multiboost_dryrun.py): a 16-model
# hyperparameter sweep trained as ONE compiled program vs the
# train-in-a-loop foil — byte-identity + dispatch-budget checked, and
# the wall speedup chained round-over-round by tools/bench_trend.py.
# Changing the shape changes the trend key (the chain breaks cleanly).
MULTIBOOST_SWEEP = {"models": 16, "rows": 2048, "features": 16,
                    "iters": 10}
MULTIBOOST_TIMEOUT_S = 420

# mesh-scaling block (ROADMAP item 2): 1 -> 8 virtual-device scaling
# curve of steady-state time/split for every mesh learner mode on the
# CPU backend — a structural cost of the partition-rule layer's
# collective recipes (learner/comm.py), trend-gated round over round
# by tools/bench_trend.py. Changing the shape requires a new id.
MESH_SCALING = {"rows": 8192, "features": 16, "leaves": 15, "trees": 2}
MESH_SCALING_ID = "mesh-scaling-v1-8192r-16f-15l"
MESH_SCALING_DEVICES = (1, 2, 4, 8)
MESH_SCALING_MODES = ("data", "feature", "voting", "partitioned")
MESH_SCALING_TIMEOUT_S = 600

# escalation order: smallest first so SOME number prints fast
ROWS_PLAN = [500_000, 2_000_000, 10_500_000]
# per-size child timeout caps (seconds); the first must cover one cold
# compile (~20-40 s) plus data gen + a few iterations with slack
SIZE_TIMEOUT = {500_000: 600, 2_000_000: 900, 10_500_000: 1800}
# minimum remaining budget worth STARTING a size at (data gen + compile
# + measurement floor) — below this a child is guaranteed to be killed
# mid-run, wasting the budget tail
SIZE_MIN_BUDGET = {500_000: 60, 2_000_000: 180, 10_500_000: 420}


def measure():
    import numpy as np

    n = int(os.environ.get("BENCH_ROWS", ROWS_PLAN[0]))
    f = int(os.environ.get("BENCH_FEATURES", 28))
    num_leaves = int(os.environ.get("BENCH_LEAVES", 255))
    iters = int(os.environ.get("BENCH_ITERS",
                               3 if n > 2_000_000 else 8))
    # warmup mirrors the measured phase: its first iteration goes
    # through the sync boost-from-average path, so warmup = iters + 1
    # leaves the SAME power-of-2 fused-block ladder for both phases and
    # the timed region never contains a compile even on a cold cache
    warmup = int(os.environ.get("BENCH_WARMUP_ITERS", iters + 1))

    import jax

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.models.gbdt import GBDT

    rng = np.random.RandomState(42)
    X = rng.randn(n, f).astype(np.float32)

    def c(i):
        return X[:, i % f]   # modulo: BENCH_FEATURES may be < 7

    logit = (2.0 * c(0) - 1.5 * c(1) + c(2) * c(3)
             + 0.8 * c(4) * c(5) - c(6))
    y = (logit + rng.randn(n).astype(np.float32) > 0).astype(np.float32)

    cfg = Config.from_params({
        "objective": "binary", "num_leaves": num_leaves,
        "learning_rate": 0.1, "max_bin": 255, "metric": "",
        "verbosity": -1})
    # ring-only telemetry: counters (compile time, trees) with no sink
    # I/O in the timed region; LGBM_TPU_TELEMETRY additionally writes
    # the JSONL trace next to the JSON result (set by the parent)
    from lightgbm_tpu.observability.telemetry import get_telemetry
    tel = get_telemetry()
    tel.ensure_started(cfg)  # JSONL sink when LGBM_TPU_TELEMETRY is set
    tel.ensure_ring()        # else ring-only counters (no sink I/O)
    # persistent compile cache BEFORE the first compile (binning jits),
    # placed by utils/compile_cache.py; a second identical run then
    # reloads the serialized executables instead of recompiling
    from lightgbm_tpu.utils.compile_cache import maybe_enable_compile_cache
    cache_dir = maybe_enable_compile_cache()
    ds = Dataset.from_numpy(X, cfg, label=y)
    booster = GBDT(cfg, ds)

    from lightgbm_tpu.utils.sync import fetch_one

    def sync():
        # fetch ONE score element as the real barrier (utils/sync.py)
        return fetch_one(booster.train_score[:1])

    t_w0 = time.perf_counter()
    booster.train(warmup)  # compile sync (iter 0) + async paths
    sync()
    warmup_dt = time.perf_counter() - t_w0
    compile_at_warmup = tel.compile_stats()

    t0 = time.perf_counter()
    booster.train(warmup + iters)
    sync()
    dt = time.perf_counter() - t0

    compile_total = tel.compile_stats()
    throughput = n * iters / dt
    result = {
        "metric": "higgs_like_train_throughput",
        "value": round(throughput / 1e6, 4),
        "unit": "Mrow-iters/s",
        "vs_baseline": round(throughput / BASELINE_ROW_ITERS_PER_S, 4),
        "rows": n,
        "num_leaves": num_leaves,
        "iters": iters,
        "backend": jax.default_backend(),
        # compile-vs-steady-state provenance (observability layer): the
        # warmup absorbs compiles; steady_s is the timed region and
        # compile_in_timed_s must be ~0 for an honest throughput number
        "warmup_s": round(warmup_dt, 3),
        "steady_s": round(dt, 3),
        "compile_count": compile_total["count"],
        "compile_s": round(compile_total["seconds"], 3),
        "compile_in_timed_s": round(
            compile_total["seconds"] - compile_at_warmup["seconds"], 3),
        # persistent-cache provenance: a warmed second run shows
        # cache_hits > 0 and compile_s collapsing toward deserialize
        # cost (docs/Performance.md)
        "compile_cache": cache_dir or "",
        "compile_cache_hits": int(compile_total.get("cache_hits", 0))}
    # roofline normalization (lightgbm_tpu/utils/roofline.py): the
    # headline rate as a fraction of the device's HBM peak under the
    # documented lower-bound byte model. A CPU run (BENCH_ALLOW_CPU,
    # the test hook) has no peak and carries no roofline block
    if jax.default_backend() != "cpu":
        from lightgbm_tpu.utils.roofline import bench_roofline
        result["roofline"] = bench_roofline(throughput, f)
    # per-phase wall-time decomposition for the trend gate's
    # REGRESSION ATTRIBUTION (tools/bench_trend.py): phase span totals
    # when the host-stepped spans ran (the fused driver has none: its
    # device time by scope comes from a profile). Shares (not absolute
    # seconds) are what the gate compares across rounds.
    phases = tel.phase_totals()
    if phases:
        result["phases"] = {k: round(v, 6)
                            for k, v in sorted(phases.items())}
    if os.environ.get("BENCH_EVAL", "1") != "0":
        # training-quality gate, DEFAULT-ON (Experiments.rst:120-148
        # accuracy table analog): in-sample AUC on a bounded slice so a
        # throughput headline that trains garbage cannot parse as
        # success. The throughput line prints either way (honest
        # record); an eval CRASH also fails the gate — an unchecked
        # number must not parse as a pass
        try:
            from types import SimpleNamespace

            from lightgbm_tpu.metric.metrics import AUCMetric
            m = min(n, 500_000)
            pred = np.asarray(booster.predict_raw(X[:m]),
                              np.float64).ravel()
            m_auc = AUCMetric(cfg)
            m_auc.init(SimpleNamespace(label=y[:m], weights=None), m)
            result["auc"] = round(float(m_auc.eval(pred, None)[0]), 5)
            result["auc_iters"] = warmup + iters
            min_auc = float(os.environ.get("BENCH_MIN_AUC", 0.80))
            result["quality_ok"] = bool(result["auc"] >= min_auc)
        except Exception as e:  # noqa: BLE001
            result["auc_error"] = str(e)[:200]
            result["quality_ok"] = False
    if os.environ.get("BENCH_SERVING", "1") != "0":
        # inference-side headline (lightgbm_tpu/serving/): a short
        # closed-loop hammer on the just-trained booster through the
        # compiled bucketed path — p50/p95/p99 latency, throughput and
        # bucket hit rate ride the same JSON line. Failures are
        # recorded, never fatal: the training headline must survive.
        try:
            from lightgbm_tpu.serving import ServingConfig, ServingEngine
            from lightgbm_tpu.serving.loadgen import serving_block
            eng = ServingEngine(
                booster, config=ServingConfig(
                    buckets=(1, 64, 256), device="always"))
            result["serving"] = serving_block(
                eng, X[:4096], batch_sizes=(1, 64),
                threads=int(os.environ.get("BENCH_SERVING_THREADS", 2)),
                duration_s=float(os.environ.get("BENCH_SERVING_S", 2)))
            eng.stop()
        except Exception as e:  # noqa: BLE001
            result["serving_error"] = str(e)[:200]
    if os.environ.get("BENCH_FLEET", "1") != "0":
        # fleet-serving headline (serving/fleet.py): a short open-loop
        # soak through a 2-replica, 2-named-model pool — the
        # p99/throughput/shed-rate trajectory tools/bench_trend.py
        # chains round-over-round. Same booster under both names keeps
        # the block cheap (shared compiled programs, shared device
        # arrays are NOT shared across versions — pinning is measured
        # too). Failures are recorded, never fatal.
        try:
            from lightgbm_tpu.serving import FleetEngine, ServingConfig
            from lightgbm_tpu.serving.loadgen import soak_loop
            fl = FleetEngine(
                models={"base": booster, "variant": booster},
                config=ServingConfig(buckets=(1, 64, 256),
                                     device="always"),
                replicas=2, default_model="base")
            blk = soak_loop(
                fl, X[:4096], batch_sizes=(1, 64),
                models=["base", "variant"],
                duration_s=float(os.environ.get("BENCH_FLEET_S", 2)),
                qps=float(os.environ.get("BENCH_FLEET_QPS", 150)))
            blk["backend"] = result["backend"]
            result["fleet"] = blk
            fl.stop()
        except Exception as e:  # noqa: BLE001
            result["fleet_error"] = str(e)[:200]
    if os.environ.get("BENCH_FLEET_ISOLATION", "1") != "0":
        # process- vs thread-mode serving cost (serving/procfleet.py):
        # same pool shape and host route in both modes, so the delta
        # IS the isolation bill (socket + JSON framing + supervisor),
        # plus the restart-to-ready latency of a killed worker. The
        # process p99 chains as the gated fleet_isolation_p99_ms
        # bench_trend series. Failures recorded, never fatal.
        try:
            result["fleet_isolation"] = measure_fleet_isolation(
                booster, X[:2048])
        except Exception as e:  # noqa: BLE001
            result["fleet_isolation_error"] = str(e)[:200]
    if os.environ.get("BENCH_OBS_OVERHEAD", "1") != "0":
        # the observability plane's serving cost: process-fleet p99
        # with metrics federation on vs off (identical pool/load both
        # ways, so the delta IS the piggyback bill: delta building in
        # the worker pong + merge on the parent). Must stay within
        # trend-gate noise — a tracked series from day one.
        try:
            result["obs_overhead"] = measure_obs_overhead(
                booster, X[:2048])
        except Exception as e:  # noqa: BLE001
            result["obs_overhead_error"] = str(e)[:200]
    tel.flush()
    print(json.dumps(result))


def measure_fleet_isolation(booster, X):
    """Thread vs process fleet p99 + restart-to-ready (item 4b)."""
    import os
    import signal
    import time as _time

    from lightgbm_tpu.serving import (FleetEngine, ProcFleetOptions,
                                      ServingConfig)
    from lightgbm_tpu.serving.loadgen import soak_loop
    dur = float(os.environ.get("BENCH_FLEET_ISO_S", 2))
    qps = float(os.environ.get("BENCH_FLEET_ISO_QPS", 120))
    cfg = ServingConfig(buckets=(1, 64), device="never",
                        flush_interval_ms=1.0)
    out = {"duration_s": dur, "offered_qps": qps,
           "replicas": 2, "buckets": [1, 64]}
    for mode in ("thread", "process"):
        fl = FleetEngine(models={"base": booster}, config=cfg,
                         replicas=2, default_model="base",
                         isolation=mode,
                         proc_opts=ProcFleetOptions(restart_max=3))
        try:
            blk = soak_loop(fl, X, duration_s=dur, qps=qps,
                            batch_sizes=(1, 8), models=["base"],
                            timeout_ms=20000)
            out[f"{mode}_p50_ms"] = blk["p50_ms"]
            out[f"{mode}_p99_ms"] = blk["p99_ms"]
            out[f"{mode}_throughput_rps"] = blk["throughput_rps"]
            out[f"{mode}_availability"] = blk["availability"]
            if mode == "process":
                # restart-to-ready: SIGKILL one worker, wait for the
                # supervisor to respawn it warm
                victim = fl.replicas[0]
                os.kill(victim.pid, signal.SIGKILL)
                deadline = _time.monotonic() + 60.0
                while _time.monotonic() < deadline \
                        and victim.state != "ok":
                    _time.sleep(0.05)
                out["restart_ready_ms"] = victim.restart_ready_ms \
                    if victim.state == "ok" else None
                out["restart_state"] = victim.state
        finally:
            fl.stop()
    if out.get("thread_p99_ms") and out.get("process_p99_ms"):
        out["process_overhead_pct"] = round(
            100.0 * (out["process_p99_ms"] / out["thread_p99_ms"]
                     - 1.0), 1)
    out.update(measure_aot_serving(booster, X))
    if out.get("restart_ready_ms") and out.get("aot_restart_ready_ms"):
        # how much of the host-route respawn bill the AOT artifact
        # replay saves (positive = AOT respawns faster)
        out["aot_restart_improvement_pct"] = round(
            100.0 * (1.0 - out["aot_restart_ready_ms"]
                     / out["restart_ready_ms"]), 1)
    return out


def measure_aot_serving(booster, X):
    """The zero-Python hot path legs of the fleet_isolation block:

    * AOT column — a process fleet serving an AOT-published model on
      the device route (replayed executables, zero retraces):
      soak p50/p99 + the gated ``single_row_p99_ms`` series from a
      sequential single-row loop, plus the warm AOT respawn cost
      (``aot_restart_ready_ms``, vs the host-route respawn above);
    * shm vs JSON transport — the same large-batch loop through the
      shm ring and through ProcFleetOptions(shm=False); the delta is
      the JSON encode/decode bill (``shm_speedup_pct``, gated via
      the shm leg attribution in tools/bench_trend.py).
    """
    import os
    import signal
    import time as _time

    import numpy as np

    from lightgbm_tpu.serving import (FleetEngine, ProcFleetOptions,
                                      ServingConfig)
    from lightgbm_tpu.serving.loadgen import soak_loop
    dur = float(os.environ.get("BENCH_FLEET_ISO_S", 2))
    qps = float(os.environ.get("BENCH_FLEET_ISO_QPS", 120))
    text = booster.model_to_string()
    big = X[:512] if len(X) >= 512 else X
    out = {"aot_batch_rows": int(len(big))}

    def _timed_loop(fl, data, budget_s):
        lats, deadline = [], _time.monotonic() + budget_s
        while _time.monotonic() < deadline:
            t0 = _time.perf_counter()
            fl.predict(data, timeout_ms=20000)
            lats.append((_time.perf_counter() - t0) * 1000.0)
        return lats

    def _pcts(prefix, lats):
        if not lats:
            return {}
        arr = np.asarray(lats)
        return {f"{prefix}_p50_ms": round(float(np.percentile(arr, 50)), 3),
                f"{prefix}_p99_ms": round(float(np.percentile(arr, 99)), 3),
                f"{prefix}_calls": len(lats)}

    for transport in ("shm", "json"):
        fl = FleetEngine(
            config=ServingConfig(buckets=(1, 64, 1024),
                                 device="always",
                                 flush_interval_ms=1.0,
                                 request_timeout_ms=20000),
            replicas=1, default_model="base", isolation="process",
            proc_opts=ProcFleetOptions(restart_max=3,
                                       shm=(transport == "shm"),
                                       shm_min_bytes=4096))
        try:
            fl.load_model("base", text, aot_booster=booster)
            rep = fl._proc_supervisor._replicas[0]
            if transport == "shm":
                out["aot_route"] = bool(rep.aot_models.get("base"))
                blk = soak_loop(fl, X, duration_s=dur, qps=qps,
                                batch_sizes=(1, 64), models=["base"],
                                timeout_ms=20000)
                out["aot_p50_ms"] = blk["p50_ms"]
                out["aot_p99_ms"] = blk["p99_ms"]
                out["aot_throughput_rps"] = blk["throughput_rps"]
                out["aot_availability"] = blk["availability"]
                # the gated single-row cost model series: sequential
                # closed-loop single rows = pure per-call floor
                out.update(_pcts("single_row", _timed_loop(
                    fl, X[:1], min(dur, 2.0))))
            out.update(_pcts(f"{transport}_large_batch", _timed_loop(
                fl, big, min(dur, 2.0))))
            if transport == "shm":
                shm = rep.describe().get("shm") or {}
                out["shm_writes"] = shm.get("writes")
                # AOT respawn: artifact + executables replay from the
                # persistent cache — compare with the host-route
                # restart_ready_ms of the process leg above
                os.kill(rep.pid, signal.SIGKILL)
                deadline = _time.monotonic() + 60.0
                while _time.monotonic() < deadline \
                        and rep.state != "ok":
                    _time.sleep(0.05)
                out["aot_restart_ready_ms"] = rep.restart_ready_ms \
                    if rep.state == "ok" else None
                out["aot_restart_compiles"] = rep.cold_start_compiles
        finally:
            fl.stop()
    if out.get("shm_large_batch_p99_ms") \
            and out.get("json_large_batch_p99_ms"):
        out["shm_speedup_pct"] = round(
            100.0 * (out["json_large_batch_p99_ms"]
                     / out["shm_large_batch_p99_ms"] - 1.0), 1)
    return out


def measure_obs_overhead(booster, X):
    """Serving p99 with metrics federation on vs off (ISSUE 16
    satellite): same process-mode pool and offered load both ways,
    the only difference is ProcFleetOptions.federation (worker-side
    delta building + parent-side merge_snapshot on every heartbeat).
    Also records how many federated series the parent scrape held at
    the end of the ON run — zero series would mean the overhead
    number measured nothing."""
    import os

    from lightgbm_tpu.observability.metrics import get_metrics
    from lightgbm_tpu.serving import (FleetEngine, ProcFleetOptions,
                                      ServingConfig)
    from lightgbm_tpu.serving.loadgen import soak_loop
    dur = float(os.environ.get("BENCH_OBS_OVERHEAD_S", 2))
    qps = float(os.environ.get("BENCH_OBS_OVERHEAD_QPS", 120))
    cfg = ServingConfig(buckets=(1, 64), device="never",
                        flush_interval_ms=1.0)
    out = {"duration_s": dur, "offered_qps": qps, "replicas": 2,
           "heartbeat_ms": 50.0}
    for fed in (True, False):
        key = "fed_on" if fed else "fed_off"
        fl = FleetEngine(models={"base": booster}, config=cfg,
                         replicas=2, default_model="base",
                         isolation="process",
                         proc_opts=ProcFleetOptions(
                             restart_max=3, heartbeat_ms=50.0,
                             federation=fed))
        try:
            blk = soak_loop(fl, X, duration_s=dur, qps=qps,
                            batch_sizes=(1, 8), models=["base"],
                            timeout_ms=20000)
            out[f"{key}_p50_ms"] = blk["p50_ms"]
            out[f"{key}_p99_ms"] = blk["p99_ms"]
            out[f"{key}_throughput_rps"] = blk["throughput_rps"]
            if fed:
                out["federated_series"] = sum(
                    w.get("series", 0) for w in
                    get_metrics().federation_workers())
        finally:
            fl.stop()
            for w in get_metrics().federation_workers():
                get_metrics().drop_worker(w["worker"])
    if out.get("fed_off_p99_ms") and out.get("fed_on_p99_ms"):
        out["federation_overhead_pct"] = round(
            100.0 * (out["fed_on_p99_ms"] / out["fed_off_p99_ms"]
                     - 1.0), 1)
    return out


def measure_linear():
    """Linear-vs-constant convergence on dense synthetic regression
    (the ISSUE-6 acceptance metric): train a constant-leaf model for
    ``iters`` rounds, then count how many rounds ``linear_tree=true``
    needs to reach (<=) its final validation l2. Prints one JSON line
    with the iteration ratio."""
    import numpy as np

    n = int(os.environ.get("BENCH_LINEAR_ROWS", 20_000))
    f = int(os.environ.get("BENCH_LINEAR_FEATURES", 10))
    iters = int(os.environ.get("BENCH_LINEAR_ITERS", 40))
    leaves = int(os.environ.get("BENCH_LINEAR_LEAVES", 15))

    rng = np.random.RandomState(9)
    X = rng.randn(n, f)
    y = (3.0 * X[:, 0] + 2.0 * X[:, 1] - 1.5 * X[:, 2]
         + 0.5 * X[:, 3] * X[:, 4] + 0.1 * rng.randn(n))
    cut = int(n * 0.8)

    import lightgbm_tpu as lgb
    from lightgbm_tpu.callback import record_evaluation

    def run(linear: bool):
        params = {"objective": "regression", "num_leaves": leaves,
                  "learning_rate": 0.1, "metric": "l2",
                  "verbosity": -1}
        if linear:
            params.update(linear_tree=True, linear_lambda=0.01)
        hist = {}
        lgb.train(params, lgb.Dataset(X[:cut], label=y[:cut]),
                  num_boost_round=iters,
                  valid_sets=[lgb.Dataset(X[cut:], label=y[cut:])],
                  valid_names=["valid"], verbose_eval=False,
                  callbacks=[record_evaluation(hist)])
        return hist["valid"]["l2"]

    const_curve = run(False)
    linear_curve = run(True)
    target = const_curve[-1]
    match_iter = next((i + 1 for i, v in enumerate(linear_curve)
                       if v <= target), None)
    result = {
        "metric": "linear_tree_convergence",
        "rows": n, "features": f, "num_leaves": leaves,
        "const_iters": iters,
        "const_valid_l2": round(float(target), 6),
        "linear_iters_to_match": match_iter,
        "linear_final_l2": round(float(linear_curve[-1]), 6),
        "iter_ratio": round(match_iter / iters, 4)
        if match_iter else None,
        # acceptance bar: linear leaves reach the constant model's
        # valid loss in <= 0.7x the iterations on dense numeric data
        "meets_0p7_bar": bool(match_iter is not None
                              and match_iter <= 0.7 * iters)}
    print(json.dumps(result))


def measure_mesh_scaling():
    """Mesh-learner scaling curve on the virtual CPU mesh: for each
    parallel mode and device count, steady-state wall time per split
    (one warmup tree absorbs the compile). The parent child-process
    runs this under ``--xla_force_host_platform_device_count=8`` so
    meshes of 1/2/4/8 shards all carve out of the same 8 virtual
    devices. ``value`` is the 8-device total across modes (lower is
    better — the number the trend gate chains); the full per-mode
    curve rides the ``mesh_scaling`` block."""
    import time as _time

    import numpy as np

    n = int(os.environ.get("BENCH_MESH_ROWS", MESH_SCALING["rows"]))
    f = int(os.environ.get("BENCH_MESH_FEATURES",
                           MESH_SCALING["features"]))
    leaves = int(os.environ.get("BENCH_MESH_LEAVES",
                                MESH_SCALING["leaves"]))
    trees = int(os.environ.get("BENCH_MESH_TREES",
                               MESH_SCALING["trees"]))

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import Dataset
    from lightgbm_tpu.parallel.learners import (
        DataParallelTreeLearner, FeatureParallelTreeLearner,
        MeshPartitionedTreeLearner, VotingParallelTreeLearner)
    from lightgbm_tpu.parallel.partition_rules import default_mesh

    rng = np.random.RandomState(17)
    x = rng.randn(n, f).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + 0.3 * rng.randn(n) > 0) \
        .astype(np.float32)
    cfg = Config.from_params({"objective": "binary",
                              "num_leaves": leaves,
                              "min_data_in_leaf": 20,
                              "verbosity": -1})
    ds = Dataset.from_numpy(x, cfg, label=y)
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((n,), 0.25, jnp.float32)
    splits = leaves - 1

    def make(mode, nd):
        mesh = default_mesh(nd)
        if mode == "data":
            return DataParallelTreeLearner(ds, cfg, mesh=mesh)
        if mode == "feature":
            return FeatureParallelTreeLearner(ds, cfg, mesh=mesh)
        if mode == "voting":
            return VotingParallelTreeLearner(ds, cfg, mesh=mesh)
        return MeshPartitionedTreeLearner(ds, cfg, mesh=mesh,
                                          mode="data", interpret=True)

    devices = [d for d in MESH_SCALING_DEVICES
               if d <= jax.device_count()]
    modes: dict = {}
    errors: dict = {}
    for mode in MESH_SCALING_MODES:
        curve = {}
        for nd in devices:
            try:
                lrn = make(mode, nd)
                res = lrn.train(grad, hess)       # warmup + compile
                jax.block_until_ready(res.tree.num_leaves)
                t0 = _time.perf_counter()
                for _ in range(trees):
                    res = lrn.train(grad, hess)
                jax.block_until_ready(res.tree.num_leaves)
                dt = (_time.perf_counter() - t0) / trees
                curve[str(nd)] = round(dt / splits * 1e3, 4)
            except Exception as e:  # noqa: BLE001 - record, keep going
                errors[f"{mode}@{nd}"] = str(e)[:160]
        if curve:
            modes[mode] = curve
    top = [m[str(devices[-1])] for m in modes.values()
           if str(devices[-1]) in m]
    result = {
        "metric": "mesh_scaling",
        "value": round(sum(top), 4) if top else None,
        "unit": "ms/split (sum over modes, max devices)",
        "backend": jax.default_backend(),
        "baseline_config": MESH_SCALING_ID,
        "mesh_scaling": {
            "devices": devices,
            "rows": n, "features": f, "leaves": leaves,
            "modes": modes,
            # scaling efficiency: 1-device time / max-device time
            "speedup": {
                m: round(c[str(devices[0])] / c[str(devices[-1])], 3)
                for m, c in modes.items()
                if str(devices[0]) in c and str(devices[-1]) in c
                and c[str(devices[-1])] > 0},
        },
    }
    if errors:
        result["mesh_scaling"]["errors"] = errors
    print(json.dumps(result))


def run_mesh_scaling_block(env, remaining):
    """Run the mesh-scaling child on the CPU backend with the 8-device
    virtual mesh. Prints its JSON line and returns it."""
    if os.environ.get("BENCH_NO_MESH") or remaining < 120:
        return None
    envc = _cpu_env(env)
    envc.pop("_BENCH_CHILD", None)
    envc["_BENCH_CHILD_MESH"] = "1"
    flags = envc.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        envc["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=envc,
            capture_output=True, text=True,
            timeout=max(120.0, min(MESH_SCALING_TIMEOUT_S, remaining)))
    except subprocess.TimeoutExpired:
        sys.stderr.write("mesh-scaling child timed out\n")
        return None
    parsed = find_result_line(proc.stdout)
    if parsed is None:
        sys.stderr.write("mesh-scaling child failed:\n"
                         + proc.stderr[-2000:] + "\n")
        return None
    print(json.dumps(parsed), flush=True)
    return parsed


def _classify_probe(detail: str) -> str:
    """Structured probe-failure reason code (tools/probe_taxonomy.py:
    no_device / init_timeout / compile_error / transport / unknown);
    falls back to 'unknown' when the taxonomy module is unreachable
    (the classification must never break the stdlib-only parent)."""
    try:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from tools.probe_taxonomy import classify_probe_failure
        return classify_probe_failure(detail)
    except Exception:  # noqa: BLE001 - taxonomy is best-effort
        return "unknown"


def emit_probe_telemetry(ok: bool, detail: str, dur_s: float) -> None:
    """Record the accelerator-probe verdict in the telemetry JSONL
    trace (kind=probe + a probe.fail counter record on failure), with
    the failure classified into a structured ``reason_code`` (the raw
    cause stays attached as ``reason``). Written with stdlib file
    appends on purpose: the bench PARENT never imports jax or
    lightgbm_tpu (see main())."""
    path = os.environ.get("LGBM_TPU_TELEMETRY", "").strip()
    if not path:
        return
    code = None if ok else _classify_probe(detail)
    recs = [{"kind": "probe", "t": 0.0, "verdict":
             "ok" if ok else "failed", "reason": detail[:300],
             "reason_code": code,
             "dur_s": round(float(dur_s), 3), "wall_time": time.time()}]
    if not ok:
        recs.append({"kind": "counter", "t": 0.0, "name": "probe.fail",
                     "value": 1, "reason_code": code})
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)),
                    exist_ok=True)
        with open(path, "a") as fh:
            for r in recs:
                fh.write(json.dumps(r) + "\n")
    except OSError as e:
        sys.stderr.write(f"probe telemetry write failed: {e}\n")


def find_result_line(stdout: str):
    """Locate and parse the last JSON result line in bench output."""
    found = None
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{") and '"metric"' in line:
            try:
                found = json.loads(line)
            except json.JSONDecodeError:
                continue
    return found


def _run_child(env, rows, timeout):
    env["BENCH_ROWS"] = str(rows)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired as e:
        return None, ("timeout", str(e.stdout)[-2000:], str(e.stderr)[-2000:])
    parsed = find_result_line(proc.stdout)
    if parsed is not None:
        return parsed, None
    return None, (proc.returncode, proc.stdout[-2000:], proc.stderr[-2000:])


def _cpu_env(env):
    """Child env forced onto the CPU backend."""
    envc = dict(env)
    envc["JAX_PLATFORMS"] = "cpu"
    flags = envc.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:  # see tests/conftest.py
        envc["XLA_FLAGS"] = (flags + " --xla_cpu_max_isa=AVX2").strip()
    return envc


def _fixed_cpu_child_env(env):
    """The ONE pinned CPU configuration (CPU_BASELINE/CPU_BASELINE_ID):
    steady-state iterations with warmup absorbing every compile."""
    envc = _cpu_env(env)
    envc["BENCH_FEATURES"] = str(CPU_BASELINE["features"])
    envc["BENCH_LEAVES"] = str(CPU_BASELINE["leaves"])
    envc["BENCH_ITERS"] = str(CPU_BASELINE["iters"])
    envc["BENCH_WARMUP_ITERS"] = str(CPU_BASELINE["iters"] + 1)
    envc["BENCH_SERVING"] = "0"       # training throughput only
    envc["BENCH_FLEET"] = "0"
    envc["BENCH_MIN_AUC"] = os.environ.get("BENCH_BASELINE_MIN_AUC",
                                           "0.75")
    return envc


def run_cpu_baseline(env, remaining, dispatches=None):
    """Measure the fixed-config steady-state CPU baseline; prints its
    JSON line (metric cpu_fixed_baseline_throughput, carrying the
    census-derived dispatches_per_split when available) and returns
    it."""
    if os.environ.get("BENCH_NO_CPU_BASELINE") or remaining < 120:
        return None
    envc = _fixed_cpu_child_env(env)
    timeout = max(120.0, min(CPU_BASELINE_TIMEOUT_S, remaining))
    parsed, err = _run_child(envc, CPU_BASELINE["rows"], timeout)
    if parsed is None:
        sys.stderr.write(f"cpu fixed baseline failed: {err}\n")
        return None
    parsed["metric"] = "cpu_fixed_baseline_throughput"
    parsed["baseline_config"] = CPU_BASELINE_ID
    if dispatches is not None:
        parsed["dispatches_per_split"] = dispatches
    print(json.dumps(parsed), flush=True)
    return parsed


def run_linear_convergence(env, remaining):
    """Run the linear-vs-constant convergence child; prints its JSON
    line (metric linear_tree_convergence) and returns it."""
    if os.environ.get("BENCH_NO_LINEAR") or remaining < 90:
        return None
    envc = _cpu_env(env)
    envc.pop("_BENCH_CHILD", None)
    envc["_BENCH_CHILD_LINEAR"] = "1"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=envc,
            capture_output=True, text=True,
            timeout=max(90.0, min(LINEAR_CONV_TIMEOUT_S, remaining)))
    except subprocess.TimeoutExpired:
        sys.stderr.write("linear convergence child timed out\n")
        return None
    parsed = find_result_line(proc.stdout)
    if parsed is None:
        sys.stderr.write("linear convergence child failed:\n"
                         + proc.stderr[-2000:] + "\n")
        return None
    print(json.dumps(parsed), flush=True)
    return parsed


def run_dispatch_census(env, remaining):
    """Compiled-HLO dispatch census (tools/hlo_census.py) on the CPU
    backend: one JSON line (metric dispatches_per_split; value = the
    serial grow program's per-split op count — the program the fixed
    CPU baseline trains with) plus the committed-budget verdict. Runs
    at tiny shapes: the while-body op census is shape-independent
    (asserted by tests/test_split_fusion.py)."""
    if os.environ.get("BENCH_NO_CENSUS") or remaining < 60:
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    art = os.path.join(here, "bench_census.json")
    # a stale artifact from an earlier run must never be mistaken for
    # this run's measurement (the child may crash before writing)
    try:
        os.remove(art)
    except OSError:
        pass
    envc = _cpu_env(env)
    envc.pop("_BENCH_CHILD", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "tools.hlo_census", "--check",
             "--json", art, "--rows", "512", "--features", "8",
             "--leaves", "15"],
            env=envc, capture_output=True, text=True, cwd=here,
            timeout=max(60.0, min(CENSUS_TIMEOUT_S, remaining)))
    except subprocess.TimeoutExpired:
        sys.stderr.write("hlo census timed out\n")
        return None
    try:
        with open(art) as fh:
            census = json.load(fh)
    except OSError:
        sys.stderr.write("hlo census child failed (no artifact):\n"
                         + proc.stderr[-2000:] + "\n")
        return None
    progs = census.get("programs", {})
    result = {
        "metric": "dispatches_per_split",
        "value": progs.get("serial_grow", {}).get("ops_per_split"),
        "unit": "hlo-ops/split",
        "baseline_config": CPU_BASELINE_ID,
        "budget_ok": proc.returncode == 0,
        "split_fusion": census.get("config", {}).get("split_fusion"),
        "programs": {n: {"ops_per_split": p.get("ops_per_split"),
                         "carry_arrays": p.get("carry_arrays"),
                         "carry_bytes": p.get("carry_bytes")}
                     for n, p in progs.items()},
    }
    print(json.dumps(result), flush=True)
    if proc.returncode != 0:
        sys.stderr.write("DISPATCH CENSUS over budget (see "
                         "tools/hlo_census_budget.json):\n"
                         + proc.stdout[-1500:] + "\n")
    return result


def run_multiboost_sweep(env, remaining):
    """Multiboost sweep dryrun (tools/multiboost_dryrun.py) on the CPU
    backend: trains the MULTIBOOST_SWEEP 16-model sweep once through
    engine.train_many (every boosting iteration = ONE jitted grow
    dispatch for the whole sweep) and once as a per-model train loop,
    then prints one JSON line (metric multiboost_speedup; value = loop
    wall seconds / batched wall seconds). The child exits non-zero if
    any model is not byte-identical to its loop twin, any model
    silently fell back to the loop, or the batched dispatch count
    exceeds foil/8 — that verdict rides the line as ``ok``."""
    if os.environ.get("BENCH_NO_MULTIBOOST") or remaining < 90:
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    art = os.path.join(here, "bench_multiboost.json")
    # a stale artifact from an earlier run must never be mistaken for
    # this run's measurement (the child may crash before writing)
    try:
        os.remove(art)
    except OSError:
        pass
    envc = _cpu_env(env)
    envc.pop("_BENCH_CHILD", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "tools.multiboost_dryrun",
             "--json", art,
             "--models", str(MULTIBOOST_SWEEP["models"]),
             "--rows", str(MULTIBOOST_SWEEP["rows"]),
             "--features", str(MULTIBOOST_SWEEP["features"]),
             "--iters", str(MULTIBOOST_SWEEP["iters"])],
            env=envc, capture_output=True, text=True, cwd=here,
            timeout=max(90.0, min(MULTIBOOST_TIMEOUT_S, remaining)))
    except subprocess.TimeoutExpired:
        sys.stderr.write("multiboost sweep timed out\n")
        return None
    try:
        with open(art) as fh:
            result = json.load(fh)
    except OSError:
        sys.stderr.write("multiboost sweep child failed "
                         "(no artifact):\n"
                         + proc.stderr[-2000:] + "\n")
        return None
    print(json.dumps(result), flush=True)
    if proc.returncode != 0:
        sys.stderr.write("MULTIBOOST SWEEP contract failed (byte "
                         "identity / batching / dispatch budget):\n"
                         + proc.stderr[-1500:] + "\n")
    return result


def run_quality_gate(env, remaining):
    """The >=100-iteration fixed-config accuracy gate: same generator
    and params as the CPU fixed baseline, QUALITY_GATE['iters']
    boosting rounds, quality_ok = AUC within QUALITY_GATE['tolerance']
    of the committed BENCH_QUALITY_BASELINE.json accuracy."""
    if os.environ.get("BENCH_NO_QUALITY") or remaining < 240:
        return None
    try:
        with open(QUALITY_BASELINE_FILE) as fh:
            base = json.load(fh)
    except OSError:
        sys.stderr.write("no committed quality baseline "
                         f"({QUALITY_BASELINE_FILE}); skipping the "
                         "quality gate\n")
        return None
    envc = _cpu_env(env)
    envc["BENCH_FEATURES"] = str(CPU_BASELINE["features"])
    envc["BENCH_LEAVES"] = str(CPU_BASELINE["leaves"])
    envc["BENCH_ITERS"] = str(QUALITY_GATE["iters"])
    envc["BENCH_WARMUP_ITERS"] = "1"
    envc["BENCH_SERVING"] = "0"
    envc["BENCH_FLEET"] = "0"
    min_auc = float(base["auc"]) - QUALITY_GATE["tolerance"]
    envc["BENCH_MIN_AUC"] = repr(min_auc)
    parsed, err = _run_child(
        envc, CPU_BASELINE["rows"],
        max(240.0, min(QUALITY_TIMEOUT_S, remaining)))
    if parsed is None:
        sys.stderr.write(f"quality gate child failed: {err}\n")
        return None
    parsed["metric"] = "cpu_fixed_quality_gate"
    parsed["baseline_config"] = QUALITY_GATE_ID
    parsed["auc_baseline"] = float(base["auc"])
    parsed["auc_tolerance"] = QUALITY_GATE["tolerance"]
    print(json.dumps(parsed), flush=True)
    return parsed


def main():
    if os.environ.get("_BENCH_CHILD") == "1":
        measure()
        return
    if os.environ.get("_BENCH_CHILD_LINEAR") == "1":
        measure_linear()
        return
    if os.environ.get("_BENCH_CHILD_MESH") == "1":
        measure_mesh_scaling()
        return
    # This parent stays off JAX: a chip belongs to one process at a
    # time, so a parent that had touched JAX would hold the chip and
    # every measuring child would fail or hang at start-up. All device
    # work happens in children, one at a time.
    budget = float(os.environ.get("BENCH_BUDGET_S", 1500))
    t_start = time.monotonic()
    env = dict(os.environ)
    env["_BENCH_CHILD"] = "1"
    # telemetry JSONL next to the JSON result (appended across sizes;
    # run_start records delimit children) unless the caller disabled it
    if not os.environ.get("BENCH_NO_TELEMETRY"):
        env.setdefault("LGBM_TPU_TELEMETRY", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "bench_telemetry.jsonl"))

    # One probe child, first: no accelerator means a non-zero exit
    # before anything is measured, and nothing is ever printed under
    # the device metric's name from a CPU run. BENCH_ALLOW_CPU=1 is
    # the test hook that lets CI drive main() on a forced-CPU backend
    # (its lines say "backend": "cpu").
    probe_src = "import jax; d = jax.devices(); print(d)"
    if not os.environ.get("BENCH_ALLOW_CPU"):
        probe_src += "; assert d and d[0].platform != 'cpu', d"
    t_probe0 = time.monotonic()
    try:
        probe = subprocess.run(
            [sys.executable, "-c", probe_src], env=env,
            capture_output=True, text=True,
            timeout=float(os.environ.get("BENCH_PROBE_TIMEOUT_S", 90)))
        tpu_ok = probe.returncode == 0
        detail = (probe.stdout if tpu_ok else probe.stderr)[-300:]
    except subprocess.TimeoutExpired as e:
        tpu_ok, detail = False, f"hung > {e.timeout:.0f}s"
    emit_probe_telemetry(tpu_ok, detail, time.monotonic() - t_probe0)
    if not tpu_ok:
        sys.stderr.write(
            "bench: no accelerator answered the probe "
            f"(reason_code={_classify_probe(detail)}): "
            f"{detail.strip()[-200:]}\n")
        sys.exit(1)

    pinned = os.environ.get("BENCH_ROWS")
    plan = [int(pinned)] if pinned is not None else list(ROWS_PLAN)
    last_err = None
    printed_any = False
    quality_fail = False

    # fixed-config CPU blocks (forced onto the CPU backend; counts and
    # structural costs, never device metrics). Pinned single-size runs
    # (BENCH_ROWS) skip them.
    if pinned is None:
        # dispatch census first (cheap, feeds the baseline line)
        census_parsed = run_dispatch_census(
            env, budget - (time.monotonic() - t_start))
        run_cpu_baseline(
            env, budget - (time.monotonic() - t_start),
            dispatches=(census_parsed or {}).get("value"))
        run_linear_convergence(
            env, budget - (time.monotonic() - t_start))
        run_mesh_scaling_block(
            env, budget - (time.monotonic() - t_start))
        run_multiboost_sweep(
            env, budget - (time.monotonic() - t_start))
        qp = run_quality_gate(
            env, budget - (time.monotonic() - t_start))
        if qp is not None and qp.get("quality_ok") is False:
            quality_fail = True

    for rows in plan:
        remaining = budget - (time.monotonic() - t_start)
        if printed_any and remaining < SIZE_MIN_BUDGET.get(rows, 60):
            break  # keep what we have; don't start a run we can't finish
        # pinned single-size runs (BENCH_ROWS) get the whole
        # budget; the per-size caps only shape the escalation plan
        cap = budget if pinned is not None else SIZE_TIMEOUT.get(rows, 1800)
        parsed, last_err = _run_child(
            env, rows, max(60.0, min(cap, remaining)))
        if parsed is None:
            break  # a size failed; larger sizes would fail harder
        print(json.dumps(parsed), flush=True)
        printed_any = True
        if parsed.get("quality_ok") is False:
            quality_fail = True

    if not printed_any:
        e = last_err or ("?", "", "")
        sys.stderr.write(
            f"bench failed; last rc={e[0]}\nstdout:\n{e[1]}\nstderr:\n{e[2]}\n")
        sys.exit(1)
    if quality_fail:
        # the throughput lines were printed (honest record) but a
        # garbage-training run must be LOUD, not parse as success
        sys.stderr.write("QUALITY GATE FAILED: an auc fell below "
                         "BENCH_MIN_AUC; see quality_ok fields\n")
        sys.exit(3)


if __name__ == "__main__":
    main()
