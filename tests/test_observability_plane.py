"""Live observability plane (ISSUE 7): metrics, flight recorder, trend.

Acceptance gates:
  * ``GET /metrics`` returns valid Prometheus text exposition
    (grammar-checked below) including the serving latency histogram
    with p50/p95/p99-derivable buckets, and scrape load causes ZERO
    steady-state recompiles and no implicit device->host transfers;
  * the fault drill (nan_grad under rollback + sigterm preemption via
    the PR 4 harness) produces an atomic flight-recorder dump carrying
    the faulting iteration's records, counter totals and the config
    fingerprint;
  * ``tools/bench_trend.py`` exits 0 on the committed BENCH_r01..r05
    series and nonzero on a synthetic >20% fixed-baseline regression.
"""

import importlib.util
import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability.flightrec import (arm_recorder,
                                                  disarm_recorder,
                                                  resolve_dump_path)
from lightgbm_tpu.observability.metrics import (LogHistogram,
                                                get_metrics,
                                                maybe_start_exporter,
                                                metrics_text,
                                                start_exporter,
                                                stop_exporter)
from lightgbm_tpu.observability.telemetry import get_telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tel():
    t = get_telemetry()
    t.reset()
    get_metrics().reset()
    yield t
    t.reset()
    get_metrics().reset()
    stop_exporter()


def _toy(n=500, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def model():
    X, y = _toy()
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=5)
    return bst, X


# ---------------------------------------------------------------------
# Prometheus text-format grammar checker (exposition format 0.0.4)
_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"'
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(?:\{{({_LABEL}(?:,{_LABEL})*)?\}})? "
    r"([-+]?(?:[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|Inf)|NaN)"
    r"( [0-9]+)?$")
_TYPES = ("counter", "gauge", "histogram", "summary", "untyped")


def validate_prometheus(text):
    """Assert every line of ``text`` is grammatical; returns
    {sample_name: value} (last value per name+labels wins) and the
    {name: type} table."""
    samples = {}
    types = {}
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.split("\n"):
        if not line:
            continue
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert re.fullmatch(_NAME, name), line
        elif line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4 and parts[3] in _TYPES, line
            assert re.fullmatch(_NAME, parts[2]), line
            assert parts[2] not in types, f"duplicate TYPE: {line}"
            types[parts[2]] = parts[3]
        elif line.startswith("#"):
            continue
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"bad sample line: {line!r}"
            samples[(m.group(1), m.group(2) or "")] = float(
                m.group(3).replace("Inf", "inf"))
    # every sample belongs to a declared metric family
    for (name, _labels) in samples:
        base = re.sub(r"_(bucket|sum|count|min|max|total)$", "", name)
        assert name in types or base in types \
            or name.removesuffix("_total") in types, \
            f"sample {name} has no TYPE declaration"
    return samples, types


def _hist_series(samples, base):
    """{labels_without_le: [(le, cum_count), ...]} for one histogram."""
    out = {}
    for (name, labels), v in samples.items():
        if name != f"{base}_bucket":
            continue
        pairs = dict(p.split("=", 1) for p in labels.split(",")) \
            if labels else {}
        le = pairs.pop("le").strip('"')
        key = tuple(sorted(pairs.items()))
        out.setdefault(key, []).append(
            (float("inf") if le == "+Inf" else float(le), v))
    for series in out.values():
        series.sort()
    return out


# ---------------------------------------------------------------------
def test_log_histogram_quantiles_derivable():
    h = LogHistogram(start=0.05, factor=2 ** 0.5, n=50)
    rng = np.random.RandomState(0)
    vals = rng.lognormal(mean=2.0, sigma=0.8, size=2000)
    for v in vals:
        h.observe(v)
    assert h.count == 2000
    assert h.sum == pytest.approx(float(vals.sum()), rel=1e-9)
    for q in (0.5, 0.95, 0.99):
        est = h.quantile(q)
        true = float(np.percentile(vals, q * 100))
        # the estimate must land within one geometric bucket of truth
        assert true / 2 ** 0.5 <= est <= true * 2 ** 0.5, \
            (q, est, true)
    assert LogHistogram(1.0, 2.0, 4).quantile(0.5) is None  # empty


def test_counters_and_observe_are_thread_safe(tel):
    tel.configure(summary=False)
    n_threads, n_iter = 8, 500

    def worker():
        for _ in range(n_iter):
            tel.count("t.count", 1)
            tel.count_iter("t.iter", 1)
            tel.observe("t.obs", 1.0)
    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = n_threads * n_iter
    # without the lock these read-modify-writes lose updates
    assert tel.counters["t.count"] == total
    assert tel.counters["t.iter"] == total
    assert tel.dists["t.obs"][0] == total
    assert tel.dists["t.obs"][1] == pytest.approx(float(total))


def test_jsonl_sink_flushes_boundary_records(tel, tmp_path):
    """run_start/train_end flush immediately — a reader (or a crash)
    right after the record sees it on disk without an explicit
    flush()."""
    path = str(tmp_path / "t.jsonl")
    tel.configure(jsonl_path=path, summary=False)
    tel.record("iter", iter=0)          # buffered is fine
    tel.record("train_end", iters=1)    # boundary: must hit the disk
    with open(path) as fh:
        kinds = [json.loads(ln)["kind"] for ln in fh if ln.strip()]
    assert "train_end" in kinds
    # the atexit hook is installed exactly once
    from lightgbm_tpu.observability import telemetry as tmod
    assert tmod._ATEXIT_INSTALLED[0]


# ---------------------------------------------------------------------
def test_metrics_render_is_valid_prometheus(tel):
    from lightgbm_tpu.serving import ServingConfig, ServingEngine
    tel.ensure_ring()
    X, y = _toy(400)
    # stepped loop (valid set) -> end_iteration feeds the
    # train_phase_seconds histogram
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1, "metric": "binary_logloss"},
                    lgb.Dataset(X, label=y), num_boost_round=3,
                    valid_sets=[lgb.Dataset(X[:80], label=y[:80])],
                    verbose_eval=False)
    eng = ServingEngine(bst, config=ServingConfig(
        buckets=(4, 16), flush_interval_ms=1.0))
    try:
        for n in (1, 5, 16):
            eng.predict(X[:n])
            eng.predict(X[:n], kind="raw_score")
        text = metrics_text()
    finally:
        eng.stop()
    samples, types = validate_prometheus(text)
    assert types["lgbm_serving_request_latency_ms"] == "histogram"
    assert types["lgbm_train_phase_seconds"] == "histogram"
    assert any(n == "lgbm_serving_queue_depth" for n, _l in samples)
    assert any(n == "lgbm_serving_requests" for n, _l in samples)
    # histogram buckets: cumulative, +Inf-terminated, count-consistent
    series = _hist_series(samples, "lgbm_serving_request_latency_ms")
    assert series, "no serving latency buckets rendered"
    for key, pairs in series.items():
        les = [le for le, _ in pairs]
        cums = [c for _, c in pairs]
        assert les[-1] == float("inf")
        assert cums == sorted(cums), (key, cums)
        labels = dict(key)
        assert "bucket" in labels and "kind" in labels
        count_key = ("lgbm_serving_request_latency_ms_count",
                     ",".join(f"{k}={v}" for k, v in key))
        assert samples[count_key] == cums[-1]


def _unescape_label(v):
    out, i = [], 0
    while i < len(v):
        if v[i] == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            out.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
            i += 2
        else:
            out.append(v[i])
            i += 1
    return "".join(out)


def test_hostile_label_values_escape_conformant(tel):
    """Escaping conformance (exposition format 0.0.4): label values
    containing backslashes, double quotes and newlines must render as
    \\\\, \\" and \\n — single-character grammar check is not enough,
    the ROUND-TRIP must recover the original value exactly."""
    reg = get_metrics()
    hostile = ['back\\slash', 'quo"te', 'new\nline',
               'every\\"\nkind', '\\n literal', 'trailing\\']
    for i, v in enumerate(hostile):
        reg.set_gauge("pipeline_stage", float(i), labels={"stage": v})
    # hostile values arriving over the federation socket render the
    # same way (worker shards go through the same escaper)
    reg.merge_snapshot("w9", {"gauges": [
        {"n": "fleet_replica_state", "l": {"rid": 'r"\\\n0'},
         "v": 2.0}]})
    text = metrics_text()
    samples, _ = validate_prometheus(text)   # grammar: every line parses
    label_re = re.compile(
        r'stage="((?:[^"\\\n]|\\\\|\\"|\\n)*)"')
    seen = set()
    for (name, labels) in samples:
        if name != "lgbm_pipeline_stage":
            continue
        m = label_re.search(labels)
        assert m, labels
        seen.add(_unescape_label(m.group(1)))
    assert seen == set(hostile)
    # the federated hostile value round-trips too
    fed = [l for (n, l) in samples
           if n == "lgbm_fleet_replica_state" and 'worker="w9"' in l]
    assert fed, text
    m = re.search(r'rid="((?:[^"\\\n]|\\\\|\\"|\\n)*)"', fed[0])
    assert m and _unescape_label(m.group(1)) == 'r"\\\n0'
    # raw control characters never leak into the exposition
    for line in text.split("\n"):
        assert "\r" not in line
    reg.drop_worker("w9")


def test_metrics_endpoint_under_load_zero_recompiles(tel, model,
                                                     monkeypatch):
    """Scrape ``GET /metrics`` on the serving frontend DURING a loadgen
    burst: every scrape is grammatical, steady-state traffic plus
    scraping triggers zero new XLA compiles, and rendering issues no
    implicit device->host transfer."""
    monkeypatch.setenv("LGBM_TPU_PREDICT_DEVICE_MIN_CELLS", "0")
    from lightgbm_tpu.serving import ServingConfig, ServingEngine
    from lightgbm_tpu.serving.http import make_http_server
    from lightgbm_tpu.serving.loadgen import closed_loop
    tel.ensure_ring()
    bst, X = model
    eng = ServingEngine(bst, config=ServingConfig(
        buckets=(1, 8, 64), device="always", flush_interval_ms=0.5))
    server = make_http_server(eng, "127.0.0.1", 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        # absorb warmup + first dispatches, then pin the compile count
        for n in (1, 7, 64):
            eng.predict(X[:n])
        compiles0 = tel.counters.get("jit.compiles", 0)

        scrapes = []
        stop = [False]

        def scraper():
            while not stop[0]:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=30) as r:
                    assert r.status == 200
                    assert r.headers["Content-Type"].startswith(
                        "text/plain")
                    scrapes.append(r.read().decode())
        st = threading.Thread(target=scraper, daemon=True)
        st.start()
        block = closed_loop(eng, X, batch_sizes=(1, 7, 64), threads=2,
                            duration_s=0.6)
        stop[0] = True
        st.join(10.0)
        assert block["requests"] > 0 and block["errors"] == 0
        assert len(scrapes) >= 2, "burst finished with <2 scrapes"
        for text in (scrapes[0], scrapes[-1]):
            samples, _types = validate_prometheus(text)
        assert tel.counters.get("jit.compiles", 0) == compiles0, \
            "scraping a serving process recompiled something"

        # the render itself must not fetch device data implicitly
        from tools.graftlint.runtime import no_implicit_host_transfers
        with no_implicit_host_transfers():
            text = metrics_text()
        samples, _types = validate_prometheus(text)
        # p50/p95/p99 are derivable from the live registry
        h = get_metrics().hist("serving_request_latency_ms",
                               {"kind": "predict", "bucket": 1})
        assert h.count > 0 and h.quantile(0.99) is not None
    finally:
        server.shutdown()
        server.server_close()
        eng.stop()


def test_exporter_serves_metrics(tel):
    tel.ensure_ring()
    tel.count("exporter.test", 3)
    server = start_exporter(0)
    port = server.server_address[1]
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            text = r.read().decode()
        samples, _ = validate_prometheus(text)
        assert samples[("lgbm_exporter_test_total", "")] == 3.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=30)
    finally:
        stop_exporter()


def test_maybe_start_exporter_config_and_env(tel, monkeypatch):
    from lightgbm_tpu.config import Config
    monkeypatch.delenv("LGBM_TPU_METRICS_PORT", raising=False)
    assert maybe_start_exporter(Config.from_params({})) is None
    monkeypatch.setenv("LGBM_TPU_METRICS_PORT", "not-a-port")
    assert maybe_start_exporter(None) is None
    with pytest.raises(ValueError):
        Config.from_params({"metrics_port": 99999})


# ---------------------------------------------------------------------
# crash flight recorder
def _drill_params(tmp_path, **extra):
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "metric": "binary_logloss",
         "checkpoint_dir": str(tmp_path / "ckpts"),
         "checkpoint_freq": 3, "guard_policy": "rollback",
         "telemetry_out": str(tmp_path / "trace.jsonl")}
    p.update(extra)
    return p


def test_flightrec_dump_path_resolution(tmp_path, monkeypatch):
    from lightgbm_tpu.config import Config
    monkeypatch.delenv("LGBM_TPU_CRASH_DUMP", raising=False)
    monkeypatch.delenv("LGBM_TPU_TELEMETRY", raising=False)
    assert resolve_dump_path(Config.from_params({})) is None
    cfg = Config.from_params({"telemetry_out": "/x/t.jsonl"})
    assert resolve_dump_path(cfg) == "/x/t.jsonl.crash.json"
    cfg = Config.from_params({"crash_dump": "/y/d.json"})
    assert resolve_dump_path(cfg) == "/y/d.json"
    monkeypatch.setenv("LGBM_TPU_CRASH_DUMP", "/z/env.json")
    assert resolve_dump_path(cfg) == "/z/env.json"


def test_fault_drill_nan_rollback_dumps_black_box(tel, tmp_path):
    """nan_grad under guard_policy=rollback (the PR 4 harness): the
    rollback RECOVERS the run, and the dump still captures the
    faulting iteration's records, counter totals and fingerprints."""
    from lightgbm_tpu.robustness.faults import set_fault_plan
    X, y = _toy(600, 8, seed=7)
    params = _drill_params(tmp_path, faults="nan_grad@iteration=7")
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=10,
                    valid_sets=[lgb.Dataset(X[:100], label=y[:100])],
                    verbose_eval=False)
    set_fault_plan(None)
    assert bst.num_trees() == 10   # rollback recovered
    dump_path = str(tmp_path / "trace.jsonl.crash.json")
    assert os.path.exists(dump_path)
    with open(dump_path) as fh:
        d = json.load(fh)
    assert d["flight_recorder"] == 1
    assert d["reason"] == "guard:nonfinite"
    assert d["counters"]["guard.nonfinite_iters"] >= 1
    assert d["counters"]["faults.nan_grad"] == 1
    assert d["config_fingerprint"] and d["bin_layout_fingerprint"]
    assert d["config"]["guard_policy"] == "rollback"
    # the faulting iteration's records are in the black box: the ring
    # holds everything up to the trip (iterations 0..6 completed)
    iters = {r["iter"] for r in d["records"]
             if r.get("kind") == "iter"}
    assert 6 in iters, sorted(iters)
    assert d["trips"] and d["trips"][0]["kind"] == "nonfinite"
    assert d["trips"][0]["iteration"] == 7
    # atomic write: no temp leftovers
    assert not [f for f in os.listdir(tmp_path)
                if f.endswith(".tmp")]


def test_fault_drill_sigterm_preemption_dumps(tel, tmp_path):
    """sigterm via the harness: the engine finishes the in-flight
    iteration, checkpoints, and the final dump (reason=preemption)
    atomically replaces the signal-time one."""
    from lightgbm_tpu.robustness.faults import set_fault_plan
    X, y = _toy(600, 8, seed=8)
    params = _drill_params(tmp_path, faults="sigterm@iteration=5")
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=12,
                    valid_sets=[lgb.Dataset(X[:100], label=y[:100])],
                    verbose_eval=False)
    set_fault_plan(None)
    assert getattr(bst, "preempted", False)
    with open(str(tmp_path / "trace.jsonl.crash.json")) as fh:
        d = json.load(fh)
    assert d["reason"] == "preemption"
    assert d["signum"] == 15
    assert d["counters"]["checkpoint.preemptions"] == 1
    assert d["checkpoint_dir"] == str(tmp_path / "ckpts")
    assert any(r.get("kind") == "iter" for r in d["records"])
    # the signal-time trip is preserved in the final dump
    assert any(t["kind"] == "signal" for t in d["trips"])


def test_uncaught_exception_dumps(tel, tmp_path):
    class Boom(RuntimeError):
        pass

    def bad_feval(preds, ds):
        raise Boom("feval exploded")
    X, y = _toy(400)
    params = _drill_params(tmp_path)
    with pytest.raises(Boom):
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5,
                  valid_sets=[lgb.Dataset(X[:80], label=y[:80])],
                  feval=bad_feval, verbose_eval=False)
    with open(str(tmp_path / "trace.jsonl.crash.json")) as fh:
        d = json.load(fh)
    assert d["reason"] == "exception"
    assert d["exception"]["type"] == "Boom"
    assert "feval exploded" in d["exception"]["message"]


def test_flightrec_disarm_ownership(tel, tmp_path):
    rec = arm_recorder(None, dump_path=str(tmp_path / "a.json"))
    assert rec is not None
    # a nested arm does not steal, and its disarm does not clear
    rec2 = arm_recorder(None, dump_path=str(tmp_path / "b.json"))
    assert rec2 is rec
    disarm_recorder(None)
    from lightgbm_tpu.observability.flightrec import active_recorder
    assert active_recorder() is rec
    disarm_recorder(rec)
    assert active_recorder() is None


# ---------------------------------------------------------------------
# bench trend gate
def _mk_round(path, n, lines):
    tail = "\n".join(json.dumps(ln) for ln in lines)
    with open(path, "w") as fh:
        json.dump({"n": n, "cmd": "bench", "rc": 0, "tail": tail,
                   "parsed": lines[-1] if lines else None}, fh)


_FIXED = {"metric": "cpu_fixed_baseline_throughput", "value": 1.0,
          "unit": "Mrow-iters/s", "baseline_config": "cpu-fixed-v1",
          "backend": "cpu"}
_HEAD = {"metric": "higgs_like_train_throughput", "value": 2.0,
         "backend": "cpu",
         "serving": {"p99_ms": 10.0, "p50_ms": 2.0,
                     "buckets": [1, 64], "batch_sizes": [1, 64],
                     "mode": "closed"}}


def test_bench_trend_committed_series_passes(capsys):
    bt = _load_tool("bench_trend")
    assert bt.main([]) == 0
    out = capsys.readouterr().out
    assert "verdict: ok" in out


def test_bench_trend_fixed_baseline_regression(tmp_path, capsys):
    bt = _load_tool("bench_trend")
    a, b = str(tmp_path / "BENCH_r06.json"), \
        str(tmp_path / "BENCH_r07.json")
    _mk_round(a, 6, [_FIXED, _HEAD])
    _mk_round(b, 7, [dict(_FIXED, value=0.79), _HEAD])  # -21%
    rep = str(tmp_path / "rep.json")
    assert bt.main([a, b, "--report", rep]) == 1
    with open(rep) as fh:
        report = json.load(fh)
    assert report["verdict"] == "regression"
    [r] = report["regressions"]
    assert r["series"] == "cpu_fixed_baseline_throughput"
    assert r["change_pct"] == -21.0
    assert "REGRESSIONS" in capsys.readouterr().out
    # -15% is within the 20% gate
    _mk_round(b, 7, [dict(_FIXED, value=0.85), _HEAD])
    assert bt.main([a, b]) == 0


def test_bench_trend_dispatch_census_series(tmp_path):
    """dispatches_per_split chains per baseline_config (lower is
    better): a >20% increase fails, a config bump breaks the chain."""
    bt = _load_tool("bench_trend")
    a, b = str(tmp_path / "BENCH_r06.json"), \
        str(tmp_path / "BENCH_r07.json")
    disp = {"metric": "dispatches_per_split", "value": 44.0,
            "baseline_config": "cpu-fixed-v1"}
    _mk_round(a, 6, [disp, _FIXED, _HEAD])
    _mk_round(b, 7, [dict(disp, value=56.0), _FIXED, _HEAD])  # +27%
    rep = str(tmp_path / "rep.json")
    assert bt.main([a, b, "--quiet", "--report", rep]) == 1
    with open(rep) as fh:
        report = json.load(fh)
    assert any(r["series"] == "dispatches_per_split"
               for r in report["regressions"])
    # fewer dispatches never regress; the value also rides the fixed
    # baseline line itself
    fixed_carry = dict(_FIXED, dispatches_per_split=40.0)
    _mk_round(b, 7, [fixed_carry, _HEAD])
    assert bt.main([a, b, "--quiet"]) == 0


def test_bench_trend_mesh_scaling_synthetic_regression(tmp_path):
    """The ISSUE-14 mesh_scaling series: total ms/split across the
    mesh learner modes at max devices chains per (backend, shape id)
    — a >20% slowdown fails the gate, an improvement passes, a config
    bump breaks the chain deliberately."""
    bt = _load_tool("bench_trend")
    a, b = str(tmp_path / "BENCH_r06.json"), \
        str(tmp_path / "BENCH_r07.json")
    mesh = {"metric": "mesh_scaling", "value": 8.0,
            "unit": "ms/split (sum over modes, max devices)",
            "backend": "cpu",
            "baseline_config": "mesh-scaling-v1-8192r-16f-15l",
            "mesh_scaling": {
                "devices": [1, 2, 4, 8],
                "modes": {"data": {"1": 4.0, "8": 2.0},
                          "voting": {"1": 5.0, "8": 2.5}},
                "speedup": {"data": 2.0, "voting": 2.0}}}
    _mk_round(a, 6, [mesh, _FIXED, _HEAD])
    _mk_round(b, 7, [dict(mesh, value=10.4), _FIXED, _HEAD])  # +30%
    rep = str(tmp_path / "rep.json")
    assert bt.main([a, b, "--report", rep, "--quiet"]) == 1
    with open(rep) as fh:
        report = json.load(fh)
    assert any(r["series"] == "mesh_scaling_ms"
               for r in report["regressions"])
    assert report["gated_points"]["mesh_scaling_ms"] == 2
    # faster never regresses
    _mk_round(b, 7, [dict(mesh, value=6.0), _FIXED, _HEAD])
    assert bt.main([a, b, "--quiet"]) == 0
    # shape-id bump breaks the chain (no bogus regression)
    _mk_round(b, 7, [dict(mesh, value=99.0,
                          baseline_config="mesh-scaling-v2"),
                     _FIXED, _HEAD])
    assert bt.main([a, b, "--quiet"]) == 0


def test_bench_trend_fleet_p99_synthetic_regression(tmp_path):
    """The fleet soak p99 chains per (backend, replicas, models,
    buckets, batch_sizes, qps): a >20% worsening fails the gate, a
    shape change breaks the chain deliberately."""
    bt = _load_tool("bench_trend")
    fleet = {"p99_ms": 10.0, "p50_ms": 2.0, "throughput_rps": 100.0,
             "shed_rate": 0.0, "availability": 1.0,
             "replicas": 2, "models": ["base", "variant"],
             "buckets": [1, 64], "batch_sizes": [1, 64],
             "offered_qps": 150, "backend": "cpu", "mode": "soak"}
    line = dict(_HEAD, fleet=fleet)
    a, b = str(tmp_path / "BENCH_r06.json"), \
        str(tmp_path / "BENCH_r07.json")
    _mk_round(a, 6, [_FIXED, line])
    worse = dict(line, fleet=dict(fleet, p99_ms=13.0))    # +30%
    _mk_round(b, 7, [_FIXED, worse])
    rep = str(tmp_path / "rep.json")
    assert bt.main([a, b, "--quiet", "--report", rep]) == 1
    with open(rep) as fh:
        report = json.load(fh)
    [r] = [r for r in report["regressions"]
           if r["series"] == "fleet_p99_ms"]
    assert r["change_pct"] == 30.0
    assert report["gated_points"]["fleet_p99_ms"] == 2
    # within threshold passes
    _mk_round(b, 7, [_FIXED, dict(line,
                                  fleet=dict(fleet, p99_ms=11.0))])
    assert bt.main([a, b, "--quiet"]) == 0
    # a replica-count change breaks the comparison chain (no gate)
    _mk_round(b, 7, [_FIXED, dict(line, fleet=dict(
        fleet, p99_ms=50.0, replicas=4))])
    assert bt.main([a, b, "--quiet"]) == 0


def test_bench_trend_single_row_and_shm_leg_attribution(tmp_path):
    """The zero-Python hot path series: single_row_p99_ms and
    shm_large_batch_p99_ms chain from the fleet_isolation block; a
    >20% worsening fails the gate, and the trip names whether the
    AOT or the shm leg regressed."""
    bt = _load_tool("bench_trend")
    fi = {"process_p99_ms": 5.0, "thread_p99_ms": 4.0,
          "replicas": 2, "buckets": [1, 64], "offered_qps": 120,
          "restart_ready_ms": 3000.0, "aot_batch_rows": 512,
          "aot_p99_ms": 3.0, "single_row_p99_ms": 2.0,
          "shm_large_batch_p99_ms": 6.0,
          "json_large_batch_p99_ms": 30.0, "shm_speedup_pct": 400.0,
          "aot_restart_ready_ms": 1500.0}
    line = dict(_HEAD, fleet_isolation=fi)
    a, b = str(tmp_path / "BENCH_r06.json"), \
        str(tmp_path / "BENCH_r07.json")
    _mk_round(a, 6, [_FIXED, line])
    # only the single-row (AOT) leg regresses: +50%, shm leg flat
    worse = dict(line, fleet_isolation=dict(fi,
                                            single_row_p99_ms=3.0))
    _mk_round(b, 7, [_FIXED, worse])
    rep = str(tmp_path / "rep.json")
    assert bt.main([a, b, "--quiet", "--report", rep]) == 1
    with open(rep) as fh:
        report = json.load(fh)
    [r] = [r for r in report["regressions"]
           if r["series"] == "single_row_p99_ms"]
    assert r["change_pct"] == 50.0
    assert r["leg"] == "aot"
    assert report["gated_points"]["single_row_p99_ms"] == 2
    assert report["gated_points"]["shm_large_batch_p99_ms"] == 2
    # only the shm transport leg regresses: named "shm"
    worse = dict(line, fleet_isolation=dict(
        fi, shm_large_batch_p99_ms=9.0))
    _mk_round(b, 7, [_FIXED, worse])
    assert bt.main([a, b, "--quiet", "--report", rep]) == 1
    with open(rep) as fh:
        report = json.load(fh)
    [r] = [r for r in report["regressions"]
           if r["series"] == "shm_large_batch_p99_ms"]
    assert r["leg"] == "shm"
    # both legs worsen past the gate: named "both" on both trips
    worse = dict(line, fleet_isolation=dict(
        fi, single_row_p99_ms=3.0, shm_large_batch_p99_ms=9.0))
    _mk_round(b, 7, [_FIXED, worse])
    assert bt.main([a, b, "--quiet", "--report", rep]) == 1
    with open(rep) as fh:
        report = json.load(fh)
    legs = {r["series"]: r.get("leg")
            for r in report["regressions"]}
    assert legs.get("single_row_p99_ms") == "both"
    assert legs.get("shm_large_batch_p99_ms") == "both"
    # within the threshold passes, and the render names the leg
    _mk_round(b, 7, [_FIXED, dict(line, fleet_isolation=dict(
        fi, single_row_p99_ms=2.2))])
    assert bt.main([a, b, "--quiet"]) == 0


def test_bench_trend_serving_p99_and_config_bump(tmp_path):
    bt = _load_tool("bench_trend")
    a, b = str(tmp_path / "BENCH_r06.json"), \
        str(tmp_path / "BENCH_r07.json")
    _mk_round(a, 6, [_FIXED, _HEAD])
    worse = dict(_HEAD, serving=dict(_HEAD["serving"], p99_ms=12.5))
    _mk_round(b, 7, [_FIXED, worse])              # p99 +25%
    assert bt.main([a, b, "--quiet"]) == 1
    # a baseline_config bump deliberately breaks the comparison chain
    _mk_round(b, 7, [dict(_FIXED, value=0.1,
                          baseline_config="cpu-fixed-v2"), _HEAD])
    assert bt.main([a, b, "--quiet"]) == 0
    # unparsable-only input is a usage error, not a silent pass
    bad = str(tmp_path / "BENCH_r08.json")
    with open(bad, "w") as fh:
        fh.write("not json")
    assert bt.main([bad]) == 2


# ---------------------------------------------------------------------
# run_report + bench probe telemetry satellites
def test_run_report_renders_hist_records_and_probe(tel, tmp_path):
    rr = _load_tool("run_report")
    path = str(tmp_path / "t.jsonl")
    recs = [
        {"kind": "run_start", "t": 0.0, "backend": "cpu"},
        {"kind": "probe", "t": 0.1, "verdict": "failed",
         "reason": "hung > 90s", "dur_s": 180.0, "cached": False},
        {"kind": "hist", "t": 1.0,
         "name": "serving_request_latency_ms",
         "labels": {"kind": "predict", "bucket": "8"},
         "count": 100, "sum": 250.0, "p50": 2.1, "p95": 6.0,
         "p99": 9.5},
        {"kind": "train_end", "t": 2.0, "iters": 1, "dur_s": 1.0},
    ]
    with open(path, "w") as fh:
        for r in recs:
            fh.write(json.dumps(r) + "\n")
    d = rr.digest(rr.load(path))
    assert d["tpu_probe"]["verdict"] == "failed"
    key, = d["hists"]
    assert "serving_request_latency_ms" in key and "bucket=8" in key
    text = rr.render(rr.load(path))
    assert "histograms (live metrics plane)" in text
    assert "tpu probe" in text and "hung > 90s" in text


def test_run_report_renders_dispatch_census(tmp_path):
    """The census artifact renders standalone AND automatically next
    to a trace report when bench_census.json sits beside the trace."""
    rr = _load_tool("run_report")
    art = {"config": {"features": 8, "leaves": 15, "backend": "cpu",
                      "split_fusion": True},
           "programs": {"serial_grow": {
               "ops_per_split": 44, "fusions": 28, "inner_whiles": 3,
               "collectives": 0, "carry_arrays": 24,
               "carry_bytes": 294508}}}
    path = str(tmp_path / "bench_census.json")
    with open(path, "w") as fh:
        json.dump(art, fh)
    loaded = rr.load_census(path)
    assert loaded is not None
    text = rr.render_census(loaded)
    assert "per-split dispatch census" in text
    assert "serial_grow" in text and "44" in text
    # sibling detection from a trace path in the same directory
    assert rr.sibling_census(str(tmp_path / "t.jsonl")) is not None
    # a crash dump / trace is NOT mistaken for a census artifact
    tr = str(tmp_path / "t2.json")
    with open(tr, "w") as fh:
        json.dump({"flight_recorder": 1, "programs": 3}, fh)
    assert rr.load_census(tr) is None


def test_run_report_renders_crash_dump(tmp_path):
    rr = _load_tool("run_report")
    dump = {"flight_recorder": 1, "reason": "guard:nonfinite",
            "pid": 1, "iteration": 9, "config_fingerprint": "abc",
            "bin_layout_fingerprint": "def",
            "config": {"objective": "binary"},
            "counters": {"guard.nonfinite_iters": 1},
            "trips": [{"kind": "nonfinite", "iteration": 9,
                       "wall_time": 0}],
            "memory": {"live_arrays": 3},
            "records": [{"kind": "iter", "t": 1.0, "iter": 8,
                         "phases": {"grow": 0.01}}]}
    path = str(tmp_path / "x.crash.json")
    with open(path, "w") as fh:
        json.dump(dump, fh, indent=1)
    assert rr.load_crash(path) is not None
    text = rr.render_crash(dump)
    assert "reason=guard:nonfinite" in text
    assert "config_fingerprint=abc" in text
    assert "iter=8" in text
    # a JSONL trace is NOT mistaken for a crash dump
    tr = str(tmp_path / "t.jsonl")
    with open(tr, "w") as fh:
        fh.write(json.dumps({"kind": "iter", "t": 0.0}) + "\n")
    assert rr.load_crash(tr) is None


def test_bench_probe_telemetry(tmp_path, monkeypatch):
    import sys
    sys.path.insert(0, REPO)
    import bench
    path = str(tmp_path / "bt.jsonl")
    monkeypatch.setenv("LGBM_TPU_TELEMETRY", path)
    bench.emit_probe_telemetry(False, "probe hung", 3.2)
    bench.emit_probe_telemetry(True, "ok", 0.4)
    with open(path) as fh:
        recs = [json.loads(ln) for ln in fh if ln.strip()]
    probes = [r for r in recs if r["kind"] == "probe"]
    assert [p["verdict"] for p in probes] == ["failed", "ok"]
    assert probes[0]["reason"] == "probe hung"
    counters = [r for r in recs if r["kind"] == "counter"]
    assert counters and counters[0]["name"] == "probe.fail"


def test_stop_exporter_joins_thread(tel):
    # graftsync regression: stop_exporter used to discard the serve
    # thread; it must now join it so shutdown leaks nothing
    start_exporter(0)
    assert any(t.name == "lgbm-metrics-exporter"
               for t in threading.enumerate())
    stop_exporter()
    assert all(t.name != "lgbm-metrics-exporter"
               for t in threading.enumerate())
    stop_exporter()  # idempotent
