"""End-to-end trace correlation (ISSUE 11).

Acceptance gates:
  * trace/span ids with parent links thread HTTP -> fleet replica ->
    engine queue/batch -> named jitted program, canary + shadow paths
    share the parent trace, and request latency decomposes into
    queue-wait vs batch/device time;
  * the export is Chrome-trace-event JSON (Perfetto-loadable;
    schema-validated below) rendered by tools/run_report.py;
  * tracing OFF (the default) adds zero recompiles and no implicit
    device->host transfers to the serving hot path — and tracing ON
    holds the same bar (host wall clock only);
  * tools/bench_trend.py names the phase whose span share regressed
    on a synthetic fixed-baseline regression;
  * probe failures classify into the structured reason codes
    (tools/probe_taxonomy.py) and the flight recorder dumps in-flight
    span stacks with trace ids.
"""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability.metrics import get_metrics, metrics_text
from lightgbm_tpu.observability.telemetry import get_telemetry
from lightgbm_tpu.observability.tracing import ProfileWindow, get_tracer
from lightgbm_tpu.serving import ServingConfig, ServingEngine
from lightgbm_tpu.serving.fleet import FleetEngine
from lightgbm_tpu.serving.http import make_http_server
from lightgbm_tpu.serving.router import Router

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _toy(n=600, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] > 0).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def binary_model():
    X, y = _toy()
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=10)
    return bst, X


@pytest.fixture
def tracer():
    tr = get_tracer()
    tr.reset()
    tel = get_telemetry()
    tel.reset()
    tel.ensure_ring()
    get_metrics().reset()
    tr.configure()
    yield tr
    tr.reset()
    tel.reset()
    get_metrics().reset()


@pytest.fixture
def no_tracer():
    tr = get_tracer()
    tr.reset()
    yield tr
    tr.reset()


def _x_events(tr):
    return [e for e in tr.events if e.get("ph") == "X"]


# ----------------------------------------------------------------------
# core: ids, nesting, disabled cost
def test_span_ids_nest_and_link(tracer):
    with tracer.span("root", cat="t") as root:
        with tracer.span("child", cat="t") as child:
            assert child.ctx.trace_id == root.ctx.trace_id
            assert child.ctx.span_id != root.ctx.span_id
    evs = {e["name"]: e for e in _x_events(tracer)}
    assert evs["child"]["args"]["parent_id"] == root.ctx.span_id
    assert "parent_id" not in evs["root"]["args"]
    assert evs["root"]["args"]["trace_id"] == root.ctx.trace_id
    # child closed before root on the timeline
    assert evs["child"]["ts"] >= evs["root"]["ts"]


def test_top_level_spans_root_their_own_traces(tracer):
    with tracer.span("a"):
        pass
    with tracer.span("b"):
        pass
    tids = {e["args"]["trace_id"] for e in _x_events(tracer)}
    assert len(tids) == 2


def test_detached_handle_crosses_threads(tracer):
    with tracer.span("root") as root:
        h = tracer.begin_span("queued", ctx=root.ctx)

        def worker():
            h.finish(outcome="ok")
            with tracer.attach(h.ctx):
                with tracer.span("work"):
                    pass
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    evs = {e["name"]: e for e in _x_events(tracer)}
    assert evs["queued"]["args"]["trace_id"] == root.ctx.trace_id
    assert evs["work"]["args"]["trace_id"] == root.ctx.trace_id
    assert evs["queued"]["args"]["outcome"] == "ok"


def test_disabled_tracer_is_inert(no_tracer):
    tr = no_tracer
    assert tr.current() is None
    with tr.span("x") as h:
        assert h.ctx is None          # the shared null handle
    h2 = tr.begin_span("y")
    h2.finish()
    tr.instant("z")
    assert tr.events == []


def test_from_header_parses_and_falls_back(tracer):
    ctx = tracer.from_header("00ff00ff00ff00ff")
    assert ctx.trace_id == "00ff00ff00ff00ff"
    ctx2 = tracer.from_header("aabb-ccdd")
    assert (ctx2.trace_id, ctx2.span_id) == ("aabb", "ccdd")
    assert tracer.from_header("not hex!").trace_id != "not hex!"
    assert tracer.from_header(None).trace_id


def test_finish_is_idempotent_and_backdatable(tracer):
    h = tracer.begin_span("once")
    t_end = time.perf_counter()
    h.finish(_end_t=t_end)
    h.finish()
    evs = [e for e in _x_events(tracer) if e["name"] == "once"]
    assert len(evs) == 1


# ----------------------------------------------------------------------
# Chrome trace JSON schema (Perfetto-loadable)
def _validate_chrome_trace(doc):
    assert isinstance(doc, dict)
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ph"] in ("X", "M", "i", "s", "t", "f")
        assert isinstance(e["pid"], int)
        if e["ph"] == "M":
            continue
        assert isinstance(e["tid"], int)
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            args = e["args"]
            assert isinstance(args["trace_id"], str)
            assert isinstance(args["span_id"], str)
        if e["ph"] in ("s", "t", "f"):
            assert isinstance(e["id"], int)
    # the whole doc round-trips as JSON (what Perfetto actually needs)
    json.loads(json.dumps(doc))


def test_chrome_trace_export_schema(tracer, tmp_path):
    with tracer.span("outer", cat="test"):
        with tracer.span("inner", cat="test"):
            pass
    tracer.instant("marker")
    path = str(tmp_path / "trace.json")
    out = tracer.export(path)
    assert out == path
    with open(path) as fh:
        doc = json.load(fh)
    _validate_chrome_trace(doc)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "process_name" in names and "outer" in names


def test_run_report_renders_timeline(tracer, tmp_path, capsys):
    with tracer.span("serving.request", cat="serving"):
        pass
    path = str(tmp_path / "t.json")
    tracer.export(path)
    run_report = _load_tool("run_report")
    assert run_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "span timeline" in out and "serving.request" in out


# ----------------------------------------------------------------------
# serving engine: queue-wait / batch / device decomposition + program
def test_serving_request_decomposition(tracer, binary_model):
    bst, X = binary_model
    eng = ServingEngine(bst, config=ServingConfig(
        buckets=(1, 8, 64), device="always"))
    try:
        fut = eng.submit(X[:5])
        fut.result(timeout=10.0)
        meta = fut.meta
        assert meta["trace_id"]
        assert meta["queue_ms"] >= 0
        assert meta["compute_ms"] >= 0
        assert meta["latency_ms"] >= meta["compute_ms"]
    finally:
        eng.stop()
    evs = _x_events(tracer)
    chain = {e["name"]: e for e in evs
             if e["args"].get("trace_id") == meta["trace_id"]}
    assert {"serving.queue_wait", "serving.batch", "serving.request"} \
        <= set(chain)
    # the device dispatch is attributed to the registered program
    dev = [e for e in evs if e["name"] == "device.dispatch"]
    assert dev and dev[-1]["args"]["program"] == "predict_scan_trees"
    assert dev[-1]["args"]["registered"] is True
    # the batch span parents into the request's trace
    assert chain["serving.batch"]["args"]["trace_id"] \
        == meta["trace_id"]


def test_serving_exemplar_on_metrics_and_stats(tracer, binary_model):
    bst, X = binary_model
    eng = ServingEngine(bst, config=ServingConfig(
        buckets=(1, 8), device="never"))
    try:
        for i in range(4):
            eng.predict(X[:1 + i])
        stats = eng.stats()
    finally:
        eng.stop()
    slow = stats["slowest_request"]
    assert slow
    worst = max(slow.values(), key=lambda s: s["latency_ms"])
    assert worst["trace_id"]
    text = metrics_text()
    assert "lgbm_serving_slowest_request_ms" in text
    assert f'trace_id="{worst["trace_id"]}"' in text
    # serving_stats telemetry record carries the exemplar too
    tel = get_telemetry()
    recs = [r for r in tel.records if r.get("kind") == "serving_stats"]
    assert recs and recs[-1].get("slowest_request")


# ----------------------------------------------------------------------
# fleet: canary + shadow share the parent trace; redispatch marks
def test_fleet_canary_and_shadow_share_trace(tracer, binary_model):
    bst, X = binary_model
    router = Router()
    router.set_canary("base", "variant", 1.0)   # weight 1 = always
    router.set_shadow("base", "variant")
    fl = FleetEngine(models={"base": bst, "variant": bst},
                     config=ServingConfig(buckets=(1, 8),
                                          device="never"),
                     replicas=2, router=router, default_model="base")
    try:
        fut = fl.submit(X[:2], tenant="acme")
        fut.result(timeout=10.0)
        meta = fut.meta
        assert meta["trace_id"]
        assert meta["target"] == "variant"      # canary took it
        deadline = time.monotonic() + 10.0
        # shadow compare runs off-thread; wait for its spans to close
        while time.monotonic() < deadline:
            evs = [e for e in _x_events(get_tracer())
                   if e["args"].get("trace_id") == meta["trace_id"]]
            if len([e for e in evs
                    if e["name"] == "serving.request"]) >= 2:
                break
            time.sleep(0.05)
    finally:
        fl.stop()
    names = sorted(e["name"] for e in evs)
    # root + canary-primary chain + shadow mirror chain, ONE trace id
    assert names.count("serving.request") >= 2, names
    assert "fleet.request" in names
    roots = [e for e in evs if e["name"] == "fleet.request"]
    assert not roots[0]["args"].get("parent_id")


def test_fleet_error_finishes_root_span(tracer, binary_model):
    bst, X = binary_model
    fl = FleetEngine(models={"base": bst},
                     config=ServingConfig(buckets=(1,), device="never"),
                     replicas=1, default_model="base")
    try:
        with pytest.raises(Exception):
            fl.submit(X[:1], model="missing").result(timeout=5.0)
    finally:
        fl.stop()
    roots = [e for e in _x_events(tracer)
             if e["name"] == "fleet.request"]
    assert roots and roots[0]["args"]["error"] == "model_not_found"


# ----------------------------------------------------------------------
# HTTP frontend: header in, trace id out, full chain
def test_http_trace_header_roundtrip(tracer, binary_model):
    bst, X = binary_model
    eng = ServingEngine(bst, config=ServingConfig(
        buckets=(1, 8), device="never"))
    server = make_http_server(eng, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    host, port = server.server_address[:2]
    try:
        body = json.dumps({"rows": X[:2].tolist()}).encode()
        req = urllib.request.Request(
            f"http://{host}:{port}/predict", data=body,
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": "feedfacefeedface"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            payload = json.loads(resp.read())
    finally:
        server.shutdown()
        server.server_close()
        eng.stop()
    assert payload["trace_id"] == "feedfacefeedface"
    evs = [e for e in _x_events(tracer)
           if e["args"].get("trace_id") == "feedfacefeedface"]
    names = {e["name"] for e in evs}
    assert {"http.predict", "serving.queue_wait",
            "serving.request"} <= names


# ----------------------------------------------------------------------
# hot-path guards: zero recompiles, no implicit host transfers
@pytest.mark.parametrize("tracing_on", [False, True])
def test_tracing_hot_path_zero_recompiles_no_transfers(
        binary_model, tracing_on):
    from tools.graftlint.runtime import no_implicit_host_transfers
    tr = get_tracer()
    tr.reset()
    tel = get_telemetry()
    tel.reset()
    tel.ensure_ring()
    if tracing_on:
        tr.configure()
    try:
        bst, X = binary_model
        eng = ServingEngine(bst, config=ServingConfig(
            buckets=(1, 8, 64), device="always"))
        try:
            eng.predict(X[:3])        # absorb any lazy first-call work
            compiles0 = tel.counters.get("jit.compiles", 0)
            with no_implicit_host_transfers():
                for n in (1, 3, 8, 5):
                    eng.predict(X[:n])
            assert tel.counters.get("jit.compiles", 0) == compiles0, \
                "tracing hot path recompiled something"
        finally:
            eng.stop()
        if tracing_on:
            assert any(e.get("name") == "device.dispatch"
                       for e in tr.events)
        else:
            assert tr.events == []
    finally:
        tr.reset()
        tel.reset()
        get_metrics().reset()


# ----------------------------------------------------------------------
# trend attribution: a synthetic regression names the phase
def test_trend_attribution_names_regressing_phase(tmp_path):
    bench_trend = _load_tool("bench_trend")

    def round_file(i, value, phases):
        line = {"metric": "cpu_fixed_baseline_throughput",
                "value": value, "baseline_config": "cfg-v1",
                "phases": phases}
        p = tmp_path / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps({"n": i, "tail": json.dumps(line)}))
        return str(p)

    f1 = round_file(1, 10.0, {"grad": 1.0, "grow": 7.0, "update": 2.0})
    f2 = round_file(2, 6.0, {"grad": 1.0, "grow": 14.0, "update": 2.0})
    rounds = [bench_trend.load_round(f) for f in (f1, f2)]
    report = bench_trend.analyze(rounds, threshold=0.2)
    assert report["verdict"] == "regression"
    reg = report["regressions"][0]
    assert reg["attribution"]["phase"] == "grow"
    assert reg["attribution"]["to_share"] > reg["attribution"][
        "from_share"]
    # shares are normalized (sum ~1) and ride the report
    shares = report["phase_shares"]
    assert len(shares) == 2
    assert abs(sum(shares[0]["shares"].values()) - 1.0) < 0.01
    rendered = bench_trend.render(report)
    assert "attributed to phase 'grow'" in rendered


def test_trend_no_attribution_without_phases(tmp_path):
    bench_trend = _load_tool("bench_trend")
    for i, v in ((1, 10.0), (2, 6.0)):
        line = {"metric": "cpu_fixed_baseline_throughput", "value": v,
                "baseline_config": "cfg-v1"}
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps({"n": i, "tail": json.dumps(line)}))
    rounds = [bench_trend.load_round(
        str(tmp_path / f"BENCH_r{i:02d}.json")) for i in (1, 2)]
    report = bench_trend.analyze(rounds, threshold=0.2)
    assert report["verdict"] == "regression"
    assert "attribution" not in report["regressions"][0]


def test_committed_series_still_passes():
    bench_trend = _load_tool("bench_trend")
    import glob
    files = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    rounds = [r for r in (bench_trend.load_round(f) for f in files)
              if r]
    assert rounds
    report = bench_trend.analyze(rounds)
    assert report["verdict"] == "ok", report["regressions"]


# ----------------------------------------------------------------------
# probe taxonomy
def test_probe_taxonomy_codes():
    pt = _load_tool("probe_taxonomy")
    cases = {
        "AssertionError: [CpuDevice(id=0)]": "no_device",
        "jax fell back: platform != 'cpu'": "no_device",
        "hung > 90s": "init_timeout",
        "DEADLINE_EXCEEDED while waiting": "init_timeout",
        "XlaRuntimeError: INTERNAL: Mosaic lowering failed":
            "compile_error",
        "failed to connect to all addresses (grpc)": "transport",
        "Connection refused by the backend": "transport",
        "something else entirely": "unknown",
        "": "unknown",
    }
    for detail, code in cases.items():
        assert pt.classify_probe_failure(detail) == code, detail
    assert set(cases.values()) <= set(pt.REASON_CODES)


def test_run_report_probe_timeline(tmp_path, capsys):
    run_report = _load_tool("run_report")
    trace = tmp_path / "t.jsonl"
    recs = [
        {"kind": "probe", "t": 0.0, "verdict": "failed",
         "reason": "hung > 90s", "reason_code": "init_timeout",
         "dur_s": 90.0},
        {"kind": "probe", "t": 0.0, "verdict": "failed",
         "reason": "Connection refused by the backend",
         "dur_s": 1.0},   # no code -> classified
        {"kind": "probe", "t": 0.0, "verdict": "ok", "reason": "",
         "dur_s": 0.1},
    ]
    trace.write_text("".join(json.dumps(r) + "\n" for r in recs))
    assert run_report.main([str(trace)]) == 0
    out = capsys.readouterr().out
    assert "tpu probe timeline" in out
    assert "init_timeout" in out and "transport" in out
    d = run_report.digest(recs)
    assert [p["reason_code"] for p in d["probe_history"]] == \
        ["init_timeout", "transport", None]


# ----------------------------------------------------------------------
# flight recorder: in-flight span stacks with trace ids
def test_flight_recorder_dumps_active_spans(tracer, tmp_path):
    from lightgbm_tpu.observability.flightrec import (arm_recorder,
                                                      disarm_recorder)
    dump = str(tmp_path / "crash.json")
    rec = arm_recorder(dump_path=dump)
    try:
        with tracer.span("iteration", cat="train",
                         args={"iter": 7}):
            h = tracer.begin_span("serving.queue_wait", cat="serving")
            rec.dump("test_trip")
            h.finish()
    finally:
        disarm_recorder(rec)
    with open(dump) as fh:
        payload = json.load(fh)
    spans = payload["trace_spans"]
    names = {s["name"] for s in spans}
    assert {"iteration", "serving.queue_wait"} <= names
    for s in spans:
        assert s["trace_id"] and s["elapsed_ms"] >= 0
    # the rendered crash report shows the stacks
    run_report = _load_tool("run_report")
    text = run_report.render_crash(payload)
    assert "in-flight span stacks" in text


# ----------------------------------------------------------------------
# profiler window: span-boundary alignment, one-shot
def test_profile_window_boundary_alignment(tmp_path, monkeypatch):
    w = ProfileWindow()
    monkeypatch.setenv("LGBM_TPU_PROFILE_SKIP", "1")
    monkeypatch.setenv("LGBM_TPU_PROFILE_SPANS", "2")
    w.arm(str(tmp_path / "prof"))
    assert w.state == "armed"
    w.boundary()                      # boundary 1 == skip -> not yet
    assert w.state == "armed"
    w.boundary()                      # boundary 2 -> capture starts
    assert w.state == "capturing"
    w.boundary()                      # within the window
    assert w.state == "capturing"
    w.boundary()                      # window exhausted -> stops
    assert w.state == "done"
    w.boundary()                      # one-shot: stays done
    assert w.state == "done"
    assert os.path.isdir(str(tmp_path / "prof"))


def test_profile_window_close_mid_capture(tmp_path, monkeypatch):
    w = ProfileWindow()
    monkeypatch.setenv("LGBM_TPU_PROFILE_SKIP", "0")
    monkeypatch.setenv("LGBM_TPU_PROFILE_SPANS", "100")
    w.arm(str(tmp_path / "prof2"))
    w.boundary()
    assert w.state == "capturing"
    w.close()
    assert w.state == "done"
    w.arm(str(tmp_path / "prof3"))    # one-shot: re-arm is a no-op
    assert w.state == "done"


# ----------------------------------------------------------------------
# training side: phase spans carry the iteration's trace
def test_training_spans_on_timeline(tracer):
    X, y = _toy(400, 5, seed=2)
    lgb.train({"objective": "binary", "num_leaves": 7,
               "verbosity": -1}, lgb.Dataset(X, label=y),
              num_boost_round=4)
    evs = _x_events(tracer)
    names = {e["name"] for e in evs}
    assert "grad" in names and "train" in names
    grads = [e for e in evs if e["name"] == "grad"]
    # every phase span carries ids linking it into the run's trace
    assert all(e["args"].get("trace_id") for e in grads)
    train_ev = [e for e in evs if e["name"] == "train"][-1]
    assert grads[-1]["args"]["trace_id"] \
        == train_ev["args"]["trace_id"]


def test_trace_out_param_exports_training_timeline(tmp_path):
    tr = get_tracer()
    tr.reset()
    get_telemetry().reset()
    try:
        X, y = _toy(300, 5, seed=4)
        out = str(tmp_path / "train_trace.json")
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbosity": -1, "trace_out": out},
                  lgb.Dataset(X, label=y), num_boost_round=3)
        with open(out) as fh:
            doc = json.load(fh)
        _validate_chrome_trace(doc)
        assert any(e.get("name") == "train"
                   for e in doc["traceEvents"])
    finally:
        tr.reset()
        get_telemetry().reset()
