"""Structured training telemetry (lightgbm_tpu/observability/).

Covers the ISSUE-1 test checklist: span nesting/accumulation, counters
across jit boundaries, the JSONL sink round-trip through
tools/run_report.py, zero records in disabled mode, and the
``record_telemetry`` engine callback.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability.telemetry import JsonlSink, get_telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_run_report():
    spec = importlib.util.spec_from_file_location(
        "run_report", os.path.join(REPO, "tools", "run_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tel():
    """Fresh singleton state per test; always restored to disabled."""
    t = get_telemetry()
    t.reset()
    yield t
    t.reset()


def _toy(n=600, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


# ---------------------------------------------------------------------
def test_spans_nest_and_accumulate(tel):
    tel.configure(summary=False)
    for _ in range(3):
        with tel.span("outer"):
            with tel.span("inner"):
                pass
            with tel.span("inner"):
                pass
    assert tel.spans["outer"][1] == 3
    assert tel.spans["outer/inner"][1] == 6
    # child time is contained in the parent's
    assert tel.spans["outer"][0] >= tel.spans["outer/inner"][0]
    # a sibling at top level gets its own path, not outer's
    with tel.span("other"):
        pass
    assert "other" in tel.spans and "outer/other" not in tel.spans


def test_phase_spans_feed_iteration_records(tel):
    tel.configure(summary=False)
    with tel.span("grad", phase=True):
        pass
    with tel.span("grow", phase=True):
        pass
    tel.end_iteration(0, trees=1)
    recs = [r for r in tel.records if r["kind"] == "iter"]
    assert len(recs) == 1
    assert set(recs[0]["phases"]) == {"grad", "grow"}
    # phases were flushed: the next iteration starts empty
    tel.end_iteration(1)
    assert tel.records[-1]["phases"] == {}


def test_counters_survive_jit_boundaries(tel):
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.learner.comm import _count_collective
    tel.configure(summary=False)

    @jax.jit
    def f(x):
        return _count_collective("test", x) * 2

    x = jnp.ones((4, 4), jnp.float32)
    np.testing.assert_allclose(np.asarray(f(x)), 2.0)
    np.testing.assert_allclose(np.asarray(f(x)), 2.0)  # cached call
    # counted at trace time: once per compiled program, 4*4*4 bytes
    assert tel.counters["comm.test_bytes"] == 64
    assert tel.counters["comm.test_calls"] == 1
    # host-side counters accept device scalars and keep accumulating
    tel.count("host.rows", jnp.int32(5))
    tel.count("host.rows", 7)
    assert tel.counters["host.rows"] == 12


def test_mesh_comm_and_ingest_counters(tel):
    """Training a mesh learner records per-op collective payloads
    (comm.<op>_bytes/_calls through the _count_collective seam) and
    the sharded-ingest counters — the data the run_report comms table
    renders (ISSUE 14 telemetry satellite)."""
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.parallel import DataParallelTreeLearner
    tel.configure(summary=False)
    X, y = _toy(n=800)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    lrn = DataParallelTreeLearner(ds, cfg)
    lrn.train(jnp.asarray(y - 0.5, jnp.float32),
              jnp.full((len(y),), 0.25, jnp.float32))
    c = tel.counters
    # the reduce-scatter recipe: ONE packed root psum, ONE per-split
    # reduce-scatter, ONE packed winner gather for the vmapped child
    # pair (the root select is replicated — no gather)
    assert c.get("comm.psum_calls", 0) == 1
    assert c.get("comm.psum_scatter_calls", 0) == 1
    assert c.get("comm.all_gather_calls", 0) == 1
    assert c.get("comm.psum_scatter_bytes", 0) > 0
    # sharded ingest: binned + mv dummy went through shard_rows
    assert c.get("ingest.sharded_puts", 0) >= 2
    assert c.get("ingest.sharded_bytes", 0) >= X.size


def test_run_report_renders_comms_table():
    rr = _load_run_report()
    records = [
        {"kind": "run_start", "backend": "cpu", "device_count": 8,
         "jax_version": "0"},
        {"kind": "train_end", "iters": 1, "num_data": 10, "dur_s": 0.1,
         "counters": {"comm.psum_bytes": 4096.0, "comm.psum_calls": 1.0,
                      "comm.all_gather_bytes": 144.0,
                      "comm.all_gather_calls": 2.0,
                      "comm.psum_scatter_bytes": 8192.0,
                      "comm.psum_scatter_calls": 1.0,
                      "ingest.sharded_bytes": 123456.0,
                      "ingest.sharded_puts": 2.0}},
    ]
    d = rr.digest(records)
    assert d["comms"]["psum_scatter"] == {"bytes": 8192.0, "calls": 1.0}
    assert d["comms"]["all_gather"]["calls"] == 2.0
    assert d["ingest"]["sharded_bytes"] == 123456.0
    out = rr.render(records)
    assert "mesh comms" in out
    assert "psum_scatter" in out and "all_gather" in out
    assert "ingest:" in out and "123,456" in out


def test_disabled_mode_adds_no_records(tel):
    assert not tel.enabled
    with tel.span("train"):
        with tel.span("grad", phase=True):
            pass
    tel.count("x", 1)
    tel.gauge("g", 2)
    tel.observe("d", 3.0)
    tel.end_iteration(0)
    tel.record("iter", iter=0)
    assert tel.records == []
    assert tel.spans == {} and tel.counters == {}
    assert tel.gauges == {} and tel.dists == {}


def test_disabled_training_emits_nothing(tel):
    X, y = _toy()
    booster = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbosity": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=3)
    assert booster.num_trees() == 3
    assert tel.records == [] and tel.counters == {}


def test_jsonl_roundtrip_through_run_report(tel, tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tel.configure(jsonl_path=path, summary=False)
    tel.ensure_started()  # run_start for an already-enabled session
    X, y = _toy(800)
    Xv, yv = _toy(200, seed=1)
    train_set = lgb.Dataset(X, label=y)
    lgb.train({"objective": "binary", "num_leaves": 7,
               "metric": "binary_logloss", "verbosity": -1},
              train_set, num_boost_round=4,
              valid_sets=[lgb.Dataset(Xv, label=yv, reference=train_set)],
              verbose_eval=False)
    tel.flush()

    rr = _load_run_report()
    records = rr.load(path)
    kinds = {r["kind"] for r in records}
    assert {"run_start", "iter", "train_end"} <= kinds
    d = rr.digest(records)
    assert d["iters"] == 4
    assert d["compile"]["count"] > 0
    assert d["compile"]["seconds"] > 0
    assert "grow" in d["phases"] and d["phases"]["grow"]["count"] == 4
    assert d["eval"], "eval records should surface in the digest"
    text = rr.render(records)
    assert "compile vs steady state" in text and "grow" in text
    # counters made it into the record stream
    assert d["counters"]["learner.trees"] == 4


def test_train_end_record_and_summary_fields(tel, tmp_path):
    path = str(tmp_path / "t.jsonl")
    tel.configure(jsonl_path=path, summary=False)
    X, y = _toy(400)
    lgb.train({"objective": "binary", "num_leaves": 7,
               "verbosity": -1}, lgb.Dataset(X, label=y),
              num_boost_round=2)
    tel.flush()
    recs = [json.loads(ln) for ln in open(path)]
    ends = [r for r in recs if r["kind"] == "train_end"]
    assert ends, "pipelined path must emit train_end"
    end = ends[-1]
    assert end["iters"] == 2 and end["num_data"] == 400
    assert end["dur_s"] > 0 and "memory" in end
    assert end["compile"]["count"] >= 1


def test_record_telemetry_callback_populates_dict(tel):
    X, y = _toy(500)
    Xv, yv = _toy(150, seed=2)
    out = {}
    train_set = lgb.Dataset(X, label=y)
    lgb.train({"objective": "binary", "num_leaves": 7,
               "metric": "binary_logloss", "verbosity": -1},
              train_set, num_boost_round=3,
              valid_sets=[lgb.Dataset(Xv, label=yv,
                                      reference=train_set)],
              verbose_eval=False,
              callbacks=[lgb.record_telemetry(out)])
    assert len(out["iterations"]) == 3
    for i, rec in enumerate(out["iterations"]):
        assert rec["iteration"] == i
        assert "phases" in rec and "grow" in rec["phases"]
        assert rec["eval"], "eval results ride the iteration record"
    assert out["summary"]["counters"]["learner.trees"] == 3
    assert "compile" in out["summary"]


def test_record_telemetry_forces_stepped_loop(tel):
    """Without eval sets the engine would take the pipelined fast path;
    requesting telemetry recording must force per-iteration stepping so
    the dict really fills."""
    X, y = _toy(300)
    out = {}
    lgb.train({"objective": "binary", "num_leaves": 7,
               "verbosity": -1}, lgb.Dataset(X, label=y),
              num_boost_round=2, callbacks=[lgb.record_telemetry(out)])
    assert len(out["iterations"]) == 2


def test_record_telemetry_does_not_swallow_env_jsonl(tel, tmp_path,
                                                     monkeypatch):
    """Creating a record_telemetry callback enables ring-only mode
    BEFORE the engine calls ensure_started; the LGBM_TPU_TELEMETRY
    JSONL sink must still attach instead of being silently dropped."""
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv("LGBM_TPU_TELEMETRY", path)
    X, y = _toy(300)
    out = {}
    lgb.train({"objective": "binary", "num_leaves": 7,
               "verbosity": -1}, lgb.Dataset(X, label=y),
              num_boost_round=2, callbacks=[lgb.record_telemetry(out)])
    assert len(out["iterations"]) == 2
    with open(path) as fh:
        kinds = {json.loads(ln)["kind"] for ln in fh if ln.strip()}
    assert {"run_start", "iter", "train_end"} <= kinds


def test_jsonl_sink_tolerates_append_and_new_instance(tel, tmp_path):
    path = str(tmp_path / "a.jsonl")
    s = JsonlSink(path)
    s.emit({"kind": "x", "t": 0.0})
    s.close()
    s2 = JsonlSink(path)
    s2.emit({"kind": "y", "t": 1.0})
    s2.close()
    rr = _load_run_report()
    assert [r["kind"] for r in rr.load(path)] == ["x", "y"]


def test_summary_sink_honors_verbosity(tel, capsys):
    from lightgbm_tpu.utils.log import set_verbosity
    tel.configure(summary=True)
    try:
        set_verbosity(-1)
        tel.record("train_end", iters=1, dur_s=0.5)
        assert "[telemetry]" not in capsys.readouterr().out
        set_verbosity(1)
        tel.record("train_end", iters=1, dur_s=0.5,
                   phase_totals={"grow": 0.4})
        out = capsys.readouterr().out
        assert "[telemetry]" in out and "grow" in out
    finally:
        set_verbosity(1)


def test_telemetry_out_param_enables_file(tel, tmp_path, monkeypatch):
    """The ``telemetry_out`` config parameter (and its CLI form
    telemetry_out=path) starts a JSONL session without the env var."""
    monkeypatch.delenv("LGBM_TPU_TELEMETRY", raising=False)
    path = str(tmp_path / "cfg.jsonl")
    X, y = _toy(300)
    lgb.train({"objective": "binary", "num_leaves": 7,
               "verbosity": -1, "telemetry_out": path},
              lgb.Dataset(X, label=y), num_boost_round=2)
    tel.flush()
    recs = [json.loads(ln) for ln in open(path)]
    assert any(r["kind"] == "run_start" for r in recs)
    assert any(r["kind"] == "train_end" for r in recs)


# ---------------------------------------------------------------------
# set-up from the inside: span and compile records (ISSUE 35)
from lightgbm_tpu.observability import scopes as vocabulary  # noqa: E402
from lightgbm_tpu.observability.telemetry import _NULL_SPAN  # noqa: E402

_FUSED_PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
                 "tree_learner": "partitioned"}


def _fused_job(on: bool):
    """A small ``Dataset`` + ``Booster`` + the first iteration + two
    fused blocks of 2 trees with telemetry on (ring only) or off:
    ``(records, lowered text of the block, the model's text)``."""
    import jax.numpy as jnp
    tel = get_telemetry()
    tel.reset()
    if on:
        tel.ensure_ring()
    try:
        X, y = _toy(n=700)
        ds = lgb.Dataset(X, label=y, params=dict(_FUSED_PARAMS)).construct()
        bst = lgb.Booster(dict(_FUSED_PARAMS), ds)
        g = bst._gbdt
        g.train(1)
        g.train(3)
        g.train(5)
        records = tel.records       # the job's, not the lowering below
        text = g._fused_jit.lower(
            g.learner.mat, g.learner.ws, g.train_score,
            tuple(g.valid_scores), jnp.float32(g.shrinkage_rate),
            jnp.int32(g.iter), m=2).as_text()
        return records, text, bst.model_to_string()
    finally:
        tel.reset()


@pytest.fixture(scope="module")
def fused_jobs():
    mp = pytest.MonkeyPatch()
    mp.setenv("LGBM_TPU_FUSE_ITERS", "1")
    try:
        yield {"on": _fused_job(True), "off": _fused_job(False)}
    finally:
        mp.undo()


def _spans_of(records):
    return [r for r in records if r["kind"] == "span"]


@pytest.mark.parametrize("what", ["roots", "nesting", "vocabulary",
                                  "fields", "compiles", "compile_parents"])
def test_setup_ledger_of_a_small_job(fused_jobs, what):
    records = fused_jobs["on"][0]
    spans = _spans_of(records)
    compiles = [r for r in records if r["kind"] == "compile"]
    by_path = {}
    for r in spans:
        by_path.setdefault(r["path"], []).append(r)
    if what == "roots":
        assert len(by_path[vocabulary.DATA_CONSTRUCT]) == 1
        assert len(by_path[vocabulary.SETUP]) == 1
        # the first iteration's call and two blocks
        assert len(by_path[vocabulary.TRAIN]) == 3
        assert {r["path"].split("/")[0] for r in spans} == {
            vocabulary.DATA_CONSTRUCT, vocabulary.SETUP, vocabulary.TRAIN}
    elif what == "nesting":
        children = [r for r in spans if r["parent"] is not None]
        assert {r["name"] for r in children} >= {
            vocabulary.DATA_FIND_BINS, vocabulary.DATA_BIN_ROWS,
            vocabulary.SETUP_LEARNER, vocabulary.SETUP_DEVICE_TABLE,
            vocabulary.SETUP_OBJECTIVE, vocabulary.SETUP_SCORES}
        for r in children:
            assert r["path"] == r["parent"] + "/" + r["name"]
            (parent,) = by_path[r["parent"]]
            assert parent["t0"] <= r["t0"] <= r["t1"] <= parent["t1"]
    elif what == "vocabulary":
        assert {r["name"] for r in spans} <= set(vocabulary.LEDGER_SPANS)
        for r in spans:
            assert r["dur_s"] == pytest.approx(r["t1"] - r["t0"], abs=1e-5)
    elif what == "fields":
        (root,) = by_path[vocabulary.DATA_CONSTRUCT]
        assert (root["rows"], root["columns"], root["source"]) \
            == (700, 6, "numpy")
        (setup,) = by_path[vocabulary.SETUP]
        assert setup["rows"] == 700
        assert setup["learner"] == "PartitionedTreeLearner"
        (learner,) = [r for r in spans
                      if r["name"] == vocabulary.SETUP_LEARNER]
        assert learner["plan"].startswith("SplitStepPlan(")
        (table,) = [r for r in spans
                    if r["name"] == vocabulary.SETUP_DEVICE_TABLE]
        assert table["bytes"] > 0
    elif what == "compiles":
        block = [r for r in compiles if r["program"] == "gbdt_fused_block"]
        assert {r["stage"] for r in block} == {"trace", "lower", "backend"}
        for r in compiles:
            assert r["stage"] in ("trace", "lower", "backend")
            assert r["t1"] - r["t0"] == pytest.approx(r["dur_s"], abs=1e-5)
            if r["stage"] == "backend":
                assert r["cache"] in ("hit", "miss", "none")
            else:
                assert "cache" not in r
    else:
        # a compile is a child of whatever caused it
        for r in compiles:
            if r["program"] == "gbdt_fused_block":
                assert r["parent"] == "train/boosting"
            if r["program"] == "gbdt_grad":
                assert r["parent"].startswith("train/")


def test_the_program_is_the_same_with_telemetry_on_and_off(fused_jobs):
    _, text_on, model_on = fused_jobs["on"]
    records_off, text_off, model_off = fused_jobs["off"]
    assert records_off == []
    assert text_on == text_off and "while" in text_on
    assert model_on == model_off
    assert model_on.count("Tree=") == 5


def test_load_after_save_leaves_the_two_io_spans(tel, tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    tel.configure(summary=False)
    X, y = _toy(n=400)
    cfg = Config.from_params({"objective": "binary", "verbosity": -1})
    path = str(tmp_path / "t.bin")
    Dataset.from_numpy(X, cfg, label=y).save_binary(path)
    before = len(tel.records)
    loaded = Dataset.load_binary(path)
    assert loaded.num_data == 400
    saved = [r for r in _spans_of(tel.records[:before])
             if r["name"] == vocabulary.DATA_SAVE_BINARY]
    assert len(saved) == 1 and saved[0]["parent"] is None
    assert saved[0]["bytes"] == os.path.getsize(path)
    after = _spans_of(tel.records[before:])
    assert [r["name"] for r in after] == [vocabulary.DATA_LOAD_BINARY,
                                          vocabulary.DATA_CONSTRUCT]
    io, root = after
    assert io["parent"] == vocabulary.DATA_CONSTRUCT
    assert io["bytes"] == os.path.getsize(path)
    assert root["source"] == "binary" and root["rows"] == 400
    assert root["t0"] <= io["t0"] <= io["t1"] <= root["t1"]


@pytest.mark.parametrize("name", vocabulary.LEDGER_SPANS)
def test_a_ledger_span_is_the_null_span_when_off(tel, name):
    assert not tel.enabled
    assert tel.setup_span(name, rows=1) is _NULL_SPAN
    assert tel.span(name, ledger=True) is _NULL_SPAN
    with tel.setup_span(name) as sp:
        sp.set(bytes=1)
    assert tel.records == [] and tel.spans == {}


def test_the_span_stack_is_a_thread_its_own(tel):
    import threading
    tel.configure(summary=False)
    seen = {}

    def other():
        with tel.span("flusher", ledger=True):
            seen["path"] = tel.current_path()

    with tel.span("train"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert tel.current_path() == "train"
    assert seen["path"] == "flusher"
    (rec,) = _spans_of(tel.records)
    assert rec["path"] == "flusher" and rec["parent"] is None
    assert set(tel.spans) == {"train", "flusher"}


@pytest.mark.parametrize("case", ["hit", "miss", "none", "short",
                                  "registered", "renamed"])
def test_compile_records_from_jax_monitoring_events(tel, case):
    """The listener on made-up events, as jax 0.9.0 sends them: the
    plain cache event on the compiling thread just before the backend
    duration it belongs to."""
    import jax.monitoring as monitoring

    import lightgbm_tpu.models.gbdt  # noqa: F401  registers programs
    tel.configure(summary=False)
    backend = "/jax/core/compile/backend_compile_duration"
    if case in ("hit", "miss"):
        monitoring.record_event(
            f"/jax/compilation_cache/cache_{'hits' if case == 'hit' else 'misses'}")
    name, dur = {"short": ("jit(convert_element_type)", 0.004),
                 "registered": ("jit(gbdt_fused_block)", 0.004),
                 "renamed": ("jit(_bag_mask_jit)", 0.004)}.get(
                     case, ("jit(some_program)", 0.25))
    with tel.span("train"):
        monitoring.record_event_duration_secs(backend, dur, fun_name=name)
        # the slot is emptied: the next compile is nobody's hit
        monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_trace_duration", 0.2,
            fun_name="jit(next)")
    recs = [r for r in tel.records if r["kind"] == "compile"]
    assert tel.counters["jit.compiles"] == 1
    assert tel.counters["jit.compile_s"] == pytest.approx(dur)
    if case == "short":
        assert [r["program"] for r in recs] == ["next"]
        return
    first, second = recs
    assert first["stage"] == "backend" and first["parent"] == "train"
    assert first["program"] == {"registered": "gbdt_fused_block",
                                "renamed": "bag_mask"}.get(
                                    case, "some_program")
    assert first["cache"] == (case if case in ("hit", "miss") else "none")
    assert first["t1"] - first["t0"] == pytest.approx(dur)
    assert second["stage"] == "trace" and "cache" not in second
