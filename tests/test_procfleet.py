"""Process-per-replica fleet isolation tests (serving/procfleet.py).

Acceptance gates from the isolation issue:
  * a process-mode fleet serves BIT-IDENTICAL results to host
    prediction of the published model text, across hot reloads;
  * SIGKILL-ing a worker mid-traffic loses ZERO requests: in-flight
    AND queued requests re-dispatch eagerly to survivors and the
    worker respawns warm within the backoff budget;
  * the crash_replica / hang_replica / oom_replica fault kinds are
    honored inside the worker and classified into the worker reason
    codes; a flapping replica is quarantined (health degrades, the
    pool never dies);
  * SIGTERM to the supervisor drains the workers and exits clean; a
    second signal escalates and still reaps the children (no
    orphans);
  * thread-mode `_mark_dead` covers futures still QUEUED in a dead
    replica's engines, not only in-flight ones (the satellite
    regression).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability.telemetry import get_telemetry
from lightgbm_tpu.robustness.faults import FaultPlan, set_fault_plan
from lightgbm_tpu.serving import (FleetEngine, ProcFleetOptions,
                                  ServingConfig)
from lightgbm_tpu.serving.procfleet import (STATE_CODES, recv_frame,
                                            send_frame)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _lock_order_guarded():
    # dynamic graftsync: every lock the supervisor/engines create is
    # instrumented; a lock-order inversion fails the module outright
    if os.environ.get("LGBM_SYNC_GUARDS", "1") == "0":
        yield
        return
    from tools.graftsync.runtime import lock_order_guard
    with lock_order_guard():
        yield


def _toy(n=400, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] > 0).astype(np.float64)
    return X, y


def _train(seed=0, leaves=7, rounds=6):
    X, y = _toy(seed=seed)
    return lgb.train({"objective": "binary", "num_leaves": leaves,
                      "verbosity": -1}, lgb.Dataset(X, label=y),
                     num_boost_round=rounds), X


@pytest.fixture
def started_with_cache(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR as a deployment sets it: before the
    process starts. The workers spawned by the test read it at their
    own jax import; this process imported jax long ago (with no cache,
    tests/conftest.py), so hand its jax the same directory for the
    test and take it back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from lightgbm_tpu.utils import compile_cache as cc
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    monkeypatch.setattr(cc, "_STATE", {"enabled_dir": None})
    jax.config.update("jax_compilation_cache_dir", str(cache))
    yield cache
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def _published_ref(bst, X):
    """Host prediction of the PUBLISHED artifact (model text) — the
    bit-parity reference for process-mode serving, same standard the
    pipeline ramp's parity watchdog uses."""
    return lgb.Booster(model_str=bst.model_to_string()).predict(X)


def _wait(cond, timeout=30.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except OSError:
        return False


# ----------------------------------------------------------------------
# wire framing
def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        payload = {"type": "submit", "id": 3,
                   "rows": [[0.1, -2.5e-17, 3.0]],
                   "meta": {"queue_ms": 0.25}}
        send_frame(a, payload)
        assert recv_frame(b) == payload
        # float64 round-trips exactly through the JSON framing (the
        # bit-parity guarantee of process mode rests on this)
        vals = [1.0 / 3.0, 1e-308, -0.0, 12345.678901234567]
        send_frame(a, {"v": vals})
        got = recv_frame(b)["v"]
        assert all(x == y and np.float64(x).tobytes()
                   == np.float64(y).tobytes()
                   for x, y in zip(vals, got))
        a.close()
        assert recv_frame(b) is None       # clean EOF -> None
    finally:
        a.close()
        b.close()


# ----------------------------------------------------------------------
# fault grammar: process-level kinds
def test_fault_grammar_replica_kinds():
    plan = FaultPlan.parse(
        "crash_replica@rid=2,signal=9;hang_replica@rid=0,ms=500;"
        "oom_replica@rid=1")
    assert [e.kind for e in plan.events] \
        == ["crash_replica", "hang_replica", "oom_replica"]
    # rid-matched: the wrong replica never takes the fault
    assert plan.take("crash_replica", rid=0) is None
    ev = plan.take("crash_replica", rid=2)
    assert ev is not None and ev.params["signal"] == 9
    # consumed-once: a second take does not re-fire
    assert plan.take("crash_replica", rid=2) is None
    assert plan.take("hang_replica", rid=0).params["ms"] == 500
    assert plan.take("oom_replica", rid=1) is not None
    assert plan.pending() == []


# ----------------------------------------------------------------------
# flight recorder: per-worker dump paths
def test_worker_dump_path_resolution(monkeypatch, tmp_path):
    from lightgbm_tpu.observability.flightrec import (resolve_dump_path,
                                                      worker_dump_path)
    assert worker_dump_path("/x/dump.json", 3) == "/x/dump.worker3.json"
    assert worker_dump_path("/x/dump", 0) == "/x/dump.worker0.json"
    base = str(tmp_path / "crash.json")
    monkeypatch.setenv("LGBM_TPU_CRASH_DUMP", base)
    monkeypatch.delenv("LGBM_TPU_WORKER_RID", raising=False)
    assert resolve_dump_path() == base
    # inside a worker process the SAME config resolves to its own file
    monkeypatch.setenv("LGBM_TPU_WORKER_RID", "2")
    assert resolve_dump_path() == str(tmp_path / "crash.worker2.json")


# ----------------------------------------------------------------------
# worker failure taxonomy
def test_classify_worker_failure_codes():
    sys.path.insert(0, REPO)
    from tools.probe_taxonomy import (WORKER_REASON_CODES,
                                      classify_worker_failure)
    assert classify_worker_failure("", exit_code=137) == "oom_killed"
    assert classify_worker_failure("", exit_code=-9) == "oom_killed"
    assert classify_worker_failure("", exit_code=-6) == "crashed"
    assert classify_worker_failure(
        "worker never said hello within 60s") == "spawn_failed"
    assert classify_worker_failure(
        "no frame from pid 123 for 3.2s") == "heartbeat_lost"
    assert classify_worker_failure(
        "replica 1 QUARANTINED (respawn_exhausted)") \
        == "respawn_exhausted"
    assert classify_worker_failure(
        "worker socket failed: broken pipe") == "socket_lost"
    for code in ("spawn_failed", "heartbeat_lost", "oom_killed",
                 "respawn_exhausted"):
        assert code in WORKER_REASON_CODES


# ----------------------------------------------------------------------
# config params
def test_config_isolation_params():
    from lightgbm_tpu.config import Config
    cfg = Config.from_params({"serving_isolation": "process",
                              "replica_restart_max": 2,
                              "replica_heartbeat_ms": 50})
    assert cfg.serving_isolation == "process"
    assert cfg.replica_restart_max == 2
    # aliases
    assert Config.from_params(
        {"isolation": "process"}).serving_isolation == "process"
    with pytest.raises(ValueError):
        Config.from_params({"serving_isolation": "container"})
    with pytest.raises(ValueError):
        Config.from_params({"replica_restart_max": -1})
    with pytest.raises(ValueError):
        Config.from_params({"replica_heartbeat_ms": 0})
    opts = ProcFleetOptions.from_config(cfg)
    assert opts.restart_max == 2 and opts.heartbeat_ms == 50


# ----------------------------------------------------------------------
# run_report: replica lifecycle timeline
def test_run_report_replica_timeline():
    sys.path.insert(0, REPO)
    from tools.run_report import digest, render
    records = [
        {"kind": "replica", "t": 0.1, "rid": 0, "event": "ready",
         "state": "ok", "pid": 100, "incarnation": 1,
         "ready_ms": 2500.0},
        {"kind": "replica", "t": 5.0, "rid": 0, "event": "dead",
         "state": "dead", "incarnation": 1,
         "reason_code": "oom_killed", "detail": "exited with -9"},
        {"kind": "replica", "t": 8.0, "rid": 0, "event": "respawned",
         "state": "ok", "incarnation": 2, "restarts": 1,
         "ready_ms": 1800.0},
        {"kind": "replica", "t": 9.0, "rid": 1, "event": "quarantined",
         "state": "quarantined", "reason_code": "respawn_exhausted"},
    ]
    d = digest(records)
    tl = d["replica_timeline"]
    assert len(tl) == 4
    assert tl[1]["reason_code"] == "oom_killed"
    text = render(records)
    assert "replica lifecycle" in text
    assert "oom_killed" in text and "respawn_exhausted" in text
    assert "death modes:" in text


# ----------------------------------------------------------------------
# satellite regression: _mark_dead must recover QUEUED futures too
def test_mark_dead_redispatches_queued_futures(monkeypatch):
    """A replica discovered dead through the submit path (_mark_dead,
    not kill_replica) used to leave requests queued in its engines to
    rot until the caller timeout; they must fail + re-dispatch
    eagerly. Kill with a FULL queue, assert zero lost requests."""
    monkeypatch.setenv("LGBM_TPU_PREDICT_DEVICE_MIN_CELLS", "0")
    bst, X = _train()
    fl = FleetEngine(models={"alpha": bst},
                     config=ServingConfig(buckets=(4,), warmup=False,
                                          flush_interval_ms=500.0,
                                          request_timeout_ms=30000),
                     replicas=2, default_model="alpha")
    try:
        futs = [fl.submit(X[i:i + 1]) for i in range(10)]
        victim = futs[0]._replica
        queued = [f for f in futs if f._replica is victim]
        assert queued, "victim took no requests"
        # the discovery path: NOT kill_replica — the fleet merely
        # learns the replica is dead (as _dispatch does on a failed
        # submit); every queued future must still be recovered
        fl._mark_dead(victim)
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(timeout=30),
                                          bst.predict(X[i:i + 1]))
        st = fl.stats()
        assert st["errors"] == 0
        assert st["redispatches"] >= len(queued)
        assert all(f.meta["replica"] != victim.rid for f in queued)
    finally:
        fl.stop()


# ----------------------------------------------------------------------
# the process-fleet acceptance suite (real worker subprocesses; one
# shared fleet keeps the spawn bill bounded). Marked slow: every
# worker pays a full interpreter + JAX import, which busts the tier-1
# wall budget on a small box — CI's full `test` job and the
# `chaos-soak` drill run these on every push.
@pytest.fixture(scope="module")
def proc_fleet():
    alpha, X = _train()
    beta, _ = _train(seed=11, leaves=5, rounds=4)
    fl = FleetEngine(
        models={"alpha": alpha, "beta": beta},
        config=ServingConfig(buckets=(4, 16), device="never",
                             flush_interval_ms=1.0,
                             request_timeout_ms=30000),
        replicas=2, default_model="alpha", isolation="process",
        proc_opts=ProcFleetOptions(heartbeat_ms=50,
                                   heartbeat_timeout_ms=2000,
                                   spawn_timeout_s=90,
                                   backoff_base_s=0.05,
                                   restart_max=5))
    yield fl, alpha, beta, X
    fl.stop()


@pytest.mark.slow
def test_process_fleet_parity_and_reload(proc_fleet):
    fl, alpha, beta, X = proc_fleet
    assert all(r.state == "ok" and r.pid for r in fl.replicas)
    for n in (1, 3, 16):
        np.testing.assert_array_equal(
            fl.predict(X[:n], model="alpha"),
            _published_ref(alpha, X[:n]))
        np.testing.assert_array_equal(
            fl.predict(X[:n], model="beta"),
            _published_ref(beta, X[:n]))
    np.testing.assert_array_equal(
        fl.predict(X[:4], model="alpha", kind="raw_score"),
        lgb.Booster(model_str=alpha.model_to_string()).predict(
            X[:4], raw_score=True))
    # hot reload broadcasts to every worker
    gamma, _ = _train(seed=9, leaves=9, rounds=5)
    v = fl.reload(gamma, model="alpha")
    assert v == 2
    np.testing.assert_array_equal(fl.predict(X[:5], model="alpha"),
                                  _published_ref(gamma, X[:5]))
    assert fl.stats()["errors"] == 0
    assert fl.health()["isolation"] == "process"


@pytest.mark.slow
def test_process_fleet_sigkill_zero_lost_and_respawn(proc_fleet,
                                                     tmp_path,
                                                     monkeypatch):
    fl, alpha, beta, X = proc_fleet
    from lightgbm_tpu.observability import flightrec
    dump_base = str(tmp_path / "crash.json")
    monkeypatch.setenv("LGBM_TPU_CRASH_DUMP", dump_base)
    rec = flightrec.FlightRecorder(dump_base)
    flightrec._ACTIVE[0] = rec
    try:
        futs = [fl.submit(X[i:i + 1], model="beta") for i in range(12)]
        victim = futs[0]._replica
        old_pid = victim.pid
        restarts0 = victim.restarts
        os.kill(old_pid, signal.SIGKILL)      # a REAL crash, no frame
        ref = _published_ref(beta, X)
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(timeout=30),
                                          ref[i:i + 1])
        st = fl.stats()
        assert st["errors"] == 0, "requests were lost in the kill"
        # the supervisor classified the SIGKILL and collected the
        # death into the parent's flight-recorder artifact
        assert _wait(lambda: victim.last_death.get("reason_code")
                     == "oom_killed", 20)
        assert _wait(lambda: os.path.exists(dump_base), 10)
        with open(dump_base) as fh:
            dump = json.load(fh)
        assert any(w["rid"] == victim.rid
                   for w in dump["worker_dumps"])
        # respawned warm within the backoff budget, new incarnation
        assert _wait(lambda: victim.state == "ok", 30)
        assert victim.restarts == restarts0 + 1
        assert victim.pid != old_pid
        assert victim.restart_ready_ms is not None
        np.testing.assert_array_equal(
            fl.predict(X[:5], model="beta"), ref[:5])
        # zero steady-state recompiles after the warm respawn: traffic
        # through the respawned worker compiles nothing new
        before = (victim.stats_lite() or {}).get("jit_compiles")
        for _ in range(3):
            fl.predict(X[:8], model="alpha")
        _wait(lambda: victim.stats_lite().get("jit_compiles")
              is not None, 10)
        after = (victim.stats_lite() or {}).get("jit_compiles")
        if before is not None and after is not None:
            assert after == before, \
                "steady-state traffic recompiled after respawn"
        assert fl.stats().get("replica_restarts", 0) >= 1
    finally:
        flightrec._ACTIVE[0] = None


@pytest.mark.slow
def test_process_fleet_fault_grammar_honored(proc_fleet):
    """crash_replica armed in the supervisor's plan is delivered to
    (and honored inside) the worker; consumed-once survives the
    respawn — the new incarnation does NOT re-crash."""
    fl, alpha, beta, X = proc_fleet
    assert _wait(lambda: all(r.state == "ok" for r in fl.replicas), 40)
    victim = fl.replicas[1]
    inc0 = victim.incarnation
    plan = set_fault_plan(f"crash_replica@rid={victim.rid},signal=9")
    try:
        assert _wait(lambda: victim.incarnation > inc0
                     and victim.state == "ok", 40), \
            f"state={victim.state} inc={victim.incarnation}"
        assert plan.pending() == []           # fired exactly once
        # traffic flows after the self-inflicted crash healed
        np.testing.assert_array_equal(
            fl.predict(X[:3], model="beta"),
            _published_ref(beta, X[:3]))
    finally:
        set_fault_plan(None)


@pytest.mark.slow
def test_rejected_publish_keeps_respawn_state_clean(proc_fleet):
    """A rejected publish (torn/invalid model) must keep previous
    versions serving AND leave the supervisor's respawn replay state
    on the last good source: a worker that dies AFTER the rejection
    replays the good model and comes back ok. (Regression: the replay
    frame used to be recorded before validation, so every respawn
    replayed the bad source until the replica was quarantined.)"""
    fl, alpha, beta, X = proc_fleet
    assert _wait(lambda: all(r.state == "ok" for r in fl.replicas), 40)
    sup = fl._proc_supervisor
    good = dict(sup._model_state["beta"])
    with pytest.raises(Exception):
        fl.reload("/no/such/model.txt", model="beta")
    assert fl._last_reload_error is not None
    assert sup._model_state["beta"] == good, \
        "rejected publish poisoned the respawn replay state"
    ref = _published_ref(beta, X)
    np.testing.assert_array_equal(
        fl.predict(X[:4], model="beta"), ref[:4])
    # a death after the rejection heals: the respawn replays the GOOD
    # state (the old bug spawn-failed on replay, every time)
    victim = fl.replicas[0]
    inc0 = victim.incarnation
    os.kill(victim.pid, signal.SIGKILL)
    assert _wait(lambda: victim.state == "ok"
                 and victim.incarnation > inc0, 40), \
        f"state={victim.state} last_death={victim.last_death}"
    np.testing.assert_array_equal(
        fl.predict(X[:4], model="beta"), ref[:4])


@pytest.mark.slow
def test_warm_respawn_zero_compiles_cache_armed(started_with_cache):
    """The acceptance bar for respawn cost: a respawned worker warms
    with ZERO compiles, serves bit-identically, compiles nothing in
    steady state, and has the persistent compile cache ARMED
    (reported over the wire). Booster publishes now also ship an AOT
    artifact (serving/aot.py), so the respawn replays the device
    route's executables too — test_aot_publish_zero_retrace_parity_
    and_shm pins that path explicitly."""
    cache = started_with_cache
    bst, X = _train()
    fl = FleetEngine(
        models={"alpha": bst},
        config=ServingConfig(buckets=(4,), device="always",
                             flush_interval_ms=1.0,
                             request_timeout_ms=30000),
        replicas=1, default_model="alpha", isolation="process",
        proc_opts=ProcFleetOptions(heartbeat_ms=50,
                                   heartbeat_timeout_ms=2000,
                                   spawn_timeout_s=90,
                                   backoff_base_s=0.05,
                                   restart_max=3))
    try:
        rep = fl.replicas[0]
        assert rep.cold_start_compiles == 0
        out0 = np.asarray(fl.predict(X[:4]))
        assert _wait(lambda: rep.stats_lite().get("compile_cache")
                     == str(cache), 10), rep.stats_lite()
        inc0 = rep.incarnation
        os.kill(rep.pid, signal.SIGKILL)
        assert _wait(lambda: rep.state == "ok"
                     and rep.incarnation > inc0, 60)
        # warm respawn: zero compiles paid, bit parity preserved
        assert rep.cold_start_compiles == 0, rep.describe()
        np.testing.assert_array_equal(np.asarray(fl.predict(X[:4])),
                                      out0)
        assert _wait(lambda: rep.stats_lite().get("compile_cache")
                     == str(cache), 10), rep.stats_lite()
        base = rep.stats_lite().get("jit_compiles")
        for _ in range(3):
            fl.predict(X[:4])
        after = rep.stats_lite().get("jit_compiles")
        if base is not None and after is not None:
            assert after == base, "steady-state recompiles after " \
                "warm respawn"
    finally:
        fl.stop()


# ----------------------------------------------------------------------
# AOT publish + shared-memory transport acceptance (the zero-Python
# serving hot path): a text publish with a dataset-backed donor ships
# an AOT artifact; the worker serves the DEVICE route from replayed
# executables with zero retraces across warm-up, steady state and a
# respawn, stays bit-identical to host prediction of the published
# text, and large batches travel over the shm ring
def test_config_aot_shm_params():
    from lightgbm_tpu.config import Config
    cfg = Config.from_params({"serving_aot": False,
                              "serving_shm_slots": 8,
                              "serving_shm_min_bytes": 0,
                              "serving_quota_unit": "bytes"})
    assert cfg.serving_aot is False and cfg.serving_shm_slots == 8
    assert Config.from_params({"shm": False}).serving_shm is False
    assert Config.from_params({"aot": False}).serving_aot is False
    with pytest.raises(ValueError):
        Config.from_params({"serving_shm_slots": 0})
    with pytest.raises(ValueError):
        Config.from_params({"serving_shm_slot_bytes": 16})
    opts = ProcFleetOptions.from_config(cfg)
    assert opts.shm_slots == 8 and opts.shm_min_bytes == 0
    from lightgbm_tpu.serving.engine import ServingConfig as SC
    assert SC.from_config(cfg).aot is False


@pytest.mark.slow
def test_aot_publish_zero_retrace_parity_and_shm(started_with_cache):
    """Acceptance: process-mode serving of an AOT-published model does
    ZERO retraces after replay (compile counter flat across warm-up,
    steady state and one respawn) AND stays bit-identical to host
    prediction of the same model text; batches >= shm_min_bytes
    travel the shm ring, oversized ones fall back to JSON framing
    with identical results."""
    cache = started_with_cache
    bst, X = _train()
    text = bst.model_to_string()
    ref = _published_ref(bst, X)
    fl = FleetEngine(
        config=ServingConfig(buckets=(1, 16, 64), device="always",
                             flush_interval_ms=1.0,
                             request_timeout_ms=30000),
        replicas=1, default_model="m", isolation="process",
        proc_opts=ProcFleetOptions(heartbeat_ms=50,
                                   heartbeat_timeout_ms=3000,
                                   spawn_timeout_s=90,
                                   backoff_base_s=0.05, restart_max=3,
                                   shm=True, shm_min_bytes=1024,
                                   shm_slot_bytes=16384))
    try:
        # publish-time AOT: the parent compiles the bucket programs
        # into the shared persistent cache and ships the artifact
        fl.load_model("m", text, aot_booster=bst)
        assert fl._counts.get("aot_publishes") == 1
        rep = fl._proc_supervisor._replicas[0]
        assert rep.aot_models.get("m") is True, rep.describe()

        # warm-up + steady state: bit parity, zero compiles
        np.testing.assert_array_equal(
            np.asarray(fl.predict(X[:64])), ref[:64])
        np.testing.assert_array_equal(
            np.asarray(fl.predict(X[:1])), ref[:1])
        base = rep.stats_lite().get("jit_compiles")
        for i in range(3):
            fl.predict(X[i:i + 16])
        after = rep.stats_lite().get("jit_compiles")
        if base is not None and after is not None:
            assert after == base, "steady-state retraces on the " \
                "AOT route"

        # the 64-row batch (4 KiB) rode the ring; single rows stayed
        # on JSON framing (below shm_min_bytes)
        shm = rep.describe()["shm"]
        assert shm is not None and shm["writes"] >= 1, shm

        # oversized batch: > slot_bytes falls back to JSON framing
        # transparently, bit-identically
        big = np.repeat(X, 8, axis=0)[:2048]          # 128 KiB
        assert big.nbytes > 16384
        np.testing.assert_array_equal(
            np.asarray(fl.predict(big)), _published_ref(bst, big))
        shm = rep.describe()["shm"]
        assert shm["oversize_misses"] + shm["fallbacks"] >= 1, shm

        # respawn: the worker replays the artifact from the model
        # frame and the executables from the persistent cache — zero
        # compiles, AOT route still live, parity preserved
        inc0, pid0 = rep.incarnation, rep.pid
        os.kill(pid0, signal.SIGKILL)
        assert _wait(lambda: rep.state == "ok"
                     and rep.incarnation > inc0, 60), rep.describe()
        assert rep.cold_start_compiles == 0, rep.describe()
        assert rep.aot_models.get("m") is True, rep.describe()
        assert rep.restart_ready_ms is not None
        np.testing.assert_array_equal(
            np.asarray(fl.predict(X[:64])), ref[:64])
        assert fl.stats()["errors"] == 0
    finally:
        fl.stop()


@pytest.mark.slow
def test_aot_disabled_still_serves_host_route(tmp_path, monkeypatch):
    """serving_aot=False publishes plain text: no artifact, host
    route, same results — the opt-out is a clean degrade."""
    bst, X = _train()
    fl = FleetEngine(
        config=ServingConfig(buckets=(4,), device="always",
                             flush_interval_ms=1.0,
                             request_timeout_ms=30000, aot=False),
        replicas=1, default_model="m", isolation="process",
        proc_opts=ProcFleetOptions(heartbeat_ms=50,
                                   heartbeat_timeout_ms=3000,
                                   spawn_timeout_s=90,
                                   backoff_base_s=0.05,
                                   restart_max=3))
    try:
        fl.load_model("m", bst.model_to_string(), aot_booster=bst)
        assert fl._counts.get("aot_publishes") is None
        rep = fl._proc_supervisor._replicas[0]
        assert rep.aot_models.get("m") is False
        np.testing.assert_array_equal(
            np.asarray(fl.predict(X[:4])),
            _published_ref(bst, X[:4]))
    finally:
        fl.stop()


@pytest.mark.slow
def test_quarantine_after_restart_budget():
    """A flapping replica exhausts replica_restart_max and is
    QUARANTINED: health degrades, the pool keeps serving."""
    bst, X = _train()
    fl = FleetEngine(
        models={"alpha": bst},
        config=ServingConfig(buckets=(4,), device="never",
                             flush_interval_ms=1.0,
                             request_timeout_ms=30000),
        replicas=2, default_model="alpha", isolation="process",
        proc_opts=ProcFleetOptions(heartbeat_ms=50,
                                   heartbeat_timeout_ms=2000,
                                   spawn_timeout_s=90,
                                   backoff_base_s=0.05,
                                   restart_max=1,
                                   flap_reset_s=3600.0))
    try:
        victim = fl.replicas[0]
        os.kill(victim.pid, signal.SIGKILL)
        assert _wait(lambda: victim.state == "ok"
                     and victim.restarts == 1, 40)
        os.kill(victim.pid, signal.SIGKILL)
        assert _wait(lambda: victim.state == "quarantined", 40), \
            victim.describe()
        h = fl.health()
        assert h["status"] == "degraded"
        assert h["replicas_quarantined"] == 1
        # the pool never dies: the survivor answers
        np.testing.assert_array_equal(
            fl.predict(X[:4]), _published_ref(bst, X[:4]))
        assert fl.stats().get("replica_quarantines", 0) == 1
        from lightgbm_tpu.observability.metrics import get_metrics
        gauges = get_metrics().labeled_gauges(
            prefix="lgbm_fleet_replica_state")
        key = ('lgbm_fleet_replica_state'
               f'{{rid="{victim.rid}"}}')
        assert gauges.get(key) == STATE_CODES["quarantined"]
    finally:
        fl.stop()
    # stop reaped everything: no orphan worker processes
    for rep in fl.replicas:
        if rep.pid:
            assert not _pid_alive(rep.pid)


# ----------------------------------------------------------------------
# preemption: SIGTERM drains workers; second signal escalates + reaps
_PREEMPT_SCRIPT = r"""
import os, sys, time, json
sys.path.insert(0, {repo!r})
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.robustness.preempt import PreemptionGuard
from lightgbm_tpu.serving import (FleetEngine, ProcFleetOptions,
                                  ServingConfig)
rng = np.random.RandomState(0)
X = rng.randn(200, 6)
y = (X[:, 0] > 0).astype(np.float64)
bst = lgb.train({{"objective": "binary", "num_leaves": 5,
                  "verbosity": -1}}, lgb.Dataset(X, label=y),
                num_boost_round=3)
guard = PreemptionGuard().install()   # BEFORE READY: the test's
assert guard.installed                # SIGTERM races the handshake
fl = FleetEngine(models={{"m": bst}},
                 config=ServingConfig(buckets=(4,), device="never",
                                      flush_interval_ms=1.0),
                 replicas=1, default_model="m", isolation="process",
                 proc_opts=ProcFleetOptions(heartbeat_ms=50,
                                            spawn_timeout_s=90))
with open({pidfile!r}, "w") as fh:
    json.dump([r.pid for r in fl.replicas], fh)
print("READY", flush=True)
futs = [fl.submit(X[i:i+1]) for i in range(4)]
while not guard.requested:
    time.sleep(0.02)
if {hang!r} == "hang":
    while True:                   # a wedged loop: only escalation
        time.sleep(0.5)           # (second signal) can end this
# graceful path: finish in-flight work, drain workers, exit clean
for f in futs:
    f.result(timeout=30)
fl.stop(drain=True)
guard.uninstall()
print("CLEAN", flush=True)
"""


def _run_preempt_child(tmp_path, hang):
    pidfile = str(tmp_path / f"workers_{hang}.json")
    script = _PREEMPT_SCRIPT.format(repo=REPO, pidfile=pidfile,
                                    hang=hang)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    # wait for the fleet (worker spawned, pidfile written)
    out_lines = []
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        out_lines.append(line)
        if "READY" in line:
            break
        if proc.poll() is not None:
            raise AssertionError("child died early:\n"
                                 + "".join(out_lines))
    with open(pidfile) as fh:
        worker_pids = json.load(fh)
    assert worker_pids and all(_pid_alive(p) for p in worker_pids)
    return proc, worker_pids


@pytest.mark.slow
def test_preempt_sigterm_drains_workers_clean(tmp_path):
    proc, worker_pids = _run_preempt_child(tmp_path, hang="clean")
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=90)
    assert proc.returncode == 0, out
    assert "CLEAN" in out
    # every worker process drained and exited — no orphans
    assert _wait(lambda: not any(_pid_alive(p) for p in worker_pids),
                 15), f"orphan workers: {worker_pids}"


@pytest.mark.slow
def test_preempt_second_signal_escalates_and_reaps(tmp_path):
    proc, worker_pids = _run_preempt_child(tmp_path, hang="hang")
    proc.send_signal(signal.SIGTERM)     # flag set; loop is wedged
    time.sleep(1.0)
    assert proc.poll() is None           # still hung (first signal
    proc.send_signal(signal.SIGTERM)     # only flags); now escalate
    try:
        proc.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise AssertionError("second SIGTERM did not end the child")
    assert proc.returncode != 0          # escalated, not graceful
    # the escalation cleanup still reaped the children
    assert _wait(lambda: not any(_pid_alive(p) for p in worker_pids),
                 15), f"orphan workers after escalation: {worker_pids}"


# ----------------------------------------------------------------------
# kill-storm soak through the shared loadgen (thread-mode fallback of
# inject_replica_fault keeps the chaos lever isolation-agnostic)
def test_soak_kill_storm_thread_fallback():
    from lightgbm_tpu.serving.loadgen import soak_loop
    bst, X = _train()
    fl = FleetEngine(models={"alpha": bst},
                     config=ServingConfig(buckets=(4,), warmup=False,
                                          flush_interval_ms=1.0),
                     replicas=3, default_model="alpha")
    try:
        block = soak_loop(fl, X, duration_s=1.2, qps=80,
                          batch_sizes=(1,), models=["alpha"],
                          timeout_ms=20000,
                          kill_storm_every_s=0.3)
        assert block["fault_storms"] >= 1
        assert block["non_shed_errors"] == 0
        assert block["availability"] == 1.0
        assert block["isolation"] == "thread"
    finally:
        fl.stop()


@pytest.mark.slow
def test_telemetry_replica_records_emitted(proc_fleet):
    tel = get_telemetry()
    recs = [r for r in tel.records if r.get("kind") == "replica"] \
        if tel.enabled else []
    if not tel.enabled:
        pytest.skip("telemetry ring not armed in this run")
    assert any(r.get("event") in ("ready", "respawned") for r in recs)


def test_shutdown_interrupts_monitor_wait():
    # graftsync GS302 regression: _monitor_loop used to tick via bare
    # time.sleep(interval), so shutdown() on a long heartbeat waited
    # out the sleep. The stop event must interrupt it.
    from lightgbm_tpu.serving.procfleet import WorkerSupervisor

    class _FleetStub:  # weakref-able stand-in; no replicas spawn
        pass

    stub = _FleetStub()
    sup = WorkerSupervisor(stub, ProcFleetOptions(heartbeat_ms=30000))
    try:
        t0 = time.monotonic()
        sup.shutdown(drain=False)
        assert time.monotonic() - t0 < 5.0
        sup._monitor_thread.join(timeout=5.0)
        assert not sup._monitor_thread.is_alive()
    finally:
        sup.shutdown(drain=False)
