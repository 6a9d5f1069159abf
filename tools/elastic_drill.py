"""Elastic distributed-training drill (the CI elastic gate).

Two REAL processes per leg — ``jax.distributed.initialize`` over
localhost, gloo CPU collectives — driven end to end through the fault
grammar (robustness/faults.py) and the collective watchdog
(robustness/elastic.py):

1. **reference** — fault-free 2-process run with coordinated
   checkpoints; its tree digest is the golden answer.
2. **kill**      — ``kill_rank@rank=1,iter=3``: rank 1 SIGKILLs itself
   mid-train (an unannounced pod preemption). Rank 0 must NOT hang:
   the watchdog declares ``peer_lost`` within the heartbeat timeout
   and the rank exits within the abort grace window.
3. **resume**    — same machine list again, ``resume=auto``: picks the
   newest full-quorum coordinated checkpoint and trains to completion
   **byte-identical** to the reference.
4. **shrink**    — the same checkpoint dir resumed by ONE process over
   a 2-virtual-device mesh with ``elastic_resume=true``: the N=2 -> M=1
   elastic reshard must also be byte-identical.
5. **guard**     — the shrink WITHOUT ``elastic_resume`` must die with
   the structured world-mismatch error (never a silent wrong-mesh
   resume).
6. **stall**     — ``stall_rank@rank=1,iter=3,ms=60000``: rank 1 wedges
   (alive, heartbeating, not progressing). Both ranks must abort
   classified ``collective_stall`` within the stall timeout.
7. **drop_hb**   — ``drop_heartbeat@rank=1``: rank 1 keeps training but
   goes silent; rank 0 must declare ``peer_lost`` and the abort
   broadcast must take rank 1 down too.

Artifacts land in the workdir (CI uploads it): per-rank telemetry
JSONL traces, per-rank stdout/stderr, ``watchdog_timeline.json`` (the
merged ``elastic`` records) and ``summary.json``.

Usage: python tools/elastic_drill.py [workdir]
"""

import json
import os
import shutil
import socket
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from probe_taxonomy import classify_elastic_failure  # noqa: E402

N_ROUND = 6
KILL_ITER = 3
LEG_TIMEOUT_S = 240

# the training child: rank >= 0 joins the 2-process world; rank == -1
# is the single-process elastic-resume case (2 virtual devices, so the
# mesh programs and padding match the 2-process run bit-for-bit)
CHILD_SRC = """
import json, os, sys, hashlib
rank, port = int(sys.argv[1]), int(sys.argv[2])
ckpt_dir, n_round = sys.argv[3], int(sys.argv[4])
extra = json.loads(sys.argv[5])
os.environ["JAX_PLATFORMS"] = "cpu"
solo = rank < 0
if solo:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()
else:
    os.environ["LIGHTGBM_TPU_RANK"] = str(rank)
import numpy as np
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel import distributed as dist

params = {
    "objective": "regression", "num_leaves": 7, "tree_learner": "data",
    "num_machines": 2, "verbosity": 0, "metric": "",
    "checkpoint_dir": ckpt_dir, "checkpoint_freq": 2,
    # drill-speed watchdog: detection must land in seconds, not the
    # production default minutes
    "elastic_heartbeat_ms": 100.0,
    "elastic_heartbeat_timeout_ms": 2000.0,
    "elastic_stall_timeout_ms": 60000.0,
    "elastic_abort_grace_ms": 1000.0,
    "elastic_barrier_s": 30.0,
}
params.update(extra)
if not solo:
    params["machines"] = "127.0.0.1:%d,127.0.0.1:%d" % (port, port + 1)
cfg = Config.from_params(params)
assert dist.init_distributed(cfg) is (not solo)

rng = np.random.RandomState(0)
X = rng.randn(400, 5).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1]).astype(np.float32)
from lightgbm_tpu import engine
from lightgbm_tpu.basic import Dataset
booster = engine.train(dict(params), Dataset(X, label=y),
                       num_boost_round=n_round, verbose_eval=False)
# hash the model text up to the parameters footer: the tree section is
# identical across legs, while params embed leg-specific paths/ports
text = booster.model_to_string().split("\\nparameters:")[0]
h = hashlib.sha256(text.encode())
print("DIGEST %d %s %d" % (rank, h.hexdigest(), booster.num_trees()),
      flush=True)
"""


def _free_port_pair() -> int:
    for _ in range(32):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        if port % 2 == 0 and port < 64000:
            return port
    return 29612


def _child_env(workdir: str, leg: str, rank: int) -> dict:
    env = dict(os.environ)
    env.pop("LGBM_TPU_FAULTS", None)
    env["LGBM_TPU_TELEMETRY"] = os.path.join(
        workdir, f"{leg}_rank{rank}.telemetry.jsonl")
    env["LGBM_TPU_DIST_INIT_ATTEMPTS"] = "4"
    env["LGBM_TPU_DIST_INIT_BACKOFF_S"] = "0.5"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["XLA_FLAGS"] = "--xla_cpu_max_isa=AVX2"
    return env


def _run_leg(workdir: str, child: str, leg: str, ckpt_dir: str,
             ranks, extra: dict, n_round: int = N_ROUND):
    """Spawn one child per rank, wait (bounded), persist artifacts.
    Returns [(rank, returncode, stdout, stderr), ...]."""
    port = _free_port_pair()
    procs = [(r, subprocess.Popen(
        [sys.executable, child, str(r), str(port), ckpt_dir,
         str(n_round), json.dumps(extra)],
        env=_child_env(workdir, leg, r), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)) for r in ranks]
    results = []
    for r, p in procs:
        try:
            out, err = p.communicate(timeout=LEG_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            for _r2, p2 in procs:
                p2.kill()
            raise SystemExit(
                f"FAIL[{leg}]: rank {r} still running after "
                f"{LEG_TIMEOUT_S}s — the watchdog did not bound the "
                "failure (hung rank)")
        for tag, text in (("out", out), ("err", err)):
            with open(os.path.join(workdir,
                                   f"{leg}_rank{r}.{tag}.log"),
                      "w") as fh:
                fh.write(text)
        results.append((r, p.returncode, out, err))
    return results


def _digest(results, leg: str) -> str:
    digests = {}
    for r, rc, out, err in results:
        assert rc == 0, (f"FAIL[{leg}]: rank {r} exited {rc}\n"
                         f"{err[-2000:]}")
        lines = [ln for ln in out.splitlines()
                 if ln.startswith("DIGEST")]
        assert lines, f"FAIL[{leg}]: rank {r} printed no DIGEST"
        _tag, _rank, digest, ntrees = lines[-1].split()
        assert int(ntrees) == N_ROUND, \
            f"FAIL[{leg}]: rank {r} built {ntrees}/{N_ROUND} trees"
        digests[r] = digest
    assert len(set(digests.values())) == 1, \
        f"FAIL[{leg}]: ranks disagree: {digests}"
    return next(iter(digests.values()))


def _assert_classified(results, leg: str, expect_reason: str,
                       surviving_ranks) -> None:
    """Every surviving rank must exit non-zero (bounded, not hung —
    the hang case already failed in _run_leg) with evidence the
    taxonomy classifies as ``expect_reason``."""
    by_rank = {r: (rc, out, err) for r, rc, out, err in results}
    for r in surviving_ranks:
        rc, out, err = by_rank[r]
        assert rc != 0, \
            f"FAIL[{leg}]: rank {r} exited 0 despite the injected fault"
        got = classify_elastic_failure(out + "\n" + err)
        assert got == expect_reason, (
            f"FAIL[{leg}]: rank {r} classified {got!r}, expected "
            f"{expect_reason!r}\n{err[-1500:]}")
        print(f"[{leg}] rank {r}: exit {rc}, classified "
              f"{expect_reason}")


def _collect_timeline(workdir: str) -> list:
    timeline = []
    for name in sorted(os.listdir(workdir)):
        if not name.endswith(".telemetry.jsonl"):
            continue
        with open(os.path.join(workdir, name)) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("kind") in ("elastic", "elastic_abort"):
                    timeline.append({"source": name, **rec})
    return timeline


def main() -> int:
    workdir = sys.argv[1] if len(sys.argv) > 1 else "elastic_drill_work"
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    child = os.path.join(workdir, "elastic_child.py")
    with open(child, "w") as fh:
        fh.write(CHILD_SRC)
    summary = {}

    # 1. fault-free reference: the golden digest
    ref_ck = os.path.join(workdir, "ck_ref")
    ref = _digest(_run_leg(workdir, child, "reference", ref_ck,
                           (0, 1), {}), "reference")
    summary["reference"] = {"digest": ref}
    print(f"[reference] 2-process digest {ref[:16]}…")

    # 2. kill drill: rank 1 dies unannounced at iteration 3 (one
    # coordinated checkpoint exists, at iteration 2); rank 0 must
    # abort bounded + classified, never hang
    kill_ck = os.path.join(workdir, "ck_kill")
    res = _run_leg(workdir, child, "kill", kill_ck, (0, 1),
                   {"faults": f"kill_rank@rank=1,iter={KILL_ITER}"})
    killed = {r: rc for r, rc, _o, _e in res}[1]
    assert killed == -9, \
        f"FAIL[kill]: rank 1 exited {killed}, expected SIGKILL (-9)"
    _assert_classified(res, "kill", "peer_lost", (0,))
    summary["kill"] = {"reason": "peer_lost"}
    # freeze the torn-at-iteration-2 state for the shrink legs before
    # the same-list resume writes newer checkpoints into kill_ck
    shrink_ck = os.path.join(workdir, "ck_shrink")
    shutil.copytree(kill_ck, shrink_ck)
    guard_ck = os.path.join(workdir, "ck_guard")
    shutil.copytree(kill_ck, guard_ck)

    # 3. resume=auto on the SAME machine list -> byte-identical
    got = _digest(_run_leg(workdir, child, "resume", kill_ck,
                           (0, 1), {}), "resume")
    assert got == ref, (f"FAIL[resume]: resumed digest {got[:16]}… != "
                        f"reference {ref[:16]}…")
    summary["resume"] = {"digest": got, "identical": True}
    print("[resume] same-list resume is byte-identical")

    # 4. elastic N=2 -> M=1 reshard resume -> still byte-identical
    got = _digest(_run_leg(workdir, child, "shrink", shrink_ck,
                           (-1,), {"elastic_resume": True}), "shrink")
    assert got == ref, (f"FAIL[shrink]: reshard digest {got[:16]}… != "
                        f"reference {ref[:16]}…")
    summary["shrink"] = {"digest": got, "identical": True}
    print("[shrink] 2->1 elastic reshard resume is byte-identical")

    # 5. the same reshard WITHOUT elastic_resume must be a structured
    # refusal naming both worlds, not a silent wrong-mesh resume
    ((_r, rc, _out, err),) = _run_leg(workdir, child, "guard",
                                      guard_ck, (-1,), {})
    assert rc != 0 and "world mismatch" in err, (
        f"FAIL[guard]: expected the structured world-mismatch error, "
        f"got exit {rc}\n{err[-1500:]}")
    summary["guard"] = {"refused": True}
    print("[guard] world-mismatch resume correctly refused")

    # 6. stall drill: rank 1 stays alive + heartbeating but wedges for
    # 60s; both ranks must classify collective_stall within ~2s
    res = _run_leg(workdir, child, "stall",
                   os.path.join(workdir, "ck_stall"), (0, 1),
                   {"faults": f"stall_rank@rank=1,iter={KILL_ITER},"
                              "ms=60000",
                    "elastic_stall_timeout_ms": 2000.0,
                    "elastic_abort_grace_ms": 500.0})
    _assert_classified(res, "stall", "collective_stall", (0, 1))
    summary["stall"] = {"reason": "collective_stall"}

    # 7. silent-rank drill: rank 1 trains on but stops heartbeating;
    # rank 0's peer_lost verdict must reach rank 1 via the abort
    # broadcast (both ranks down, both classified)
    res = _run_leg(workdir, child, "drop_hb",
                   os.path.join(workdir, "ck_drop"), (0, 1),
                   {"faults": "drop_heartbeat@rank=1"}, n_round=500)
    _assert_classified(res, "drop_hb", "peer_lost", (0, 1))
    summary["drop_hb"] = {"reason": "peer_lost"}

    timeline = _collect_timeline(workdir)
    with open(os.path.join(workdir, "watchdog_timeline.json"),
              "w") as fh:
        json.dump(timeline, fh, indent=1)
    aborts = [r for r in timeline if r.get("event") == "abort"]
    assert aborts, "no abort records reached the telemetry timeline"
    with open(os.path.join(workdir, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(f"PASS: elastic drill ({len(timeline)} timeline records, "
          f"{len(aborts)} classified aborts) — artifacts in {workdir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
