"""Multi-host bootstrap + distributed bin finding
(parallel/distributed.py; Network::Init and
dataset_loader.cpp:824-1001 analogs).

Most tests emulate the second host with a fake ``process_allgather``
(hermetic, fast); ``test_two_process_data_parallel_training`` at the
bottom is the REAL thing — two spawned processes,
``jax.distributed.initialize`` over localhost, gloo CPU collectives,
one data-parallel model — and is ``slow``-marked accordingly.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel import distributed as dist


def test_parse_machines_string():
    cfg = Config.from_params({"machines": "10.0.0.1:12400,10.0.0.2:12400,"
                                          "10.0.0.3"})
    m = dist.parse_machines(cfg)
    assert m == [("10.0.0.1", 12400), ("10.0.0.2", 12400),
                 ("10.0.0.3", 12400)]


def test_parse_machines_file(tmp_path):
    p = tmp_path / "mlist.txt"
    p.write_text("10.1.0.1 12400\n10.1.0.2 12401\n\n10.1.0.3:12402\n")
    cfg = Config.from_params({"machine_list_filename": str(p)})
    m = dist.parse_machines(cfg)
    assert m == [("10.1.0.1", 12400), ("10.1.0.2", 12401),
                 ("10.1.0.3", 12402)]


def test_find_local_rank_env_override(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_RANK", "2")
    cfg = Config.from_params({})
    assert dist.find_local_rank(
        [("a", 1), ("b", 2), ("c", 3)], cfg) == 2


def test_find_local_rank_by_address(monkeypatch):
    monkeypatch.delenv("LIGHTGBM_TPU_RANK", raising=False)
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    cfg = Config.from_params({})
    machines = [("10.9.9.9", 12400), ("127.0.0.1", 12400)]
    assert dist.find_local_rank(machines, cfg) == 1


def test_find_local_rank_port_disambiguation(monkeypatch):
    monkeypatch.delenv("LIGHTGBM_TPU_RANK", raising=False)
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    cfg = Config.from_params({"local_listen_port": 12401})
    machines = [("127.0.0.1", 12400), ("127.0.0.1", 12401)]
    assert dist.find_local_rank(machines, cfg) == 1


def test_init_distributed_wires_jax(monkeypatch):
    calls = {}

    class FakeDist:
        @staticmethod
        def is_initialized():
            return False

        @staticmethod
        def initialize(coordinator_address, num_processes, process_id,
                       initialization_timeout):
            calls.update(addr=coordinator_address, n=num_processes,
                         pid=process_id, timeout=initialization_timeout)

    import jax
    monkeypatch.setattr(jax, "distributed", FakeDist)
    cfg = Config.from_params(
        {"machines": "10.0.0.1:12400,127.0.0.1:12400", "time_out": 5})
    assert dist.init_distributed(cfg) is True
    assert calls == {"addr": "10.0.0.1:12400", "n": 2, "pid": 1,
                     "timeout": 300}


def test_init_distributed_retries_transient_failures(monkeypatch):
    """Init flakes (coordinator not up yet) are retried with bounded
    backoff (robustness/retry.py) instead of failing the job."""
    calls = {"n": 0}

    class FlakyDist:
        @staticmethod
        def is_initialized():
            return False

        @staticmethod
        def initialize(**kw):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("connection refused (coordinator "
                                   "not listening yet)")

    import jax
    monkeypatch.setattr(jax, "distributed", FlakyDist)
    monkeypatch.setenv("LGBM_TPU_DIST_INIT_ATTEMPTS", "4")
    monkeypatch.setenv("LGBM_TPU_DIST_INIT_BACKOFF_S", "0.01")
    cfg = Config.from_params(
        {"machines": "10.0.0.1:12400,127.0.0.1:12400", "time_out": 1})
    assert dist.init_distributed(cfg) is True
    assert calls["n"] == 3


def test_init_distributed_single_machine_noop():
    cfg = Config.from_params({"machines": "127.0.0.1:12400"})
    assert dist.init_distributed(cfg) is False
    assert dist.init_distributed(Config.from_params({})) is False


def test_gather_bin_sample_single_process_identity():
    x = np.random.RandomState(0).randn(50, 4)
    np.testing.assert_array_equal(dist.gather_bin_sample(x), x)


def test_gather_bin_sample_multi_process(monkeypatch):
    """Emulate 2 hosts with unequal sample sizes via a fake
    process_allgather; the merged sample must be the concatenation."""
    import jax
    rng = np.random.RandomState(1)
    local = rng.randn(30, 3)
    other = rng.randn(20, 3)

    monkeypatch.setattr(dist, "_multi_process", lambda: True)

    def fake_allgather(x):
        x = np.asarray(x)
        if x.ndim == 1:  # the counts gather
            return np.stack([x, np.asarray([other.shape[0]])])
        pad = np.zeros((x.shape[0] - other.shape[0], x.shape[1]))
        return np.stack([x, np.concatenate([other, pad])])

    from jax.experimental import multihost_utils
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        fake_allgather)
    merged = dist.gather_bin_sample(local)
    np.testing.assert_array_equal(
        merged, np.concatenate([local, other]))


def test_distributed_bins_match_pooled_bins(monkeypatch):
    """Two pre-partitioned shards must derive the same BinMappers as a
    single host holding all the data — via the sample gather."""
    import jax
    from lightgbm_tpu.data.dataset import Dataset as InnerDataset

    rng = np.random.RandomState(3)
    full = rng.randn(600, 5)
    shard_a, shard_b = full[:300], full[300:]

    cfg = Config.from_params({"objective": "regression",
                              "pre_partition": True, "verbosity": -1})

    # host A's view: gather returns the full pooled sample
    monkeypatch.setattr(dist, "_multi_process", lambda: True)
    from jax.experimental import multihost_utils

    def fake_allgather(x):
        x = np.asarray(x)
        if x.ndim == 1:
            return np.stack([x, np.asarray([shard_b.shape[0]])])
        return np.stack([x, shard_b])

    monkeypatch.setattr(multihost_utils, "process_allgather",
                        fake_allgather)
    ds_a = InnerDataset.from_numpy(shard_a, cfg,
                                   label=np.zeros(300))

    monkeypatch.setattr(dist, "_multi_process", lambda: False)
    ds_full = InnerDataset.from_numpy(full, cfg, label=np.zeros(600))

    for j in range(5):
        ma = ds_a.feature_mapper(j)
        mf = ds_full.feature_mapper(j)
        np.testing.assert_allclose(ma.bin_upper_bound,
                                   mf.bin_upper_bound)


def test_distributed_sparse_bins_match_pooled_bins(monkeypatch):
    """Two pre-partitioned SPARSE shards must derive the same
    BinMappers as a single host holding all the data (VERDICT r3 #6:
    the sparse path previously binned per-host with a warning)."""
    import scipy.sparse as sp
    from lightgbm_tpu.data.dataset import Dataset as InnerDataset
    from lightgbm_tpu.parallel import distributed as dist2

    rng = np.random.RandomState(9)
    n, f = 800, 6
    dense = np.where(rng.rand(n, f) < 0.15,
                     rng.randn(n, f) * 3.0, 0.0)
    full = sp.csr_matrix(dense)
    shard_a, shard_b = full[:400], full[400:]

    cfg = Config.from_params({"objective": "regression",
                              "pre_partition": True, "verbosity": -1})

    # precompute host B's contribution exactly as the impl would
    csc_b = shard_b.tocsc()
    b_cols = []
    for j in range(f):
        colv = np.asarray(
            csc_b.data[csc_b.indptr[j]:csc_b.indptr[j + 1]], np.float64)
        b_cols.append(colv[np.abs(colv) > 1e-35])
    b_counts = np.asarray([len(c) for c in b_cols], np.int64)
    b_flat = np.concatenate(b_cols) if b_counts.sum() else \
        np.zeros(0, np.float64)
    b_meta = np.asarray([400, 400, len(b_flat)], np.int64)

    monkeypatch.setattr(dist2, "_multi_process", lambda: True)
    from jax.experimental import multihost_utils

    def fake_allgather(x):
        x = np.asarray(x)
        if x.shape == (3,):      # meta gather
            return np.stack([x, b_meta])
        if x.shape == (f,):      # per-feature counts gather
            return np.stack([x, b_counts])
        m = x.shape[0]           # padded flat-values gather
        bf = np.concatenate([b_flat, np.zeros(m - len(b_flat))])
        return np.stack([x, bf])

    monkeypatch.setattr(multihost_utils, "process_allgather",
                        fake_allgather)
    ds_a = InnerDataset.from_scipy(shard_a, cfg, label=np.zeros(400))

    monkeypatch.setattr(dist2, "_multi_process", lambda: False)
    ds_full = InnerDataset.from_scipy(full, cfg, label=np.zeros(n))

    assert ds_a.num_features == ds_full.num_features
    for j in range(f):
        ma, mf = ds_a.bin_mappers[j], ds_full.bin_mappers[j]
        np.testing.assert_allclose(ma.bin_upper_bound,
                                   mf.bin_upper_bound)
        assert ma.num_bin == mf.num_bin


# ---------------------------------------------------------------------
# Real multi-process coverage (VERDICT r5 weak #3): everything above
# fakes the collectives; this spawns two actual processes and — per
# ISSUE 14 — covers every unified-spec-layer mode (data / voting /
# feature), with the trained model additionally bit-equal to a
# SINGLE-process run over a 2-virtual-device mesh (rank = -1): same
# partition rules, same comm recipe, gloo DCN vs in-process ICI.

_CHILD_SRC = """
import os, sys, hashlib
rank, port, mode = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
solo = rank < 0
if solo:
    # single-process reference: one process, 2 virtual devices
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()
else:
    os.environ["LIGHTGBM_TPU_RANK"] = str(rank)
import numpy as np
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel import distributed as dist

params = {
    "objective": "regression", "num_leaves": 7, "tree_learner": mode,
    "num_machines": 2, "verbosity": -1, "metric": ""}
if not solo:
    params["machines"] = "127.0.0.1:%d,127.0.0.1:%d" % (port, port + 1)
cfg = Config.from_params(params)
if solo:
    assert dist.init_distributed(cfg) is False
else:
    assert dist.init_distributed(cfg) is True
import jax
assert jax.device_count() == 2, jax.device_count()
if not solo:
    assert jax.process_count() == 2, jax.process_count()

# row/feature/voting sharding over the 2-device mesh; histograms and
# packed winner buffers cross the process boundary via the comm
# recipe's collectives, so identical trees on both ranks (and vs the
# single-process mesh) prove the spec layer end to end
rng = np.random.RandomState(0)
X = rng.randn(400, 5).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1]).astype(np.float32)
from lightgbm_tpu.data.dataset import Dataset
from lightgbm_tpu.models.gbdt import GBDT
ds = Dataset.from_numpy(X, cfg, label=y)
b = GBDT(cfg, ds)
b.train(2)
b.finalize_trees()
h = hashlib.sha256()
for t in b.models:
    h.update(np.asarray(t.split_feature).tobytes())
    h.update(np.asarray(t.threshold_bin).tobytes())
    h.update(np.asarray(t.leaf_value, np.float64).tobytes())
pred = float(np.asarray(b.predict(X)).sum())
print("DIGEST %d %s %d %.6f" % (rank, h.hexdigest(), len(b.models),
                                pred), flush=True)
"""


def _free_port_pair() -> int:
    """Two adjacent free ports (coordinator + the rank-1 listen slot
    used only for rank disambiguation)."""
    for _ in range(32):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        if port % 2 == 0 and port < 65000:
            return port
    return 29512


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["data", "voting", "feature"])
def test_two_process_parallel_training(tmp_path, mode):
    """Two REAL processes per mode: jax.distributed.initialize on
    localhost, gloo CPU collectives, one tiny parallel model — both
    ranks must build bit-identical trees, and the model must ALSO be
    bit-equal to a single-process run over a 2-virtual-device mesh
    (the unified spec layer + comm recipe are process-topology-blind:
    the reduce-scatter/packed-gather traffic crosses gloo DCN in one
    case and stays in-process in the other)."""
    child = tmp_path / "dist_child.py"
    child.write_text(_CHILD_SRC)
    env = dict(os.environ)
    env.pop("LGBM_TPU_TELEMETRY", None)
    env.pop("LGBM_TPU_FAULTS", None)
    # init flakes (coordinator not listening yet / TIME_WAIT port) are
    # absorbed INSIDE init_distributed by the robustness retry wrapper
    # (robustness/retry.py: bounded attempts, logged jittered waits);
    # short backoff keeps the test fast when a retry does happen
    env["LGBM_TPU_DIST_INIT_ATTEMPTS"] = "4"
    env["LGBM_TPU_DIST_INIT_BACKOFF_S"] = "0.5"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    # one local device per process: strip the parent suite's 8-device
    # virtual-mesh flag, keep the AVX2 ISA cap
    env["XLA_FLAGS"] = "--xla_cpu_max_isa=AVX2"
    last = None
    for _attempt in range(2):  # one retry for a port race
        port = _free_port_pair()
        procs = [subprocess.Popen(
            [sys.executable, str(child), str(rank), str(port), mode],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True) for rank in range(2)]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=240)
                outs.append((p.returncode, out, err))
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.skip("distributed children hung (sandbox "
                        "networking); covered by the fake-collective "
                        "tests above")
        last = outs
        if all(rc == 0 for rc, _o, _e in outs):
            break
        joined = "\n".join(e for _rc, _o, e in outs)
        if "Failed to bind" in joined or "address already in use" \
                in joined.lower():
            continue  # port race: retry once on a fresh port
        break
    assert all(rc == 0 for rc, _o, _e in last), \
        [(rc, e[-2000:]) for rc, _o, e in last]
    digests = {}
    for _rc, out, _err in last:
        line = [ln for ln in out.splitlines()
                if ln.startswith("DIGEST")][-1]
        _tag, rank, digest, ntrees, pred = line.split()
        digests[int(rank)] = (digest, int(ntrees), float(pred))
    assert set(digests) == {0, 1}
    assert digests[0] == digests[1], digests
    assert digests[0][1] == 2  # both iterations produced real trees
    # single-process reference over the same 2-shard mesh (rank -1)
    solo = subprocess.run(
        [sys.executable, str(child), "-1", "0", mode],
        env=env, capture_output=True, text=True, timeout=240)
    assert solo.returncode == 0, solo.stderr[-2000:]
    line = [ln for ln in solo.stdout.splitlines()
            if ln.startswith("DIGEST")][-1]
    _tag, _rank, digest, ntrees, pred = line.split()
    assert (digest, int(ntrees), float(pred)) == digests[0], \
        (line, digests)


# ---------------------------------------------------------------------
# Elastic drill legs (ISSUE 19): the REAL 2-process kill/resume story.
# The full leg matrix (stall, drop_heartbeat, world-mismatch guard)
# runs in tools/elastic_drill.py — the CI elastic-drill job; this test
# keeps the four load-bearing legs in the tier-marked suite.

def _run_elastic_leg(child, workdir, leg, ckpt_dir, ranks, extra,
                     n_round, timeout=240):
    """Spawn the drill child once per rank; returns
    [(rank, rc, stdout, stderr)] or None on a sandbox hang."""
    import json as _json
    env = dict(os.environ)
    for k in ("LGBM_TPU_TELEMETRY", "LGBM_TPU_FAULTS"):
        env.pop(k, None)
    env["LGBM_TPU_DIST_INIT_ATTEMPTS"] = "4"
    env["LGBM_TPU_DIST_INIT_BACKOFF_S"] = "0.5"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["XLA_FLAGS"] = "--xla_cpu_max_isa=AVX2"
    for _attempt in range(2):  # one retry for a port race
        port = _free_port_pair()
        procs = [(r, subprocess.Popen(
            [sys.executable, str(child), str(r), str(port),
             str(ckpt_dir), str(n_round), _json.dumps(extra)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)) for r in ranks]
        results = []
        try:
            for r, p in procs:
                out, err = p.communicate(timeout=timeout)
                results.append((r, p.returncode, out, err))
        except subprocess.TimeoutExpired:
            for _r, p in procs:
                p.kill()
            return None
        joined = "\n".join(e for _r, _rc, _o, e in results)
        if "Failed to bind" in joined or "address already in use" \
                in joined.lower():
            continue
        return results
    return results


def _elastic_digest(results, leg):
    digests = {}
    for r, rc, out, err in results:
        assert rc == 0, (leg, r, rc, err[-2000:])
        line = [ln for ln in out.splitlines()
                if ln.startswith("DIGEST")][-1]
        _tag, _rank, digest, ntrees = line.split()
        digests[r] = (digest, int(ntrees))
    assert len(set(digests.values())) == 1, (leg, digests)
    return next(iter(digests.values()))


@pytest.mark.slow
def test_two_process_elastic_kill_and_resume(tmp_path):
    """The watchdog + coordinated-checkpoint story end to end: rank 1
    SIGKILLed mid-train -> rank 0 exits bounded with a classified
    ``peer_lost`` (no hung rank); ``resume=auto`` on the SAME machine
    list and an ``elastic_resume`` reshard onto ONE process must both
    train to a model byte-identical to the fault-free run."""
    import shutil
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.elastic_drill import CHILD_SRC, KILL_ITER, N_ROUND
    from tools.probe_taxonomy import classify_elastic_failure
    child = tmp_path / "elastic_child.py"
    child.write_text(CHILD_SRC)

    def leg(name, ckdir, ranks, extra, n_round=N_ROUND):
        res = _run_elastic_leg(child, tmp_path, name, ckdir, ranks,
                               extra, n_round)
        if res is None:
            pytest.skip("distributed children hung (sandbox "
                        "networking); covered by tools/elastic_drill.py"
                        " in CI")
        return res

    # 1. fault-free reference digest
    ref = _elastic_digest(
        leg("ref", tmp_path / "ck_ref", (0, 1), {}), "ref")
    assert ref[1] == N_ROUND

    # 2. kill rank 1 mid-train: rank 0 must exit (bounded by the
    # communicate timeout above == no hung rank) and classify the
    # failure; rank 1 shows the raw SIGKILL
    kill_ck = tmp_path / "ck_kill"
    res = leg("kill", kill_ck, (0, 1),
              {"faults": f"kill_rank@rank=1,iter={KILL_ITER}"})
    by_rank = {r: (rc, out, err) for r, rc, out, err in res}
    assert by_rank[1][0] == -9, by_rank[1]
    rc0, out0, err0 = by_rank[0]
    assert rc0 != 0, "rank 0 exited clean despite a dead peer"
    assert classify_elastic_failure(out0 + "\n" + err0) == \
        "peer_lost", (rc0, err0[-1500:])
    shrink_ck = tmp_path / "ck_shrink"
    shutil.copytree(kill_ck, shrink_ck)

    # 3. resume=auto on the same machine list -> byte-identical
    got = _elastic_digest(
        leg("resume", kill_ck, (0, 1), {}), "resume")
    assert got == ref, "same-list resume diverged from fault-free run"

    # 4. elastic 2 -> 1 reshard resume -> still byte-identical
    got = _elastic_digest(
        leg("shrink", shrink_ck, (-1,), {"elastic_resume": True}),
        "shrink")
    assert got == ref, "elastic reshard resume diverged"


def test_sync_bin_find_seed(monkeypatch):
    """application.cpp:96: cooperative bin finding syncs
    data_random_seed to the fleet minimum; serial learners and
    single-process runs are untouched."""
    from jax.experimental import multihost_utils
    base = {"machines": "10.0.0.1:1,127.0.0.1:2", "num_machines": 2,
            "data_random_seed": 7, "verbosity": -1}
    monkeypatch.setattr(dist, "_multi_process", lambda: True)
    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda x: np.stack([np.asarray(x), np.asarray([3])]))
    cfg = Config.from_params({**base, "tree_learner": "voting"})
    assert dist.sync_bin_find_seed(cfg) == 3
    assert cfg.data_random_seed == 3
    # serial learner: no sync even multi-process
    cfg = Config.from_params({**base, "tree_learner": "feature"})
    assert dist.sync_bin_find_seed(cfg) == 7
    # single process: no sync
    monkeypatch.setattr(dist, "_multi_process", lambda: False)
    cfg = Config.from_params({**base, "tree_learner": "data"})
    assert dist.sync_bin_find_seed(cfg) == 7
