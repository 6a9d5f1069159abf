"""bench.py prints a valid result line when a device answers, and
exits non-zero with no device-metric line when none does.

Exercises the measurement child directly at a tiny size on CPU
(``BENCH_ALLOW_CPU=1`` is the test hook that lets main() accept the
CPU backend; its lines say ``"backend": "cpu"``) and the result-line
parser.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_main_probe_and_pinned_plan(tmp_path):
    """Full main() flow: the probe child (accepts the forced CPU
    backend under BENCH_ALLOW_CPU), the pinned-size plan, the
    measuring child's result line passed through whole, and the
    telemetry JSONL written next to the JSON output."""
    env = dict(os.environ)
    tel_path = str(tmp_path / "bench_telemetry.jsonl")
    env.update(JAX_PLATFORMS="cpu",
               BENCH_ROWS="3000", BENCH_FEATURES="6",
               BENCH_LEAVES="7", BENCH_ITERS="1",
               BENCH_WARMUP_ITERS="1", BENCH_BUDGET_S="500",
               BENCH_MIN_AUC="0.4", BENCH_ALLOW_CPU="1",
               LGBM_TPU_TELEMETRY=tel_path)
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_cpu_max_isa=AVX2").strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], env=env,
        capture_output=True, text=True, timeout=570)
    assert proc.returncode == 0, proc.stderr[-2000:]
    sys.path.insert(0, REPO)
    from bench import find_result_line
    line = find_result_line(proc.stdout)
    assert line is not None, proc.stdout[-2000:]
    assert line["metric"] == "higgs_like_train_throughput"
    assert line["unit"] == "Mrow-iters/s"
    assert line["value"] > 0
    assert line["vs_baseline"] > 0
    assert line["rows"] == 3000
    assert line["num_leaves"] == 7
    assert line["backend"] == "cpu" and "roofline" not in line
    assert 0.4 < line["auc"] <= 1.0   # default-on quality gate ran
    assert line["quality_ok"] is True
    # compile-vs-steady-state provenance (observability layer)
    assert line["compile_count"] > 0
    assert line["compile_s"] > 0
    assert line["warmup_s"] > 0 and line["steady_s"] > 0
    assert line["compile_in_timed_s"] <= line["compile_s"]
    # the driver parses the LAST json line; make sure serialization
    # round-trips
    assert json.loads(json.dumps(line)) == line
    with open(tel_path) as fh:
        kinds = {json.loads(ln)["kind"] for ln in fh if ln.strip()}
    assert {"run_start", "train_end"} <= kinds


def test_bench_quality_gate_is_loud():
    """A run whose AUC misses the bar still prints its line (honest
    record) but exits 3 so an unattended driver can't read garbage
    training as success."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               BENCH_ROWS="3000", BENCH_FEATURES="6",
               BENCH_LEAVES="7", BENCH_ITERS="1",
               BENCH_WARMUP_ITERS="1", BENCH_BUDGET_S="500",
               BENCH_MIN_AUC="1.01",   # unreachable bar
               BENCH_ALLOW_CPU="1", BENCH_NO_TELEMETRY="1")
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_cpu_max_isa=AVX2").strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], env=env,
        capture_output=True, text=True, timeout=570)
    assert proc.returncode == 3, (proc.returncode, proc.stderr[-2000:])
    sys.path.insert(0, REPO)
    from bench import find_result_line
    line = find_result_line(proc.stdout)
    assert line is not None and line["quality_ok"] is False


@pytest.mark.slow
def test_bench_fixed_quality_gate_block():
    """The >=100-iteration fixed-config accuracy gate (VERDICT r5 weak
    #5): quality_ok means 'within 0.002 AUC of the committed baseline
    accuracy at matched params' (BENCH_QUALITY_BASELINE.json) — the
    3-iteration sanity floor is no longer the bench's accuracy
    verdict."""
    sys.path.insert(0, REPO)
    import bench
    assert os.path.exists(bench.QUALITY_BASELINE_FILE)
    env = dict(os.environ)
    env.update(_BENCH_CHILD="1", JAX_PLATFORMS="cpu",
               BENCH_NO_TELEMETRY="1")
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_cpu_max_isa=AVX2").strip()
    parsed = bench.run_quality_gate(env, remaining=900)
    assert parsed is not None
    assert parsed["metric"] == "cpu_fixed_quality_gate"
    assert parsed["baseline_config"] == bench.QUALITY_GATE_ID
    assert parsed["auc_iters"] >= bench.QUALITY_GATE["iters"]
    assert parsed["auc_tolerance"] == 0.002
    assert parsed["quality_ok"] is True, parsed


@pytest.mark.slow
def test_bench_dispatch_census_line():
    """bench.py's census block: one dispatches_per_split JSON line
    with the per-program breakdown and the committed-budget verdict."""
    sys.path.insert(0, REPO)
    import bench
    env = dict(os.environ)
    env["_BENCH_CHILD"] = "1"
    parsed = bench.run_dispatch_census(env, remaining=600)
    assert parsed is not None
    assert parsed["metric"] == "dispatches_per_split"
    assert parsed["baseline_config"] == bench.CPU_BASELINE_ID
    assert parsed["budget_ok"] is True
    assert parsed["value"] > 0
    assert set(parsed["programs"]) == {"serial_grow",
                                       "partitioned_grow"}


@pytest.mark.slow
def test_bench_mesh_scaling_child():
    """The mesh-scaling child (ISSUE 14): one JSON line with the
    1->N-device time/split curve for every mesh learner mode, on the
    virtual CPU mesh."""
    env = dict(os.environ)
    env.update(_BENCH_CHILD_MESH="1", JAX_PLATFORMS="cpu",
               BENCH_MESH_ROWS="2048", BENCH_MESH_FEATURES="6",
               BENCH_MESH_LEAVES="7", BENCH_MESH_TREES="1")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags
                 + " --xla_force_host_platform_device_count=8").strip()
    if "xla_cpu_max_isa" not in flags:
        flags = (flags + " --xla_cpu_max_isa=AVX2").strip()
    env["XLA_FLAGS"] = flags
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], env=env,
        capture_output=True, text=True, timeout=570)
    assert proc.returncode == 0, proc.stderr[-2000:]
    sys.path.insert(0, REPO)
    from bench import find_result_line
    line = find_result_line(proc.stdout)
    assert line is not None, proc.stdout[-2000:]
    assert line["metric"] == "mesh_scaling"
    assert line["value"] and line["value"] > 0
    ms = line["mesh_scaling"]
    assert ms["devices"] == [1, 2, 4, 8]
    # every mode produced a full curve with no recorded errors
    assert sorted(ms["modes"]) == ["data", "feature", "partitioned",
                                   "voting"], ms.get("errors")
    assert "errors" not in ms, ms["errors"]
    for mode, curve in ms["modes"].items():
        assert set(curve) == {"1", "2", "4", "8"}, (mode, curve)
        assert all(v > 0 for v in curve.values())
    assert set(ms["speedup"]) == set(ms["modes"])


def test_bench_linear_convergence_child():
    """The linear_tree=true bench block (ISSUE 6): the convergence
    child prints a JSON line with the iteration ratio that the parent
    records in the bench output. A full double training in a child
    process — slow-marked so the tier-1 budget gate keeps its headroom
    (the full suite and CI still run it; the in-process convergence
    acceptance test lives in tests/test_linear_tree.py)."""
    env = dict(os.environ)
    env.pop("_BENCH_CHILD", None)
    env.update(JAX_PLATFORMS="cpu", _BENCH_CHILD_LINEAR="1",
               BENCH_LINEAR_ROWS="2500", BENCH_LINEAR_ITERS="15",
               BENCH_LINEAR_LEAVES="15")
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_cpu_max_isa=AVX2").strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], env=env,
        capture_output=True, text=True, timeout=570)
    assert proc.returncode == 0, proc.stderr[-2000:]
    sys.path.insert(0, REPO)
    from bench import find_result_line
    line = find_result_line(proc.stdout)
    assert line is not None, proc.stdout[-2000:]
    assert line["metric"] == "linear_tree_convergence"
    assert line["const_iters"] == 15
    assert line["linear_iters_to_match"] is not None
    assert 0 < line["iter_ratio"] <= 1.0
    assert isinstance(line["meets_0p7_bar"], bool)


def test_bench_without_a_chip_fails_and_prints_no_device_metric():
    """No accelerator: main() exits non-zero straight after the probe
    and prints nothing under the device metric's name — no CPU
    fallback headline, no cached verdict, no retry."""
    env = dict(os.environ)
    env.pop("BENCH_ALLOW_CPU", None)
    env.update(JAX_PLATFORMS="cpu", BENCH_NO_TELEMETRY="1")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "higgs_like_train_throughput" not in proc.stdout
    assert "no accelerator answered the probe" in proc.stderr
    assert "reason_code=no_device" in proc.stderr


def test_find_result_line_takes_last_valid():
    sys.path.insert(0, REPO)
    from bench import find_result_line
    out = "\n".join([
        "noise",
        '{"metric": "higgs_like_train_throughput", "value": 1}',
        '{"not-a-metric": true}',
        'WARNING {"metric": "x"} inline noise',
        '{"metric": "higgs_like_train_throughput", "value": 2}',
    ])
    assert find_result_line(out)["value"] == 2
    assert find_result_line("no json here") is None
