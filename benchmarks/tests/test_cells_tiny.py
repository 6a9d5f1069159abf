"""Every cell end to end on the CPU at a tiny size, through the
``tiny`` argument of the entry function; the last line's keys pinned
exactly."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import run, spec
from benchmarks.tests.tiny import tiny_for, with_held_back

ROOT = spec.ROOT
# the listed cells and the held-back ones (benchmarks/held_back.json)
BENCH = with_held_back(spec.load_benchmark())


def _run(capsys, workload, trace, scratch, seconds="3"):
    rc = run.main(["--workload", workload, "--seed", "5", "--seconds",
                   seconds, "--trace", str(trace)],
                  tiny=tiny_for(workload, scratch))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    return json.loads(out[-1]), [ln for ln in out if ln.startswith("info:")]


def _check_line(result, workload, trace):
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    if trace:
        keys.add("breakdown")
    assert set(result) == keys
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        device |= {"busy_s", "window_s"}
        assert 0 < result["device"]["busy_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(result["breakdown"]["device_ops"]) <= 10
        assert len(result["breakdown"]["idle_gaps"]) <= 10
    assert set(result["device"]) == device
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in
                spec.metrics_for(BENCH, section, workload)}
    assert set(result["metrics"]) <= set(declared)
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert m["unit"] == declared[name]["unit"]
    if not trace:
        # every declared end-to-end metric is there and is never 0
        assert set(result["metrics"]) == set(declared)
        assert all(m["value"] > 0 for m in result["metrics"].values())
    return result["metrics"]


@pytest.mark.parametrize("workload,trace", [
    ("higgs-10m-train", 0), ("higgs-10m-train", 1),
    ("criteo-7m-train", 1), ("higgs-500k-train", 0)])
def test_one_chip_training_cells(capsys, tmp_path, workload, trace):
    result, info = _run(capsys, workload, trace, tmp_path)
    got = _check_line(result, workload, trace)
    path = json.loads(next(ln for ln in info if ln.startswith(
        "info: check_path")).split(" ", 2)[2])
    assert path["learner"] == "PartitionedTreeLearner"
    assert path["megakernel"] is True and path["compiles_in_window"] == 0
    if trace:
        assert {"dataset_construct_s", "driver_host_calls_per_tree",
                "grow_ms_per_split", "device_idle_share"} <= set(got)
        # one dispatch and one fetch a step, steps of 2 trees
        assert got["driver_host_calls_per_tree"]["value"] == 1.0
    else:
        assert set(got) == {"train_mrow_iters_per_s", "setup_s"}


def test_dataset_cache_hits_on_the_second_run(capsys, tmp_path):
    first = _run(capsys, "higgs-500k-train", 0, tmp_path, "1")[1]
    second = _run(capsys, "higgs-500k-train", 0, tmp_path, "1")[1]
    assert '"cache": "miss"' in next(
        ln for ln in first if ln.startswith("info: dataset"))
    assert '"cache": "hit"' in next(
        ln for ln in second if ln.startswith("info: dataset"))


def test_four_chip_cell_on_the_virtual_mesh(capsys, tmp_path, monkeypatch):
    """The learner factory routes data-parallel onto the mesh
    segment-kernel learner as it does on a TPU (kernels stay in
    interpret mode), as tests/test_chip_smoke.py does."""
    import lightgbm_tpu.parallel.learners as learners
    monkeypatch.setattr(learners, "on_tpu", lambda: True)
    result, info = _run(capsys, "criteo-dp4-train", 1, tmp_path, "2")
    got = _check_line(result, "criteo-dp4-train", 1)
    path = json.loads(next(ln for ln in info if ln.startswith(
        "info: check_path")).split(" ", 2)[2])
    assert path["learner"] == "MeshPartitionedTreeLearner"
    assert path["num_shards"] == 4 and path["megakernel"] is False
    assert {"collective_ms_per_split", "collective_exposed_share"} \
        <= set(got)
    assert 0 < got["collective_exposed_share"]["value"] <= 100


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell(capsys, tmp_path, trace):
    result, info = _run(capsys, "higgs-serve-online", trace, tmp_path)
    got = _check_line(result, "higgs-serve-online", trace)
    assert result["attempted"] >= 1000
    if trace:
        assert set(got) == {"serve_queue_ms_p50", "serve_batch_rows_mean",
                            "serve_compute_ms_p50",
                            "predict_device_ms_per_batch",
                            "gen_late_ms_p99", "serve_device_idle_share"}
    else:
        assert set(got) == {"serve_p50_ms", "serve_p99_ms",
                            "serve_krows_per_s", "setup_s"}
        assert got["serve_p99_ms"]["value"] >= got["serve_p50_ms"]["value"]


def test_sweep_prints_a_table_and_no_result(capsys, tmp_path):
    rc = run.main(["--workload", "higgs-serve-online", "--seed", "5",
                   "--seconds", "1", "--sweep", "1"],
                  tiny=tiny_for("higgs-serve-online", tmp_path))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert sum(ln.startswith("info: sweep {") for ln in out) == 2
    assert out[-1].startswith("info: sweep_written")
    table = json.load(open(tmp_path / "out" /
                           "sweep-higgs-serve-online.json"))["table"]
    assert [row["rate_rps"] for row in table] == [100, 400]


def _script(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py")] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "higgs-10m-train", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_the_command_refuses_a_cpu():
    proc = _script(ARGS, ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


def test_the_command_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(
                        ".cache", "out", "__pycache__"))
    proc = _script(ARGS, str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "lightgbm_tpu" in proc.stderr
