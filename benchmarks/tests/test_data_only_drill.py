"""The proof that later PRs can bring their cells as data: in a temp
copy of the benchmark, a fifth workload, a new traffic mix and a new
per-layer metric are ADDED as files and entries, no existing file of
``benchmarks/`` is touched, and the harness runs the new cell."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmarks import spec
from benchmarks.tests.tiny import tiny_for, with_held_back

ROOT = spec.ROOT

NEW_LAYER = '''"""Layer: load_generator. Median of sent minus due."""

from ..stats import percentile


def read(facts):
    values = facts.get("late_ms")
    return percentile(values, 50) if values else None
'''


def _digests(top):
    out = {}
    for folder, _dirs, files in os.walk(top):
        if "__pycache__" in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    copy = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
                    ignore=shutil.ignore_patterns(
                        ".cache", "out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "lightgbm_tpu"), copy / "lightgbm_tpu")
    before = _digests(copy / "benchmarks")

    # what a later PR adds: a mix with bursts, a reader, three entries
    mix = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "serve-online.json"))
    mix["arrival"] = {"kind": "onoff", "period_s": 1.0, "burst_s": 0.1,
                      "burst_factor": 4.0}
    with open(copy / "benchmarks" / "traffic" / "serve-bursty.json",
              "w") as fh:
        json.dump(mix, fh)
    with open(copy / "benchmarks" / "layers" / "gen_late_ms_p50.py",
              "w") as fh:
        fh.write(NEW_LAYER)
    bench = with_held_back(spec.load_benchmark())
    bench["workloads"].append({
        "name": "higgs-serve-bursty", "config": "higgs",
        "traffic": "serve-bursty", "chips": 1, "why": "bursts"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "higgs-serve-online" in m.get("workloads", []):
            m["workloads"].append("higgs-serve-bursty")
    bench["per_layer"].append({
        "name": "gen_late_ms_p50", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "load_generator",
        "moves": "serve_p99_ms", "workloads": ["higgs-serve-bursty"]})
    with open(copy / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)

    tiny = tiny_for("higgs-serve-online", tmp_path / "scratch")
    del tiny["benchmark"]           # the copy reads its own BENCHMARK.json
    code = (
        "import json, sys; sys.path.insert(0, {root!r});\n"
        "import benchmarks.tests.conftest\n"        # the CPU environment
        "sys.path.insert(0, {copy!r})\n"
        "for name in [m for m in sys.modules if m.startswith('benchmarks')]:"
        " del sys.modules[name]\n"
        "from benchmarks import run\n"
        "assert run.ROOT == {copy!r}, run.ROOT\n"
        "sys.exit(run.main(['--workload', 'higgs-serve-bursty', '--seed',"
        " '3', '--seconds', '3', '--trace', '1'], tiny={tiny!r}))\n"
    ).format(root=ROOT, copy=str(copy), tiny=tiny)
    proc = subprocess.run([sys.executable, "-c", code], cwd=copy,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert "gen_late_ms_p50" in result["metrics"]
    assert "serve_queue_ms_p50" in result["metrics"]
    after = _digests(copy / "benchmarks")
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "layers/gen_late_ms_p50.py", "traffic/serve-bursty.json"]
