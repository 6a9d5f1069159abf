"""Mesh dryrun: all four mesh learner modes on the virtual CPU mesh.

The CI ``mesh-dryrun`` job's driver (ISSUE 14): trains data-, feature-,
voting- and mesh-partitioned-parallel learners on an 8-virtual-device
CPU mesh against the serial foil, with telemetry ON so the collective
byte/call counters (``comm.<op>_bytes`` — learner/comm.py
``_count_collective``) land in the JSONL trace the job uploads, and
writes a JSON summary with the per-mode comm profile.

Checks (exit 1 on any failure):
  * data / feature: trained tree EXACTLY matches serial (split
    features, thresholds; leaf values to float tolerance) and the
    full leaf_id vector is identical;
  * voting (top_k >= F) and mesh-partitioned data: tree matches serial;
  * every mode's comm counters contain ONLY the ops its recipe
    declares (the runtime shadow of graftcheck GC401 — the job also
    runs ``python -m tools.graftcheck`` over the four mesh programs,
    which pins the compiled multisets exactly).

Usage::

    python tools/mesh_dryrun.py [--json mesh_dryrun.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8") \
        .strip()
if "xla_cpu_max_isa" not in _flags:
    _flags = (_flags + " --xla_cpu_max_isa=AVX2").strip()
os.environ["XLA_FLAGS"] = _flags

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the ops each recipe is ALLOWED to count (learner/comm.py header)
EXPECTED_OPS = {
    "data": {"psum", "psum_scatter", "all_gather"},
    "feature": {"all_gather"},
    "voting": {"all_gather", "psum"},
    "partitioned": {"psum", "psum_scatter", "all_gather"},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="mesh_dryrun.json")
    ap.add_argument("--rows", type=int, default=3001)
    ap.add_argument("--features", type=int, default=10)
    ap.add_argument("--leaves", type=int, default=15)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import Dataset
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    from lightgbm_tpu.observability.telemetry import get_telemetry
    from lightgbm_tpu.parallel.learners import (
        DataParallelTreeLearner, FeatureParallelTreeLearner,
        MeshPartitionedTreeLearner, VotingParallelTreeLearner)

    tel = get_telemetry()
    tel.ensure_started()
    tel.ensure_ring()

    rng = np.random.RandomState(0)
    n, f = args.rows, args.features
    X = rng.randn(n, f)
    logit = 2 * X[:, 0] - 1.5 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (logit + rng.randn(n) * 0.3 > 0).astype(np.float32)
    cfg = Config.from_params({"objective": "binary",
                              "num_leaves": args.leaves,
                              "top_k": max(20, f), "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((n,), 0.25, jnp.float32)

    serial = SerialTreeLearner(ds, cfg)
    ref = serial.train(grad, hess)
    ref_tree = serial.to_host_tree(ref)
    ref_leaf = np.asarray(ref.leaf_id)

    def check_tree(tree, exact_leaf_id, res):
        ok = True
        ok &= tree.num_leaves == ref_tree.num_leaves
        ok &= bool(np.array_equal(tree.split_feature_inner,
                                  ref_tree.split_feature_inner))
        ok &= bool(np.array_equal(tree.threshold_bin,
                                  ref_tree.threshold_bin))
        ok &= bool(np.allclose(tree.leaf_value, ref_tree.leaf_value,
                               rtol=2e-4, atol=2e-6))
        if exact_leaf_id:
            ok &= bool(np.array_equal(np.asarray(res.leaf_id),
                                      ref_leaf))
        return bool(ok)

    def snapshot():
        return {k: v for k, v in tel.counters.items()
                if k.startswith("comm.")}

    modes = {
        "data": lambda: DataParallelTreeLearner(ds, cfg),
        "feature": lambda: FeatureParallelTreeLearner(ds, cfg),
        "voting": lambda: VotingParallelTreeLearner(ds, cfg),
        "partitioned": lambda: MeshPartitionedTreeLearner(
            ds, cfg, mode="data", interpret=True),
    }
    summary = {"devices": jax.device_count(), "rows": n,
               "features": f, "modes": {}}
    failures = []
    before = snapshot()
    for name, make in modes.items():
        lrn = make()
        res = lrn.train(grad, hess)
        tree = lrn.to_host_tree(res)
        after = snapshot()
        delta = {k: after.get(k, 0) - before.get(k, 0)
                 for k in set(after) | set(before)
                 if after.get(k, 0) != before.get(k, 0)}
        before = after
        ops = {k.split(".", 1)[1].rsplit("_", 1)[0]
               for k in delta if k.endswith("_calls")}
        exact = name in ("data", "feature")
        ok = check_tree(tree, exact, res)
        stray = ops - EXPECTED_OPS[name]
        entry = {"matches_serial": ok,
                 "collective_ops": sorted(ops),
                 "comm_counters": {k: round(float(v), 1)
                                   for k, v in sorted(delta.items())},
                 "stray_ops": sorted(stray)}
        summary["modes"][name] = entry
        if not ok:
            failures.append(f"{name}: tree diverged from serial foil")
        if stray:
            failures.append(f"{name}: stray collective op(s) {stray}")
        print(f"mesh-dryrun {name}: matches_serial={ok} "
              f"ops={sorted(ops)}", flush=True)

    summary["ok"] = not failures
    summary["failures"] = failures
    # a train_end record carries the accumulated counters so
    # tools/run_report.py renders the mesh-comms table straight from
    # the uploaded JSONL artifact
    tel.record("train_end", counters=dict(tel.counters))
    tel.flush()
    with open(args.json, "w") as fh:
        json.dump(summary, fh, indent=1)
        fh.write("\n")
    if failures:
        for msg in failures:
            print(f"mesh-dryrun FAILED: {msg}", file=sys.stderr)
        return 1
    print(f"mesh-dryrun ok: 4 modes on {summary['devices']} devices")
    return 0


if __name__ == "__main__":
    sys.exit(main())
