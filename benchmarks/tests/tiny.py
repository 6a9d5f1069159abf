"""The size overrides only the tests pass to ``benchmarks.run.main``:
every cell at a size the CPU runs in seconds. Widths are cut here and
nowhere else."""

import copy
import os

from benchmarks import spec


def with_held_back(bench: dict) -> dict:
    """``bench`` with the entries of ``benchmarks/held_back.json``
    appended, as a later PR would append them: the held-back cells run
    through this."""
    held = spec.load_json(os.path.join(spec.BENCH_DIR, "held_back.json"))
    out = copy.deepcopy(bench)
    for section in ("workloads", "end_to_end", "per_layer"):
        out[section] += copy.deepcopy(held[section])
    for workload, metrics in held["also_reports"].items():
        for m in out["end_to_end"] + out["per_layer"]:
            if m["name"] in metrics:
                m["workloads"].append(workload)
    return out


TRAIN_CONFIG = {"params": {"num_leaves": 15},
                "check": {"rows": 2000, "trees": 3, "auc_rows": 2000}}

TINY = {
    # the interpret twins of the kernels and of the megakernel, as
    # tests/test_chip_smoke.py forces them on the CPU
    "higgs-10m-train": {
        "config": TRAIN_CONFIG,
        "traffic": {"rows": 6000, "block": 2,
                    "params": {"tree_learner": "partitioned",
                               "fused_split_kernel": "on"}}},
    "higgs-500k-train": {
        "config": TRAIN_CONFIG,
        "traffic": {"rows": 4000, "block": 4,
                    "params": {"tree_learner": "partitioned",
                               "fused_split_kernel": "on"}}},
    "criteo-7m-train": {
        "config": TRAIN_CONFIG,
        "traffic": {"rows": 5000, "block": 2,
                    "params": {"tree_learner": "partitioned",
                               "fused_split_kernel": "on"}}},
    "criteo-dp4-train": {
        "config": TRAIN_CONFIG,
        "traffic": {"rows": 4000, "block": 2}},
    "higgs-serve-online": {
        "config": {"params": {"num_leaves": 15},
                   "served_model": {"trees": 20},
                   "check": {"serve_sample": 32}},
        "traffic": {"pool_rows": 3000, "rate_rps": 500,
                    "sizes": [{"share": 0.8, "rows": 1},
                              {"share": 0.2, "log_uniform": [2, 64]}],
                    "engine": {"buckets": [1, 8, 64]},
                    "sweep": {"seconds": 1.5, "rates_rps": [100, 400]}}},
}


def tiny_for(workload: str, scratch: str) -> dict:
    return dict(TINY[workload], allow_cpu=True, scratch=str(scratch),
                benchmark=with_held_back(spec.load_benchmark()))
