"""Peak-RSS comparison of two_round vs in-memory file ingestion.

Reference analog: docs/Experiments.rst:150-170 records peak RES during
training with two_round=true (Higgs 0.868 GB). This tool generates a
Higgs-shaped CSV, loads it to a constructed Dataset both ways in fresh
subprocesses, and reports each child's peak RSS (ru_maxrss) so the
memory-bounded contract is a measured number, not a design claim.

Run: python tools/measure_two_round_memory.py [rows] [features]
"""

import json
import os
import resource
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def child(path: str, two_round: bool) -> None:
    sys.path.insert(0, REPO)
    from lightgbm_tpu.basic import Dataset
    ds = Dataset(path, params={"objective": "binary", "verbosity": -1,
                               "two_round": two_round}).construct()
    n = ds.construct()._inner.num_data
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    print(json.dumps({"two_round": two_round, "rows": n,
                      "peak_rss_mb": round(peak_mb, 1)}))


def main() -> int:
    if os.environ.get("_TWO_ROUND_MEM_CHILD"):
        child(sys.argv[1], sys.argv[2] == "1")
        return 0
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    import numpy as np
    path = "/tmp/two_round_mem.train"
    rng = np.random.RandomState(0)
    with open(path, "w") as fh:
        for lo in range(0, rows, 100_000):
            m = min(100_000, rows - lo)
            X = rng.randn(m, f).astype(np.float32)
            y = (X[:, 0] > 0).astype(np.int8)
            np.savetxt(fh, np.column_stack([y, X]), delimiter="\t",
                       fmt="%.7g")
    size_mb = os.path.getsize(path) / 1e6
    print(f"file: {rows} x {f}, {size_mb:.0f} MB text")
    env = dict(os.environ)
    env.update(_TWO_ROUND_MEM_CHILD="1", JAX_PLATFORMS="cpu")
    for tr in ("0", "1"):
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), path, tr],
            env=env, capture_output=True, text=True, timeout=1800)
        out = [ln for ln in p.stdout.splitlines()
               if ln.startswith("{")]
        print(out[-1] if out else f"FAILED rc={p.returncode}: "
                                  f"{p.stderr[-500:]}")
    os.unlink(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
