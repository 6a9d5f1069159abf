#!/usr/bin/env python3
"""Records ``data/tiny-train-scoped-v5e.*`` on a chip (by hand, through
the chip tool; not a test):

    chiprun --chips 1 -- python3 benchmarks/tests/record_scoped.py

One traced step of the tiny training cell (20,000 rows x 28 features,
15 leaves, a step of 2 trees, seed 1), through ``benchmarks.run.main``
with ``--keep-trace 1``. Writes under ``chiprun_out/scoped/`` the
``.xplane.pb``, the scope table of the window's program as JSON (with
the facts the readers need), and the compiled module's text (kept out
of git: to re-parse by hand). Then prints what has to hold before
anything is built on the join: the share of the trace's leaf time whose
instruction is in the table, the names on the ``XLA Modules`` line and
the ``lgbm.`` spans on the host's lines.
"""

import glob
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") != ROOT]

from benchmarks import run, scopes as bench_scopes  # noqa: E402
from benchmarks import trace_reduce as tr           # noqa: E402

CELL = "higgs-10m-train"
ROWS, BLOCK, LEAVES = 20000, 2, 15
TINY = {"config": {"params": {"num_leaves": LEAVES},
                   "check": {"rows": 2000, "trees": 3, "auc_rows": 2000}},
        "traffic": {"rows": ROWS, "block": BLOCK, "trace_steps": 1}}


def main() -> int:
    cpu = "--cpu" in sys.argv       # a rehearsal of this script only
    if cpu:
        from benchmarks.tests import conftest  # noqa: F401 (sets the env)
        TINY.update(allow_cpu=True)
        TINY["traffic"]["params"] = {"tree_learner": "partitioned",
                                     "fused_split_kernel": "on"}
    from jax.profiler import ProfileData

    from lightgbm_tpu.observability import scopes
    out = os.path.join(ROOT, "chiprun_out", "scoped")
    os.makedirs(out, exist_ok=True)
    texts = {}                      # id(table) -> the text it is of
    parse = scopes.parse_hlo_scopes

    def parse_and_keep(text):
        table = parse(text)
        texts[id(table)] = text
        return table
    scopes.parse_hlo_scopes = parse_and_keep
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "1", "--keep-trace", "1"],
                  tiny=dict(TINY, scratch=out))
    if rc:
        return rc
    facts = {"rows": ROWS, "block": BLOCK}
    _, table, table_s = bench_scopes._table(facts)
    stem = os.path.join(out, "tiny-train-scoped-v5e")
    xplane = tr.find_xplane(os.path.join(out, "out", f"trace-{CELL}"))
    shutil.copy(xplane, stem + ".xplane.pb")
    with open(stem + ".scopes.json", "w") as fh:
        json.dump({"program": bench_scopes.PROGRAM, "rows": ROWS,
                   "block": BLOCK,
                   "traced_trees": [{"leaves": LEAVES}] * BLOCK,
                   "table": table}, fh, indent=0, sort_keys=True)
    with open(stem + ".hlo.txt", "w") as fh:
        fh.write(texts[id(table)])

    trace = tr.Trace.from_file(stem + ".xplane.pb", cpu_stand_in=cpu)
    leaf_s, missing = 0.0, {}
    for ops in trace.devices.values():
        for which, lo, hi in zip(ops.which[ops.leaf], ops.start[ops.leaf],
                                 ops.end[ops.leaf]):
            name = tr.short_name(ops.texts[which])
            leaf_s += hi - lo
            if name not in table:
                missing[name] = missing.get(name, 0.0) + hi - lo
    report = {"leaf_s": leaf_s,
              "joined_share": 1.0 - sum(missing.values()) / leaf_s,
              "table_s": table_s, "ops_in_table": len(table),
              "not_in_table": sorted(missing.items(),
                                     key=lambda kv: -kv[1])[:20],
              "modules": {}, "lgbm_host_events": {}}
    for plane in ProfileData.from_file(stem + ".xplane.pb").planes:
        for line in plane.lines:
            for ev in line.events:
                if line.name == "XLA Modules":
                    key = ev.name.split("(")[0]
                    report["modules"][key] = \
                        report["modules"].get(key, 0) + 1
                elif ev.name.startswith("lgbm."):
                    key = f"{line.name.split('/')[0]}: {ev.name}"
                    report["lgbm_host_events"][key] = \
                        report["lgbm_host_events"].get(key, 0) + 1
    print("scoped: " + json.dumps(report, indent=1))
    for path in glob.glob(stem + ".*"):
        print(path, os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main())
