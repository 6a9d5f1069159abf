"""Compiled-HLO dispatch census for the fused grow-loop programs.

The grow loop compiles to ONE ``lax.while_loop`` program per tree; what
the hardware actually pays per split is the number of executable ops in
the compiled while-loop BODY (each fusion / reduce / scatter / inner
loop is one dispatch on CPU and one kernel launch worth of fixed cost
on an accelerator). This tool lowers the repo's grow programs at a
fixed config, finds the grow ``while`` in the optimized HLO, counts the
body's non-trivial ops, and compares the result against the committed
budget (``tools/hlo_census_budget.json``) — CI fails when a change
regresses the per-split dispatch count (the round-6 directive: prove
the per-split fixed-cost reduction with an op census, VERDICT item 2).

Usage:
  python -m tools.hlo_census            # print the census table
  python -m tools.hlo_census --check    # exit 1 on budget regression
  python -m tools.hlo_census --update   # rewrite budget measurements
  python -m tools.hlo_census --json F   # also write the census artifact

Counting rules (deliberately simple and stable):
  * the grow while is the ``while`` op WITHOUT a ``known_trip_count``
    backend_config (scatter expansions and pallas grid loops are
    trip-counted) whose body holds the most non-trivial ops;
  * non-trivial = everything except parameter / constant / tuple /
    get-tuple-element / bitcast (pure bookkeeping that costs nothing);
  * inner ``while`` ops (CPU scatter expansion, interpret-mode Pallas
    grids) count as ONE op each — on TPU they are one kernel.

The numbers are CPU-backend numbers and comparable only to each other
(the partitioned program carries interpret-mode Pallas emulation glue
that does not exist on TPU), which is exactly what a trend gate needs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# the census counts XLA:CPU's compiled ops: CPU unless the caller
# chose a platform
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# parsing/counting core shared with tools/graftcheck (ONE parser, two
# front-ends — ISSUE 9); the helpers moved there verbatim, so the
# committed budget and the reported fixed-config counts are unchanged
from tools.graftcheck.hlo import census_from_hlo  # noqa: E402

BUDGET_PATH = os.path.join(os.path.dirname(__file__),
                           "hlo_census_budget.json")

# fixed census config: the bench fixed CPU baseline's shape family
# (cpu-fixed-v1: 28 features, 63 leaves; see bench.py CPU_BASELINE_ID).
# Rows are scaled down — the while-body op census is row-count
# independent (row count only scales tensor shapes, never the op list)
# — so the compile stays fast enough for CI.
CENSUS_ROWS = 4096
CENSUS_FEATURES = 28
CENSUS_LEAVES = 63

def _build_dataset(rows=CENSUS_ROWS, features=CENSUS_FEATURES,
                   leaves=CENSUS_LEAVES):
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import Dataset
    rng = np.random.RandomState(0)
    x = rng.randn(rows, features).astype(np.float32)
    y = (rng.rand(rows) < 0.5).astype(np.float32)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": leaves,
        "min_data_in_leaf": 20, "verbosity": -1})
    return Dataset.from_numpy(x, cfg, label=y), cfg


def lower_serial(ds, cfg):
    """jax Lowered of the serial grow program at this dataset/config
    (shared with tools/graftcheck's serial_grow example builder)."""
    import jax.numpy as jnp

    from lightgbm_tpu.learner.serial import SerialTreeLearner, _grow_jit
    lrn = SerialTreeLearner(ds, cfg)
    n = ds.num_data
    grad = jnp.zeros((n,), jnp.float32)
    hess = jnp.ones((n,), jnp.float32)
    return _grow_jit.lower(
        lrn.binned, grad, hess, lrn._ones_rows, lrn._all_features,
        lrn.meta, rand_key=None, cegb_used0=None, cegb_charged0=None,
        params=lrn.params, num_leaves=lrn.num_leaves,
        max_depth=lrn.max_depth, num_bins_max=lrn.num_bins_max,
        hist_method=lrn.hist_method, bundled=lrn.bundled,
        extra_trees=False, ff_bynode=1.0, bynode_count=2,
        forced_plan=(), cache_hists=lrn.cache_hists,
        mv_slots=lrn.mv_slots, mv_groups=lrn.mv_groups,
        has_monotone=lrn.has_monotone,
        split_fusion=_fusion_mode())


def _compiled_serial(ds, cfg) -> str:
    return lower_serial(ds, cfg).compile().as_text()


def lower_partitioned(ds, cfg, fused_kernel: bool = False):
    """jax Lowered of the partitioned grow program (shared with
    tools/graftcheck's partitioned_grow example builder).
    ``fused_kernel=True`` lowers the megakernel path
    (``fused_split_kernel=on``: on CPU the interpret twin of
    ops/split_step_pallas.py), the ``partitioned_grow_fused`` census
    program."""
    import dataclasses

    import jax.numpy as jnp

    from lightgbm_tpu.learner.partitioned import (PartitionedTreeLearner,
                                                  _grow_partitioned)
    if fused_kernel:
        cfg = dataclasses.replace(cfg, fused_split_kernel="on")
    lrn = PartitionedTreeLearner(ds, cfg)
    n = ds.num_data
    grad = jnp.zeros((n,), jnp.float32)
    hess = jnp.ones((n,), jnp.float32)
    return _grow_partitioned.lower(
        lrn.mat, lrn.ws, grad, hess, lrn._ones_rows, lrn._all_features,
        lrn.meta, None, None, params=lrn.params,
        num_leaves=lrn.num_leaves, max_depth=lrn.max_depth,
        num_bins_max=lrn.num_bins_max, num_features=lrn.num_features,
        num_groups=lrn.num_groups, n=lrn.num_data, bundled=lrn.bundled,
        interpret=lrn.interpret, extra_trees=False, ff_bynode=1.0,
        bynode_count=2, forced_plan=(), cache_hists=lrn.cache_hists,
        hist_slots=lrn.hist_slots, has_monotone=lrn.has_monotone,
        split_fusion=_fusion_mode(), plan=lrn.split_plan())


def _compiled_partitioned(ds, cfg) -> str:
    return lower_partitioned(ds, cfg).compile().as_text()


def _compiled_partitioned_fused(ds, cfg) -> str:
    return lower_partitioned(ds, cfg,
                             fused_kernel=True).compile().as_text()


def _fusion_mode() -> bool:
    from lightgbm_tpu.learner.split_step import split_fusion_default
    return split_fusion_default()


PROGRAMS = {
    "serial_grow": _compiled_serial,
    "partitioned_grow": _compiled_partitioned,
    # the megakernel path (ops/split_step_pallas.py): the whole split
    # as ONE pallas_call — the lax per-phase programs above stay the
    # bit-exactness foil with their budgets unchanged
    "partitioned_grow_fused": _compiled_partitioned_fused,
}


def run_census(programs=None, rows=CENSUS_ROWS,
               features=CENSUS_FEATURES, leaves=CENSUS_LEAVES) -> dict:
    """Compile + census every requested program. Returns the artifact
    dict (the committed budget holds a subset of these fields). The
    ops_per_split census is shape-independent — smaller ``rows``/
    ``features``/``leaves`` only shrink tensor shapes (and thus the
    compile time), never the while-body op list — so tests run a tiny
    config against the same budget (asserted by
    tests/test_split_fusion.py)."""
    ds, cfg = _build_dataset(rows, features, leaves)
    out = {
        "config": {"rows": rows, "features": features,
                   "leaves": leaves, "backend": "cpu",
                   "split_fusion": _fusion_mode(),
                   "baseline_family": "cpu-fixed-v1-50k-28f-63l-10it"},
        "programs": {},
    }
    for name in (programs or PROGRAMS):
        txt = PROGRAMS[name](ds, cfg)
        out["programs"][name] = census_from_hlo(txt)
    return out


def load_budget(path: str = BUDGET_PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def check(current: dict, budget: dict):
    """(ok, messages): every program's ops_per_split must stay within
    budget + slack; carry_bytes within its own budget + slack_bytes."""
    msgs, ok = [], True
    for name, b in budget["programs"].items():
        cur = current["programs"].get(name)
        if cur is None:
            msgs.append(f"{name}: MISSING from census run")
            ok = False
            continue
        limit = b["ops_per_split"] + b.get("slack", 0)
        status = "ok" if cur["ops_per_split"] <= limit else "REGRESSED"
        msgs.append(
            f"{name}: ops/split {cur['ops_per_split']} "
            f"(budget {b['ops_per_split']} + slack {b.get('slack', 0)}"
            f", pre-PR {b.get('pre_pr', '?')}) [{status}]")
        if cur["ops_per_split"] > limit:
            ok = False
        cb = b.get("carry_bytes")
        if cb is not None:
            climit = cb + b.get("slack_bytes", 0)
            if cur["carry_bytes"] > climit:
                msgs.append(f"{name}: carry {cur['carry_bytes']}B "
                            f"exceeds budget {climit}B [REGRESSED]")
                ok = False
    return ok, msgs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when the committed budget regresses")
    ap.add_argument("--update", action="store_true",
                    help="rewrite budget measurements (keeps slack + "
                         "pre_pr fields)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the full census artifact JSON")
    ap.add_argument("--programs", default=None,
                    help="comma list (default: all)")
    ap.add_argument("--rows", type=int, default=CENSUS_ROWS)
    ap.add_argument("--features", type=int, default=CENSUS_FEATURES)
    ap.add_argument("--leaves", type=int, default=CENSUS_LEAVES,
                    help="shape overrides: the op census is shape-"
                         "independent, smaller shapes only compile "
                         "faster (bench uses 512x8x15)")
    args = ap.parse_args(argv)

    programs = args.programs.split(",") if args.programs else None
    current = run_census(programs, rows=args.rows,
                         features=args.features, leaves=args.leaves)

    for name, c in current["programs"].items():
        print(f"{name}: ops/split={c['ops_per_split']} "
              f"fusions={c['fusions']} inner_whiles={c['inner_whiles']} "
              f"collectives={c['collectives']} "
              f"carry={c['carry_arrays']} arrays / "
              f"{c['carry_bytes']} bytes")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(current, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")

    if args.update:
        budget = load_budget() if os.path.exists(BUDGET_PATH) else {
            "programs": {}}
        for name, c in current["programs"].items():
            b = budget["programs"].setdefault(name, {})
            b["ops_per_split"] = c["ops_per_split"]
            b["carry_bytes"] = c["carry_bytes"]
            b.setdefault("slack", 8)
            b.setdefault("slack_bytes", 4096)
        # the top-level config describes ALL program measurements:
        # only rewrite it when this run re-measured every program at
        # the canonical shape (a partial/overridden --update must not
        # mislabel untouched entries)
        full = (programs is None
                and (args.rows, args.features, args.leaves)
                == (CENSUS_ROWS, CENSUS_FEATURES, CENSUS_LEAVES))
        if full:
            budget["config"] = current["config"]
        else:
            print("partial --update: keeping the budget's config "
                  "block (re-run without --programs/shape overrides "
                  "to refresh it)")
        with open(BUDGET_PATH, "w") as f:
            json.dump(budget, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"updated {BUDGET_PATH}")
        return 0

    if args.check:
        ok, msgs = check(current, load_budget())
        for m in msgs:
            print(m)
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
