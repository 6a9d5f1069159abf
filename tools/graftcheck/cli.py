"""graftcheck CLI: ``python -m tools.graftcheck``.

Exit codes: 0 = every contract holds, 1 = findings, 2 = usage error.

Modes:
  (default)   build + measure + contract-check vs contracts.json
  --update    rewrite the manifest measurements (keeps slack/allow)
  --json F    also write the full artifact (config, measurements,
              findings) — the CI job uploads this
  --programs  comma list to restrict the sweep (default: all)
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

# like tools/hlo_census: always the CPU backend, with the virtual
# 8-device mesh the collective census needs and the AVX2 cap this
# sandbox requires
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8") \
        .strip()
if "xla_cpu_max_isa" not in _flags:
    _flags = (_flags + " --xla_cpu_max_isa=AVX2").strip()
os.environ["XLA_FLAGS"] = _flags


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tools.graftcheck",
        description="compiled-program contract checker over every "
                    "registered jit entry point "
                    "(docs/StaticAnalysis.md)")
    p.add_argument("--check", action="store_true",
                   help="explicit check mode (the default; kept for "
                        "workflow symmetry with tools.hlo_census)")
    p.add_argument("--update", action="store_true",
                   help="rewrite contracts.json measurements "
                        "(preserves slack/allow/note fields)")
    p.add_argument("--json", metavar="PATH",
                   help="write the full JSON artifact")
    p.add_argument("--programs", default=None,
                   help="comma list of program names (default: all)")
    p.add_argument("--format", choices=("table", "json"),
                   default="table")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    from .core import check_run, run_census
    from .manifest import load_manifest, update_manifest
    from .programs import BUILDERS
    from .reporters import render_json, render_table

    names = None
    if args.programs:
        names = [n.strip() for n in args.programs.split(",")
                 if n.strip()]
        unknown = [n for n in names if n not in BUILDERS]
        if unknown:
            print(f"graftcheck: unknown program(s): "
                  f"{', '.join(unknown)}", file=sys.stderr)
            return 2

    current, build_findings = run_census(names)

    if args.update:
        if build_findings:
            for f in build_findings:
                print(f"  {f.program}: {f.rule} {f.message}")
            print("graftcheck: refusing to --update with build "
                  "failures", file=sys.stderr)
            return 1
        if names is not None:
            print("partial --update: manifest config block describes "
                  "the LAST full run; re-run without --programs to "
                  "refresh every entry")
        update_manifest({k: v for k, v in current.items()
                         if k != "_hlo"})
        print(f"updated contracts for "
              f"{len(current['programs'])} program(s)")
        return 0

    findings = check_run(current, build_findings, load_manifest())
    report = render_table(findings, current) \
        if args.format == "table" else render_json(findings, current)
    print(report, end="")
    if args.json:
        with open(args.json, "w") as f:
            f.write(render_json(findings, current))
        print(f"wrote {args.json}")
    return 1 if findings else 0
