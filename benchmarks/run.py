#!/usr/bin/env python3
"""One process, one cell, once.

    python3 benchmarks/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Refuses anything but a TPU of a kind in ``benchmarks/peaks.py`` (exit
2, nothing on stdout), keeps the compile cache where
``lightgbm_tpu/utils/compile_cache.py`` puts it (inside the checkout),
sets the cell up, warms exactly the shapes the window uses, measures
for ``--seconds``, checks correctness outside the window and prints as
the last line of stdout the result object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, in a traced run,
``breakdown`` - no other key. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Everything else worth seeing goes on earlier ``info:`` lines.

``--sweep 1`` (serving cells; never run by the driver) finds the knee
instead and prints a table.

The harness is driven by data: the cell's configuration, traffic mix,
generators and per-layer readers are files found by the names
``BENCHMARK.json`` gives (``benchmarks/spec.py``). There is no list of
cells, kinds or metrics in this file.
"""

import time

T_START = time.perf_counter()       # before anything heavy is imported

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# run as a script, Python puts benchmarks/ itself first on the path,
# where stats.py or spec.py would shadow other modules of those names;
# the harness is imported as the package ``benchmarks`` from the root
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".")
                        not in (ROOT, os.path.join(ROOT, "benchmarks"))]

from benchmarks import monitor, spec    # noqa: E402


class Context:
    """What a cell's runner is handed."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 device: Dict[str, Any], compiles, allow_cpu: bool,
                 keep_trace: bool, t_start: float, scratch: str):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.device = device
        self.compiles = compiles
        self.allow_cpu = allow_cpu
        self.keep_trace = keep_trace
        # what a run leaves behind, both listed in .gitignore
        self.out_dir = os.path.join(scratch, "out")
        self.cache_dir = os.path.join(scratch, ".cache")
        self.setup_s: Optional[float] = None
        self._t_start = t_start
        os.makedirs(self.out_dir, exist_ok=True)

    def info(self, what: str, **fields) -> None:
        print(f"info: {what} {json.dumps(fields, default=str)}",
              flush=True)

    def start_window(self) -> None:
        """Set-up ends here: process start to window start."""
        self.setup_s = time.perf_counter() - self._t_start
        self.info("setup", setup_s=round(self.setup_s, 3),
                  **self.compiles.snapshot())


def result_line(obs: Dict[str, Any], metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any], breakdown=None) -> str:
    """The last line of stdout: exactly the contract's keys."""
    out = {"correct": bool(obs["correct"]),
           "attempted": int(obs["attempted"]),
           "failed": int(obs["failed"]),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    return json.dumps(out)


def _metrics(bench, ctx, obs) -> Dict[str, Dict[str, Any]]:
    """End-to-end metrics of an untraced run, per-layer metrics of a
    traced one, each as measured with all its digits."""
    out: Dict[str, Dict[str, Any]] = {}
    if not ctx.trace:
        values = dict(obs["end_to_end"], setup_s=ctx.setup_s)
        for m in spec.metrics_for(bench, "end_to_end", ctx.cell.name):
            if values.get(m["name"]) is None:
                raise spec.SpecError(
                    f"the cell did not measure {m['name']}")
            out[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
        return out
    facts = dict(obs["facts"], setup_s=ctx.setup_s)
    for m in spec.metrics_for(bench, "per_layer", ctx.cell.name):
        reader = spec.load_module("layers", m["name"])
        value = reader.read(facts)
        # a reader that finds nothing to read returns nothing
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, *, tiny: Optional[Dict[str, Any]] = None,
         t_start: Optional[float] = None) -> int:
    """``tiny`` is passed by the tests only: size overrides laid on the
    configuration and the mix, ``allow_cpu`` for the CPU rehearsal,
    ``scratch``, where the run may write instead of ``benchmarks/``,
    and ``benchmark``, read in place of ``BENCHMARK.json``.
    The command line cannot shrink a cell. ``t_start`` is when the
    process started, as the script's first line read the clock."""
    if t_start is None:
        t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", type=int, choices=(0, 1), default=0,
                    help="serving cells: find the knee instead")
    ap.add_argument("--keep-trace", type=int, choices=(0, 1), default=0,
                    help="keep the .xplane.pb and a summary of it "
                    "under benchmarks/out/")
    args = ap.parse_args(argv)
    tiny = tiny or {}
    allow_cpu = bool(tiny.get("allow_cpu"))

    # everything below needs the program: in a directory that holds
    # only the benchmark, this import fails and so does the run
    from lightgbm_tpu.utils.compile_cache import \
        maybe_enable_compile_cache

    bench = tiny.get("benchmark") or spec.load_benchmark()
    cell = spec.load_cell(bench, args.workload, tiny)
    try:
        device = monitor.device_report(cell.chips, allow_cpu)
    except monitor.NoAccelerator as e:
        sys.stderr.write(f"benchmarks/run.py: {e}; no result\n")
        return 2
    compiles = monitor.CompileWatch()
    cache_dir = maybe_enable_compile_cache()    # before the first compile
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), device,
                  compiles, allow_cpu, bool(args.keep_trace), t_start,
                  tiny.get("scratch", os.path.join(ROOT, "benchmarks")))
    import jax
    import jaxlib
    ctx.info("start", workload=cell.name, config=cell.config_name,
             traffic=cell.traffic_name, chips=cell.chips, seed=args.seed,
             seconds=args.seconds, trace=args.trace, device=device,
             jax=jax.__version__, jaxlib=jaxlib.__version__,
             compile_cache=cache_dir)
    kind = spec.load_module("kinds", cell.traffic["kind"])
    if args.sweep:
        table = kind.sweep(ctx)
        path = os.path.join(ctx.out_dir, f"sweep-{cell.name}.json")
        with open(path, "w") as fh:
            json.dump(table, fh, indent=1)
        ctx.info("sweep_written", path=path)
        return 0
    obs = kind.run(ctx)
    metrics = _metrics(bench, ctx, obs)
    device_out = dict(device,
                      memory_peak_bytes=monitor.memory_peak_bytes(
                          cell.chips))
    breakdown = None
    if ctx.trace:
        trace = obs["facts"].get("trace")
        if trace is None or trace.busy_s() <= 0:
            sys.stderr.write("benchmarks/run.py: the traced window shows "
                             "no operation on the device; no result\n")
            return 3
        device_out.update(busy_s=trace.busy_s(), window_s=trace.window_s)
        breakdown = trace.breakdown()
    ctx.info("end", total_s=round(time.perf_counter() - t_start, 2),
             **compiles.snapshot())
    print(result_line(obs, metrics, device_out, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
