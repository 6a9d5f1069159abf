"""The benchmark's own profiler window.

Opens ``jax.profiler`` around a few steps or seconds inside the
measured window, reduces the ``.xplane.pb`` with
``benchmarks/trace_reduce.py`` and throws the file away (traces are
large). The program's own ``ProfileWindow`` is not used: nothing read
what it wrote.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from typing import Optional

from .trace_reduce import Trace, describe, find_xplane


class TraceWindow:
    """One traced stretch of a run. ``start()`` and ``stop()`` are
    called by the cell's runner; ``trace`` then holds the reduced
    trace."""

    def __init__(self, ctx):
        self._ctx = ctx
        self._dir = os.path.join(ctx.out_dir, f"trace-{ctx.cell.name}")
        self.running = False
        self.done = False
        self.trace: Optional[Trace] = None
        self._t_start = 0.0

    def start(self) -> None:
        import jax
        shutil.rmtree(self._dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # host events (level 2) name what the host does in an idle gap;
        # Python's own call events would swamp the trace
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._t_start = time.perf_counter()
        self.running = True

    def stop(self) -> None:
        import jax
        t_stop = time.perf_counter()
        window_s = t_stop - self._t_start
        jax.profiler.stop_trace()
        self.running = False
        t_read = time.perf_counter()
        path = find_xplane(self._dir)
        self.trace = Trace.from_file(
            path, window_s, cpu_stand_in=self._ctx.allow_cpu)
        self._ctx.info("trace", window_s=round(window_s, 4),
                       xplane_mb=round(os.path.getsize(path) / 1e6, 2),
                       events={d: len(o) for d, o
                               in self.trace.devices.items()},
                       stop_s=round(t_read - t_stop, 2),
                       read_s=round(time.perf_counter() - t_read, 2))
        if self._ctx.keep_trace:
            with open(os.path.join(
                    self._ctx.out_dir,
                    f"trace-{self._ctx.cell.name}.summary.json"),
                    "w") as fh:
                json.dump(describe(path), fh, indent=1)
        else:
            shutil.rmtree(self._dir, ignore_errors=True)
        self.done = True
