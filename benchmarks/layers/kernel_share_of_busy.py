"""Layer: kernels. Share of the device's busy time spent in the
program's Pallas kernels, percent; the rest is XLA's own operations of
the split step."""

from ..trace_reduce import MOSAIC


def read(facts):
    trace = facts.get("trace")
    if trace is None or trace.busy_s() <= 0:
        return None
    return 100.0 * trace.time_matching(MOSAIC) / trace.busy_s()
