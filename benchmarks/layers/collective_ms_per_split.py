"""Layer: collectives. Time in XLA's collective operations per split
grown in the traced steps, milliseconds, averaged over the chips."""

from ._common import splits


def read(facts):
    trace, n = facts.get("trace"), splits(facts)
    if trace is None or not n:
        return None
    total, _exposed = trace.collective_times()
    if total <= 0:
        return None
    return total * 1e3 / n
