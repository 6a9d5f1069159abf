"""Layer: grow_loop. Device busy time in the traced steps over the
splits grown in them (leaves - 1 over their trees), milliseconds."""

from ._common import splits


def read(facts):
    trace, n = facts.get("trace"), splits(facts)
    if trace is None or not n:
        return None
    return trace.busy_s() * 1e3 / n
