"""Layer: predict. Device busy time in the traced stretch over the
batches dispatched in it, milliseconds."""


def read(facts):
    trace, batches = facts.get("trace"), facts.get("traced_batches")
    if trace is None or not batches:
        return None
    return trace.busy_s() * 1e3 / batches
