"""Layer: collectives. Share of the collective time during which no
other operation runs on that chip, percent."""


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    total, exposed = trace.collective_times()
    if total <= 0:
        return None
    return 100.0 * exposed / total
