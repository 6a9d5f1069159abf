"""Layer: device. 1 - busy / traced window on the chip that is busy
least, percent."""


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    share = trace.idle_share()
    return None if share is None else 100.0 * share
