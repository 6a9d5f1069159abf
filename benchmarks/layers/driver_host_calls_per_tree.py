"""Layer: iteration_driver. Device programs launched plus blocking
fetches, per tree, over the window (the program's ``host.dispatches``
and ``host.syncs`` counters)."""


def read(facts):
    counters, trees = facts.get("counters"), facts.get("trees_in_window")
    if not counters or not trees:
        return None
    return (counters["host.dispatches"] + counters["host.syncs"]) / trees
