"""Layer: grow_loop. Splits of the traced trees that test membership
in a category set, over all their splits, percent; from the trees (the
runner counts each traced tree's categorical splits into ``facts``)."""

from ._common import splits


def read(facts):
    trees = facts.get("traced_trees") or []
    total = splits(facts)
    if not total or any("cat_splits" not in t for t in trees):
        return None
    return 100.0 * sum(t["cat_splits"] for t in trees) / total
