"""Shared by the per-layer readers of the training cells."""


def splits(facts) -> int:
    """Splits grown by the traced trees: leaves - 1 over the trees."""
    return sum(max(t["leaves"] - 1, 0)
               for t in facts.get("traced_trees", []))
