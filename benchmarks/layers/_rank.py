"""Shared by the readers of a ranking objective's gradient program
(``lgbm.gradients.rank.*``, ``objective/rank.py``)."""

from .. import scopes

PARTS = ("RANK_LAYOUT", "RANK_SORT", "RANK_PAIRS")


def seconds(facts, constants):
    """Device seconds under the scopes the program's vocabulary holds
    as ``constants``; ``None`` where there is no scope table, the
    program names no ranking scope (every program before the one that
    laid queries out by length, and every cell whose objective is
    elementwise) or none of them ran."""
    got = scopes.by_scope(facts)
    if got is None or not all(hasattr(got["vocabulary"], c)
                              for c in PARTS):
        return None
    names = [getattr(got["vocabulary"], c) for c in constants]
    if not any(getattr(got["vocabulary"], c) in got["scopes"]
               for c in PARTS):
        return None
    return sum(got["scopes"].get(name, 0.0) for name in names)


def ms_per_tree(facts, constants):
    spent, trees = seconds(facts, constants), scopes.trees(facts)
    return 1e3 * spent / trees if spent is not None and trees else None
