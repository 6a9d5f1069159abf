"""Layer: gradients. Device time of a ranking objective's whole
gradient program over the traced trees, milliseconds: what is under
``lgbm.gradients`` itself plus its three parts
(``lgbm.gradients.rank.layout``, ``.sort``, ``.pairs``).
``benchmarks/scopes.py`` books an instruction to its innermost scope,
so ``grad_ms_per_tree`` holds only what is outside the three."""

from ._rank import PARTS, ms_per_tree


def read(facts):
    return ms_per_tree(facts, ("GRADIENTS",) + PARTS)
