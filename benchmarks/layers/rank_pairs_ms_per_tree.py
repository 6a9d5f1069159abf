"""Layer: gradients. Device time under ``lgbm.gradients.rank.pairs``
(the ``[C, L, L]`` pair block of every length class, its sums and the
normalisation) over the traced trees, milliseconds."""

from ._rank import ms_per_tree


def read(facts):
    return ms_per_tree(facts, ("RANK_PAIRS",))
