"""Layer: gradients. Device time under ``lgbm.gradients.rank.layout``
(scores and labels into the query layout, a window a query, and
gradients back to row order, one gather over the documents) over the
traced trees, milliseconds."""

from ._rank import ms_per_tree


def read(facts):
    return ms_per_tree(facts, ("RANK_LAYOUT",))
