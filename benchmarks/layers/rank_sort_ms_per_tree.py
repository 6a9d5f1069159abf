"""Layer: gradients. Device time under ``lgbm.gradients.rank.sort``
(two multi-operand sorts a length class: by score with label and
position as payload, and back by position) over the traced trees,
milliseconds."""

from ._rank import ms_per_tree


def read(facts):
    return ms_per_tree(facts, ("RANK_SORT",))
