"""Layer: grow_loop. Device time under
``lgbm.grow.leaf_of_pos`` (segment bounds to a leaf per position and
the final row ids, after a tree's last split) over the traced trees,
milliseconds."""

from .. import scopes


def read(facts):
    return scopes.ms_per(facts, ("GROW_LEAF_OF_POS",), scopes.trees(facts))
