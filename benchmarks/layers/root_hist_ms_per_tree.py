"""Layer: kernels. Device time under ``lgbm.grow.root`` (the root
histogram over every row, its scan and the making of the tree's first
state) over the traced trees, milliseconds."""

from .. import scopes


def read(facts):
    return scopes.ms_per(facts, ("GROW_ROOT",), scopes.trees(facts))
