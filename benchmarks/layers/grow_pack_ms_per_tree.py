"""Layer: grow_loop. Device time under ``lgbm.grow.pack`` (the
gradient repack in current row order, before a tree's root histogram)
over the traced trees, milliseconds."""

from .. import scopes


def read(facts):
    return scopes.ms_per(facts, ("GROW_PACK",), scopes.trees(facts))
