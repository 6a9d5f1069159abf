"""Layer: iteration_driver. Device time under
``lgbm.score_update`` (the scatter-add of leaf values into the training
score) over the traced trees, milliseconds."""

from .. import scopes


def read(facts):
    return scopes.ms_per(facts, ("SCORE_UPDATE",), scopes.trees(facts))
