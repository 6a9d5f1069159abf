"""Layer: gradients. Device time under ``lgbm.gradients`` and
``lgbm.sample`` (the objective's gradients and the row sampling inside
the fused block) over the traced trees, milliseconds."""

from .. import scopes


def read(facts):
    return scopes.ms_per(facts, ("GRADIENTS", "SAMPLE"), scopes.trees(facts))
