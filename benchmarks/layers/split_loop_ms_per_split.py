"""Layer: grow_loop. Device time under ``lgbm.grow.splits``
(the grow ``while``: the megakernel and what XLA runs around it) over
the splits grown by the traced trees, milliseconds."""

from .. import scopes
from ._common import splits


def read(facts):
    return scopes.ms_per(facts, ("GROW_SPLITS",), splits(facts))
