"""Layer: grow_loop. Device time of the whole split body over the
splits grown by the traced trees, milliseconds: ``lgbm.grow.splits``
and, where the program names them, the parts of the per-phase body
(``lgbm.grow.splits.partition`` / ``.hist`` / ``.scan`` and
``lgbm.cat_scan``, which holds the root's one scan a tree too).
``benchmarks/scopes.py`` sums a scope's own instructions, not its
children's, so this is the one number that compares the megakernel's
split with the per-phase kernels' split: in a cell whose body is the
megakernel it equals ``split_loop_ms_per_split``."""

from .. import scopes
from ._common import splits

PARTS = ("GROW_SPLITS", "SPLITS_PARTITION", "SPLITS_HIST", "SPLITS_SCAN",
         "CAT_SCAN")


def read(facts):
    got = scopes.by_scope(facts)
    if got is None:
        return None
    named = tuple(c for c in PARTS if hasattr(got["vocabulary"], c))
    return scopes.ms_per(facts, named, splits(facts))
