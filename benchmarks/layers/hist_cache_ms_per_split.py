"""Layer: grow_loop. Device time under ``lgbm.grow.splits.cache`` (the
per-phase split body's traffic with the per-leaf histogram cache: the
parent's histogram read from it, the two children's written into it;
the sibling's subtraction is ``seg_hist_ms_per_split``'s) over the
splits grown by the traced trees, milliseconds."""

from ._split_phases import ms_per_split


def read(facts):
    return ms_per_split(facts, "SPLITS_CACHE")
