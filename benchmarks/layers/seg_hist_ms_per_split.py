"""Layer: kernels. Device time under ``lgbm.grow.splits.hist`` (the
smaller child's segment histogram kernel of the per-phase split body
and the sibling's subtraction)
over the splits grown by the traced trees, milliseconds."""

from ._split_phases import ms_per_split


def read(facts):
    return ms_per_split(facts, "SPLITS_HIST")
