"""Layer: grow_loop. Device time under ``lgbm.grow.splits.scan`` (the numeric
XLA scan of both children of a split and the choice among the
columns; the categorical scan inside it has a scope and a metric of its
own)
over the splits grown by the traced trees, milliseconds."""

from ._split_phases import ms_per_split


def read(facts):
    return ms_per_split(facts, "SPLITS_SCAN")
