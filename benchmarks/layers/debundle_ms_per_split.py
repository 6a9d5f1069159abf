"""Layer: grow_loop. Device time under ``lgbm.grow.splits.debundle``
(a bundled table's split body: both children's group histograms
expanded to one histogram a logical feature, before the scan; the
root's one debundle a tree has a scope of its own under
``lgbm.grow.root`` and is not in it) over the splits grown by the
traced trees, milliseconds. ``None`` on a program that names no such
scope, as every program before the one that bundled on the chip."""

from ._split_phases import ms_per_split


def read(facts):
    return ms_per_split(facts, "SPLITS_DEBUNDLE")
