"""Layer: grow_loop. Device time under ``lgbm.cat_scan``
(``per_feature_categorical`` and its merge with the numeric scan: the
sort by gradient statistic and the walk from both ends, for both
children of a split; the root's one scan a tree is in it too)
over the splits grown by the traced trees, milliseconds."""

from ._split_phases import ms_per_split


def read(facts):
    return ms_per_split(facts, "CAT_SCAN")
