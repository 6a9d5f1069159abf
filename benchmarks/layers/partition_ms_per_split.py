"""Layer: kernels. Device time under ``lgbm.grow.splits.partition``
(the partition kernel of the per-phase split body with its bitset
table, and the table's making)
over the splits grown by the traced trees, milliseconds."""

from ._split_phases import ms_per_split


def read(facts):
    return ms_per_split(facts, "SPLITS_PARTITION")
