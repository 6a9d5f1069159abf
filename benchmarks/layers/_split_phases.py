"""Shared by the readers of the per-phase split body's scopes
(``lgbm.grow.splits.partition`` / ``.hist`` / ``.scan`` and
``lgbm.cat_scan``, which a table the compiled megakernel refuses
runs)."""

from .. import scopes
from ._common import splits


def ms_per_split(facts, constant: str):
    """Milliseconds under the scope the program's vocabulary holds as
    ``constant``, over the splits of the traced trees; ``None`` where
    the program has no such scope, no table, or nothing ran under it."""
    got = scopes.by_scope(facts)
    if got is None or not hasattr(got["vocabulary"], constant):
        return None
    return scopes.ms_per(facts, (constant,), splits(facts))
