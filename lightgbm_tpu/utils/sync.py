"""Device synchronization helpers.

``fetch_one`` is a barrier that hands the caller a VALUE: the device
work that produces it has retired once it arrives. One element only —
callers time hot loops and must not add an O(result) transfer to the
timed region.
"""

from __future__ import annotations

import jax
import numpy as np


def fetch_one(tree):
    """Real device barrier: pull one element of the first non-empty
    array leaf of ``tree`` to host. Returns that element (or None when
    the tree has no non-empty array leaves, e.g. an empty carry)."""
    leaves = [x for x in jax.tree.leaves(tree)
              if hasattr(x, "ravel") and getattr(x, "size", 0)]
    if not leaves:
        return None
    # index on DEVICE first: np.asarray on the full leaf would transfer
    # the whole array before slicing, an O(N) cost inside callers'
    # timed regions
    return np.asarray(leaves[0].ravel()[0])
