"""Layer: iteration_driver. Device idle time (gaps between leaf
operations on the first chip) that falls under the driver's
``lgbm.block.sync`` or ``lgbm.block.trees`` host spans, over the
``lgbm.block.dispatch`` spans in the trace, milliseconds per block."""

from .. import scopes


def read(facts):
    got = scopes.by_scope(facts)
    if got is None:
        return None
    dispatch, sync, trees = got["spans"]
    blocks = got["idle"][dispatch + ".count"]
    if not blocks:
        return None
    return 1e3 * (got["idle"][sync] + got["idle"][trees]) / blocks
