"""Layer: device. Leaf-operation time of the traced window that no
scope of the fused block owns (instructions without a scope, and other
programs' operations) over busy time, percent."""

from .. import scopes


def read(facts):
    got = scopes.by_scope(facts)
    if got is None or got["busy"] <= 0:
        return None
    return 100.0 * got[scopes.UNATTRIBUTED] / got["busy"]
