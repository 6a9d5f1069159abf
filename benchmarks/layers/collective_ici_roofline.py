"""Layer: collectives. The split loop's collectives against the chip's
published inter-chip bandwidth, percent: the least time for the bytes
the data-parallel algorithm must move a split (``benchmarks/ici.py``:
the ring reduce-scatter of the smaller child's ``[F, bins, 3]`` float32
histogram and the two children's winners, counted from the width, the
bin count and the traced trees' splits, never from the program's
buffers) over the time under ``lgbm.grow.splits.collective``, per chip.
The root's collective is left out on both sides: XLA may combine its
all-reduce with collectives of the score, whose bytes are not the
algorithm's. Nothing where the program has no such scope."""

from .. import scopes
from ..ici import ici_gbps, split_bytes
from ._common import splits


def read(facts):
    got = scopes.by_scope(facts)
    n = splits(facts)
    if got is None or not n or facts.get("chips", 1) < 2:
        return None
    name = getattr(got["vocabulary"], "SPLITS_COLLECTIVE", None)
    seconds = got["scopes"].get(name, 0.0) if name else 0.0
    if seconds <= 0:
        return None
    f, d = facts["features"], facts["chips"]
    need = n * split_bytes(f, facts["max_bin"] + 1, d)
    try:
        peak = ici_gbps(facts["device_kind"]) * 1e9
    except KeyError:
        return None                 # no published figure: no share
    return 100.0 * need / peak / seconds
