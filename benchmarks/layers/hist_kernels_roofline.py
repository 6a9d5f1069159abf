"""Layer: kernels. The histogram passes against their roofline,
percent: the least time the chip could take to read the rows the
algorithm histograms (the root's rows and every split's smaller
child's, ``benchmarks/peaks.py`` ``hist_bytes_per_row`` each) over the
device time under ``lgbm.grow.root`` and ``lgbm.grow.splits.hist``.
The bytes come from the traced trees and the table's width, never from
how a kernel cuts its work, so a later kernel is read on the same
work; the time is the two scopes' whole time (the root's scan and the
sibling's subtraction with it), so the share reads low, never high.
The bound is HBM bandwidth: a histogram is a few additions a byte.
Read only where the split body is the per-phase one: the megakernel's
histogram has no scope of its own."""

from .. import scopes
from ..peaks import hist_bytes_per_row, peaks_for
from ..trace_reduce import MOSAIC


def read(facts):
    got = scopes.by_scope(facts)
    trees = facts.get("traced_trees")
    if got is None or not trees \
            or not hasattr(got["vocabulary"], "SPLITS_HIST"):
        return None
    # a chip's kernels: the CPU's interpret twins have no roofline
    if facts["trace"].time_matching(MOSAIC) <= 0:
        return None
    names = [getattr(got["vocabulary"], c)
             for c in ("GROW_ROOT", "SPLITS_HIST")]
    if names[1] not in got["scopes"]:
        return None
    spent_s = sum(got["scopes"].get(name, 0.0) for name in names)
    rows = sum(float(t["split_rows"][0]) if t["split_rows"] else 0.0
               for t in trees) \
        + sum(float(sum(t["smaller_child_rows"])) for t in trees)
    least_s = rows * hist_bytes_per_row(facts["features"]) \
        / facts["chips"] \
        / (peaks_for(facts["device_kind"])["hbm_gbps"] * 1e9)
    return 100.0 * least_s / spent_s
