"""Layer: kernels. The Pallas kernels of the grow loop against their
roofline, percent: the least time the chip could take for the bytes the
algorithm needs, over the time the kernels took. The bytes: per split,
the parent's rows are partitioned (read and written) and the smaller
child's rows are read for its histogram; the root histogram reads every
row (``benchmarks/peaks.py``). The bound is HBM bandwidth: the
histogram is additions, a few operations a byte, far under the
compute peak."""

from ..peaks import peaks_for, tree_bytes
from ..trace_reduce import MOSAIC


def read(facts):
    trace, trees = facts.get("trace"), facts.get("traced_trees")
    if trace is None or not trees:
        return None
    kernel_s = trace.time_matching(MOSAIC)
    if kernel_s <= 0:
        return None
    need = sum(tree_bytes(t["split_rows"], t["smaller_child_rows"],
                          facts["features"]) for t in trees)
    least_s = need / facts["chips"] \
        / (peaks_for(facts["device_kind"])["hbm_gbps"] * 1e9)
    return 100.0 * least_s / kernel_s
