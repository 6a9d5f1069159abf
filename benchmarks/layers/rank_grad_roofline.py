"""Layer: gradients. The ranking gradients against their roofline,
percent: the least time the chip could take to move the bytes the
gradients need whatever computes them (a document's score and grade
read, its gradient and hessian written, ``GRAD_BYTES_PER_DOC``; the
pairs are arithmetic on what is then on the chip) over the device time
under ``lgbm.gradients`` and its three ranking scopes. HBM-bound by
construction, as ``grow_kernels_roofline`` is: the pair block is
VPU-bound and the layout latency-bound, so the share reads far under
1 % and can never pass 100 %. The bytes come from the documents, never
from how the layout pads them. If the pair block becomes a Pallas
kernel this is that kernel's share too. Read only on a chip with a
published peak."""

from .. import scopes
from ..peaks import peaks_for
from ._rank import PARTS, seconds

# score and grade read, gradient and hessian written, f32 each
GRAD_BYTES_PER_DOC = 16


def grad_bytes(docs: int, trees: int) -> float:
    return float(docs) * GRAD_BYTES_PER_DOC * trees


def read(facts):
    spent_s = seconds(facts, ("GRADIENTS",) + PARTS)
    trees = scopes.trees(facts)
    if not spent_s or not trees:
        return None
    try:
        peak = peaks_for(facts["device_kind"])["hbm_gbps"] * 1e9
    except KeyError:
        return None
    least_s = grad_bytes(facts["rows"], trees) / facts["chips"] / peak
    return 100.0 * least_s / spent_s
