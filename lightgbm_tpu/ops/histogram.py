"""Histogram construction: the hottest op of GBDT training.

Reference analog: ``DenseBin::ConstructHistogramInner``
(``src/io/dense_bin.hpp:76-105``) and the OpenCL kernels
(``src/treelearner/ocl/histogram256.cl``). On TPU there is no fast
scatter-add, so the op is reformulated:

  * ``histogram_scatter`` — ``jax.ops.segment_sum`` per feature. Fast on
    CPU (tests), poor on TPU; the correctness reference.
  * ``histogram_onehot`` — chunked one-hot contraction
    ``onehot(bin)[n, F, B] x ghc[n, 3] -> [F, B, 3]`` that XLA maps onto
    the MXU. TPU path until the Pallas kernel (ops/hist_pallas.py) lands.

Inputs are the whole binned matrix plus a per-row leaf mask; the
smaller-child + subtraction trick (serial_tree_learner.cpp:434-436) lives
in the learner, not here.

Histogram layout: ``[F, B, 3]`` float32 with channels (sum_grad, sum_hess,
count). The reference stores (grad, hess) pairs and derives counts from
hessians (feature_histogram.hpp:565,581); we carry exact counts instead —
cheap on TPU and exact under sample weights.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..utils.device import on_tpu


def make_ghc(grad: jnp.ndarray, hess: jnp.ndarray,
             weight_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Stack (grad, hess, count) channels, optionally bagging-masked.

    The count channel is the *selection indicator* (weight > 0), not the
    weight itself: GOSS up-weights sampled small-gradient rows
    (goss.hpp:92) but each selected row still counts as one datum for
    min_data_in_leaf, matching the reference's partition-based counts.
    """
    ones = jnp.ones_like(grad)
    if weight_mask is not None:
        ghc = jnp.stack([grad * weight_mask, hess * weight_mask,
                         (weight_mask > 0).astype(grad.dtype)], axis=-1)
    else:
        ghc = jnp.stack([grad, hess, ones], axis=-1)
    return ghc


def histogram_scatter(binned: jnp.ndarray, ghc: jnp.ndarray,
                      num_bins: int) -> jnp.ndarray:
    """Per-feature segment-sum histogram. binned [N, F] int, ghc [N, 3]."""
    def one_feature(col):
        return jax.ops.segment_sum(ghc, col, num_segments=num_bins)
    return jax.vmap(one_feature, in_axes=1, out_axes=0)(
        binned.astype(jnp.int32))


def histogram_onehot(binned: jnp.ndarray, ghc: jnp.ndarray,
                     num_bins: int, chunk: int = 16384) -> jnp.ndarray:
    """Chunked one-hot-matmul histogram (MXU-friendly formulation)."""
    n, num_features = binned.shape
    chunk = min(chunk, n)
    num_chunks = (n + chunk - 1) // chunk
    pad = num_chunks * chunk - n
    if pad:
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        ghc = jnp.pad(ghc, ((0, pad), (0, 0)))  # zero ghc: no contribution
    xb = binned.astype(jnp.int32).reshape(num_chunks, chunk, num_features)
    gh = ghc.reshape(num_chunks, chunk, 3)
    bins = jnp.arange(num_bins, dtype=jnp.int32)

    def body(carry, xs):
        xc, gc = xs
        onehot = (xc[:, :, None] == bins[None, None, :]).astype(jnp.float32)
        # HIGHEST precision: histogram sums feed split gains; bf16-rounded
        # MXU inputs (TPU default) cost ~3 decimal digits of gradient sum
        hist = jnp.einsum("cfb,ck->fbk", onehot, gc,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        return carry + hist, None

    init = jnp.zeros((num_features, num_bins, 3), jnp.float32)
    out, _ = jax.lax.scan(body, init, (xb, gh))
    return out


def build_histogram(binned: jnp.ndarray, ghc: jnp.ndarray, num_bins: int,
                    method: str = "auto") -> jnp.ndarray:
    """Dispatch histogram construction. Returns [F, B, 3] float32."""
    if method == "auto":
        method = "onehot" if on_tpu() else "scatter"
    if method == "scatter":
        return histogram_scatter(binned, ghc, num_bins)
    if method == "onehot":
        return histogram_onehot(binned, ghc, num_bins)
    if method == "pallas":
        from .hist_pallas import histogram_pallas
        return histogram_pallas(binned, ghc, num_bins)
    raise ValueError(f"unknown histogram method {method}")


def multival_hist(slots: jnp.ndarray, ghc: jnp.ndarray, g_mv: int,
                  b: int) -> jnp.ndarray:
    """[G_mv, B, 3] histograms of the multi-val pseudo-groups
    (Dataset::ConstructHistogramsMultiVal, dataset.cpp:1170-1273, done
    the XLA way): K scatter-adds over the flat (pseudo*256 + value)
    space, one per slot column. Padding slots (0) accumulate into
    pseudo 0 / value 0, which the debundle never reads — bin 0 is
    always reconstructed from leaf totals."""
    from ..data.bundling import MV_SLOT_STRIDE
    flat = jnp.zeros((g_mv * MV_SLOT_STRIDE, 3), jnp.float32)
    n, k = slots.shape
    if n * k <= 4_000_000:
        # one scatter over the flattened slots (no serialization)
        src = jnp.broadcast_to(ghc[:, None, :], (n, k, 3))
        flat = flat.at[slots.reshape(-1)].add(src.reshape(-1, 3))
    else:
        # large inputs: K chained scatters avoid the [N*K, 3] temp
        for j in range(k):
            flat = flat.at[slots[:, j]].add(ghc)
    hist = flat.reshape(g_mv, MV_SLOT_STRIDE, 3)
    if b <= 256:
        return hist[:, :b, :]
    return jnp.pad(hist, ((0, 0), (0, b - 256), (0, 0)))


def multival_feature_bins(slots: jnp.ndarray, base, nbins):
    """Per-row bins of ONE multi-val feature: the slot holding an
    encoded value in [base, base + nbins - 1) decodes to bins 1.., all
    other rows read the default bin 0 (MultiValBin row scan)."""
    inr = (slots >= base) & (slots < base + nbins - 1)
    return jnp.where(inr, slots - base + 1, 0).sum(axis=1)


def multival_node_bins(mv_slots, col, offset, num_bin, g_dense: int):
    """Per-row bins for per-row NODE vectors (the device tree
    traversals): decode each row's current node's multi-val feature
    from the slot matrix. Shares the encoding with build_mv_slots
    (data/bundling.py: MV_SLOT_STRIDE)."""
    from ..data.bundling import MV_SLOT_STRIDE
    base = ((col - g_dense) * MV_SLOT_STRIDE + offset)[:, None]
    return multival_feature_bins(mv_slots, base, num_bin[:, None])


def debundle_totals(hist_g: jnp.ndarray, g, h, c, local_hist: bool):
    """Leaf totals for debundle_hist's bin-0 reconstruction. A comm
    that keeps histograms shard-LOCAL (voting) must debundle with
    LOCAL totals — any one group's bins sum to the shard's leaf rows —
    while globally-reduced histograms use the global g/h/c."""
    if local_hist:
        t = hist_g[0].sum(axis=0)
        return t[0], t[1], t[2]
    return g, h, c


def debundle_leaf_hist(hist_g: jnp.ndarray, meta, g, h, c,
                       local_hist: bool) -> jnp.ndarray:
    """One-call EFB debundle for a leaf scan: pick the right totals
    (shard-local vs global) and expand group histograms to per-feature
    histograms. The single entry point for every grow loop."""
    tg, th, tc = debundle_totals(hist_g, g, h, c, local_hist)
    return debundle_hist(hist_g, meta.group, meta.offset, meta.num_bins,
                         tg, th, tc)


def debundle_hist(hist_g: jnp.ndarray, group: jnp.ndarray,
                  offset: jnp.ndarray, num_bins: jnp.ndarray,
                  leaf_g, leaf_h, leaf_c) -> jnp.ndarray:
    """EFB group histograms -> per-feature histograms.

    hist_g: [G, B, 3] histograms over bundled columns. For feature f
    with offset o > 0, its bins 1..nb-1 live at group bins
    o..o+nb-2 (data/bundling.py layout) and bin 0 is reconstructed
    from the leaf totals — Dataset::FixHistogram semantics
    (dataset.cpp:1424-1442). offset 0 = raw passthrough. Returns
    [F, B, 3].
    """
    b = hist_g.shape[1]
    hf = hist_g[group]                               # [F, B, 3]
    bins = jnp.arange(b, dtype=jnp.int32)[None, :]   # [1, B]
    valid = (bins >= 1) & (bins < num_bins[:, None])
    # feature bin k is group bin o - 1 + k: the group's row moved left
    # by o - 1, a row gather and then one static roll a bit of the
    # shift (what wraps around lands in slots ``valid`` masks: the
    # last bin read, o + nb - 2, is inside the group's budget). An
    # index a bin (``take_along_axis``) is F x B element gathers, which
    # the TPU runs one at a time: 10.5 ms for both children of a split
    # at 4,228 features against 1.6 ms for this (chip, PERF.md PR 33;
    # ``tools/check_kernels_on_chip.py debundle`` holds the two equal)
    shift = jnp.where(offset > 0, offset - 1, 0)
    gathered = hf
    for bit in range(max(b - 1, 1).bit_length()):
        take = ((shift >> bit) & 1).astype(bool)[:, None, None]
        gathered = jnp.where(take, jnp.roll(gathered, -(1 << bit), axis=1),
                             gathered)
    x = jnp.where(valid[:, :, None], gathered, 0.0)
    sums = x.sum(axis=1)                             # [F, 3]
    f = hf.shape[0]
    totals = jnp.stack([jnp.broadcast_to(leaf_g, (f,)),
                        jnp.broadcast_to(leaf_h, (f,)),
                        jnp.broadcast_to(leaf_c, (f,))], axis=-1)
    x = x.at[:, 0, :].set(totals - sums)
    bundled = (offset > 0)[:, None, None]
    return jnp.where(bundled, x, hf)


def fix_histogram(hist: jnp.ndarray, parent_g: jnp.ndarray,
                  parent_h: jnp.ndarray, parent_c: jnp.ndarray,
                  most_freq_bins: jnp.ndarray) -> jnp.ndarray:
    """Reconstitute an elided most-frequent bin from leaf totals.

    Analog of ``Dataset::FixHistogram`` (dataset.cpp:1424-1442). Our dense
    device layout always materializes every bin, so this is only used by
    learners that zero the most-frequent bin to save bandwidth (e.g. the
    distributed reduce path can skip it and restore post-reduction).

    hist: [F, B, 3]; most_freq_bins: [F] int32.
    """
    f = hist.shape[0]
    totals = hist.sum(axis=1)  # [F, 3] without the elided bin
    parent = jnp.stack([jnp.broadcast_to(parent_g, (f,)),
                        jnp.broadcast_to(parent_h, (f,)),
                        jnp.broadcast_to(parent_c, (f,))], axis=-1)
    missing = parent - totals
    onehot = jax.nn.one_hot(most_freq_bins, hist.shape[1], dtype=hist.dtype)
    return hist + onehot[:, :, None] * missing[:, None, :]
