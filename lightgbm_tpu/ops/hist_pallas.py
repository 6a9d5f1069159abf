"""Pallas TPU histogram kernel over a dynamic row segment.

Reference analog: the OpenCL histogram kernels
(``src/treelearner/ocl/histogram256.cl``) + ``DenseBin::
ConstructHistogramInner`` (dense_bin.hpp:76-105). The GPU reference
scatter-adds into workgroup-local memory with float atomics; TPUs have
no scatter-add, so the kernel is reformulated for the MXU: per bin b,

    hist[b] += lhs[win, 8]^T @ (mat == b)[win, C]

one bf16 matmul whose one-hot factor is exact and whose gh operand is a
bf16 hi/lo pair summing to the f32 value — full f32 fidelity on the
bf16 datapath (the reference's ``gpu_use_dp`` story one level up,
gpu_tree_learner.cpp:299).

**Single training-matrix layout.** Everything a tree build touches
rides in ONE row-major uint8 matrix (the TPU analog of the reference
packing 4 dense feature groups per 32-bit word, Feature4,
gpu_tree_learner.h:75-77):

    cols [0, F)        feature bins (u8)
    col  F+0..3        grad f32 bytes (little-endian)
    col  F+4..7        hess f32 bytes
    col  F+8           bagging/count indicator (0/1)
    col  F+9..12       row id (i32 bytes; partition bookkeeping)
    C = round_up(F+13, 128)

Since XLA pads a [N, F] u8 array's minor dim to 128 anyway, these
payload columns are FREE whenever F % 128 <= 115 — and one buffer
means the partition kernel moves rows once and the histogram kernel
issues one DMA stream.

The segment [begin, begin+count) is DYNAMIC — per-leaf cost is
O(leaf rows), not O(N) (the point of partitioned layout; LightGBM
scans only the leaf's rows via DataPartition, data_partition.hpp:161).
DMA windows start at the 8-aligned floor of `begin` (Mosaic granule
for u8 rows); the in-window shift is masked via the gh operand, so no
dynamic VMEM slicing is needed anywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu
from ..utils.jit_registry import register_jit

ALIGN = 8          # Mosaic offset granule for u8 2-D row slices
GH_COLS = 13       # payload columns appended after the features
RID_OFF = 9        # row-id bytes start at column F + RID_OFF

# Mosaic's default scoped-VMEM budget is 16 MB; the nibble kernel's
# statically-unrolled group loop stacks ~34 MB of block intermediates
# at blk=2048 (measured on v5e: "scoped allocation with size 33.93M").
# v5e has 128 MB of VMEM — raise the ceiling rather than shrink the
# block (smaller blocks double the DMA count per row).
VMEM_LIMIT = 100 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def matrix_cols(num_features: int) -> int:
    return _round_up(num_features + GH_COLS, 128)


def matrix_rows(n: int, blk: int = 2048) -> int:
    # slack so any window [base + k*blk, +blk+ALIGN) stays in bounds
    return _round_up(n, blk) + blk + ALIGN


def _split_hi_lo_f32(x):
    """bf16 hi/lo pair summing to f32 x. The hi part TRUNCATES the
    mantissa via integer masking — a plain astype(bf16).astype(f32)
    round-trip is folded to identity under XLA's
    allow-excess-precision, which would silently drop the residual."""
    hi_f32 = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, jnp.uint32)
        & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi_f32.astype(jnp.bfloat16), (x - hi_f32).astype(jnp.bfloat16)


def build_matrix(binned, blk: int = 2048) -> jnp.ndarray:
    """[N, F] int bins -> training matrix [N_pad, C] u8 with row ids."""
    n, f = binned.shape
    mat = jnp.zeros((matrix_rows(n, blk), matrix_cols(f)), jnp.uint8)
    mat = mat.at[:n, :f].set(binned.astype(jnp.uint8))
    rid = jnp.arange(n, dtype=jnp.uint32)
    for k in range(4):
        mat = mat.at[:n, f + RID_OFF + k].set(
            ((rid >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)).astype(
                jnp.uint8))
    return mat


def pack_gh(mat, num_features: int, grad, hess, cnt) -> jnp.ndarray:
    """Write the gh payload columns for rows [0, len(grad))."""
    f = num_features
    planes = []
    for v in (grad, hess):
        u = jax.lax.bitcast_convert_type(v.astype(jnp.float32),
                                         jnp.uint32)
        planes += [((u >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)).astype(
            jnp.uint8) for k in range(4)]
    planes.append((cnt > 0).astype(jnp.uint8))
    payload = jnp.stack(planes, axis=1)            # [n, 9]
    return jax.lax.dynamic_update_slice(mat, payload, (0, f))


def extract_row_ids(mat, num_features: int, n: int) -> jnp.ndarray:
    """Recover i32 row ids from the payload columns (rows [0, n))."""
    f = num_features
    b = [mat[:n, f + RID_OFF + k].astype(jnp.uint32)
         for k in range(4)]
    return (b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)).astype(
        jnp.int32)


LO = 8             # low-nibble size (bin = hi * LO + lo)
PAY = 5            # payload planes: g_hi, g_lo, h_hi, h_lo, cnt
GRP = 3            # features per MXU tile of the nibble kernel
MAX_NIBBLE_F = 192  # nibble-kernel unroll cap (program size; ~1 MB VMEM)

PAYB = 9           # payload bytes the hist kernels decode (g4+h4+cnt)


def _nibble_dma(mat_hbm, buf, sems, base, blk, win, *, compact: bool,
                f_lo: int, nf: int, feat0: int):
    """Input DMA for the nibble kernels. Non-compact streams the full
    row window; compact (feature-sliced wide datasets) copies ONLY the
    slice's columns plus the payload columns into a narrow buffer, so
    HBM read traffic per slice is ~nf+9 columns instead of C — without
    this, an Epsilon-like C=2048 would re-read the whole matrix once
    per slice. Returns (start, wait) taking (slot, i)."""
    def copies(slot, i):
        s = pl.multiple_of(base + i * blk, ALIGN)
        if not compact:
            return [pltpu.make_async_copy(
                mat_hbm.at[pl.ds(s, win), :], buf.at[slot],
                sems.at[slot, 0])]
        return [
            pltpu.make_async_copy(
                mat_hbm.at[pl.ds(s, win), pl.ds(f_lo, nf)],
                buf.at[slot, :, pl.ds(0, nf)], sems.at[slot, 0]),
            pltpu.make_async_copy(
                mat_hbm.at[pl.ds(s, win), pl.ds(feat0, PAYB)],
                buf.at[slot, :, pl.ds(nf, PAYB)], sems.at[slot, 1]),
        ]

    def start(slot, i):
        for cp in copies(slot, i):
            cp.start()

    def wait(slot, i):
        for cp in copies(slot, i):
            cp.wait()

    return start, wait


def _payload_lanes(g_hi, g_lo, h_hi, h_lo, cnt, lhs_p):
    """Route the 5 payload planes into their (.., p) lane pattern —
    the pattern repeats per lo/feature, so one build serves every mask
    tile of the block."""
    pay = [g_hi.astype(jnp.float32), g_lo.astype(jnp.float32),
           h_hi.astype(jnp.float32), h_lo.astype(jnp.float32), cnt]
    pay_b = pay[PAY - 1]
    for p in range(PAY - 2, -1, -1):
        pay_b = jnp.where(lhs_p == p, pay[p], pay_b)
    return pay_b


def _decode_block(mat_i32, feat0: int, shift, rem, win: int):
    """Block decode shared by the histogram kernel and the split-step
    megakernel's phase 0 (ops/split_step_pallas.py): validity mask +
    the payload planes ((g, h) as exact bf16 hi/lo pairs, 0/1 count)
    read back out of the row bytes. Returns
    ``(valid, g_hi, g_lo, h_hi, h_lo, cnt)`` — all [win, 1], cnt f32.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, (win, 1), 0)
    valid = jnp.where((row >= shift) & (row < shift + rem),
                      jnp.float32(1), jnp.float32(0))   # [win, 1]

    def i32b(c):
        return mat_i32[:, c:c + 1]

    def f32col(c):                                   # little-endian f32
        # mul-add instead of shift-or: i32 `<< 16` miscompiles on
        # this Mosaic version (observed on v5e); multiplies are
        # exact (i32 wraparound gives the same bit pattern)
        u = (i32b(c) + i32b(c + 1) * 256 + i32b(c + 2) * 65536
             + i32b(c + 3) * 16777216)
        return jax.lax.bitcast_convert_type(u, jnp.float32)

    g = f32col(feat0 + 0) * valid
    h = f32col(feat0 + 4) * valid
    cnt = mat_i32[:, feat0 + 8:feat0 + 9].astype(jnp.float32) * valid
    g_hi, g_lo = _split_hi_lo_f32(g)
    h_hi, h_lo = _split_hi_lo_f32(h)
    return valid, g_hi, g_lo, h_hi, h_lo, cnt


def _hist_nibble_kernel_grouped(scal_ref,  # SMEM [2] (begin, count)
                                mat_hbm,   # ANY [N_pad, C] u8
                                out_ref,   # VMEM [NG, 120, GRP*H] f32
                                buf, sems,
                                *, blk: int, cols: int, feat0: int,
                                ngroups: int, hi_n: int,
                                f_lo: int = 0, nf: int = 0):
    """Hierarchical (hi/lo nibble) histogram build: ``bin = hi*LO +
    lo``, and per group of GRP features,

        out[(f, lo, p), (f', hi)] += lhs[win, GRP*LO*PAY]^T
                                     @ rhs[win, GRP*H]

    diagonal f == f' blocks are the histogram; cross-feature products
    land in otherwise-idle MXU lanes and are discarded. lo/hi are
    precomputed FULL-WIDTH once per block (3 VPU ops for all features)
    and routed into mask lanes with two selects per group: VPU op cost
    scales with op COUNT x sublanes, not lanes, so packing 3 features'
    masks into one ~full-width tile amortizes each compare/select
    across 3 features (~10 ops per group per block). Payload stays
    exact: lhs entries are the bf16 hi/lo halves of the f32 grad/hess,
    accumulated in f32.

    ``f_lo``/``nf`` histogram the feature SLICE [f_lo, f_lo+nf):
    datasets wider than MAX_NIBBLE_F dispatch one kernel call per
    slice, so program size stays bounded.
    """
    if nf == 0:
        nf = feat0
    compact = nf != feat0
    pay0 = nf if compact else feat0      # payload col base in buf
    col0 = 0 if compact else f_lo        # feature col base in buf
    begin = scal_ref[0]
    count = scal_ref[1]
    nblk = pl.cdiv(count, blk)
    base = (begin // ALIGN) * ALIGN
    shift = begin - base
    win = blk + ALIGN

    m_lhs = GRP * LO * PAY                           # 120
    n_rhs = GRP * hi_n
    dma_start, dma_wait = _nibble_dma(
        mat_hbm, buf, sems, base, blk, win, compact=compact,
        f_lo=f_lo, nf=nf, feat0=feat0)

    out_ref[...] = jnp.zeros_like(out_ref)

    lane_l = jax.lax.broadcasted_iota(jnp.int32, (1, m_lhs), 1)
    lhs_f = lane_l // (LO * PAY)                     # feature-in-group
    lhs_lo = (lane_l % (LO * PAY)) // PAY            # lo value
    lhs_p = lane_l % PAY                             # payload plane
    lane_r = jax.lax.broadcasted_iota(jnp.int32, (1, n_rhs), 1)
    rhs_f = lane_r // hi_n
    rhs_hi = lane_r % hi_n

    @pl.when(nblk > 0)
    def _():
        dma_start(0, 0)

    def block_body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nblk)
        def _():
            dma_start(1 - slot, i + 1)

        dma_wait(slot, i)
        mat_i32 = buf[slot].astype(jnp.int32)        # [win, C']
        # full-width nibble split ONCE for every feature column
        mat_hi = mat_i32 // LO                       # [win, C']
        mat_lo = mat_i32 - mat_hi * LO

        rem = jnp.minimum(count - i * blk, blk)
        _, g_hi, g_lo, h_hi, h_lo, cnt = _decode_block(
            mat_i32, pay0, shift, rem, win)
        pay_b = _payload_lanes(g_hi, g_lo, h_hi, h_lo, cnt,
                               lhs_p)                # [win, m_lhs]

        for gidx in range(ngroups):
            # tail group clamps past-slice columns onto the last
            # feature; garbage lanes are sliced off in the epilogue
            def fcol(m, j):
                c = col0 + min(gidx * GRP + j, nf - 1)
                return m[:, c:c + 1]                 # [win, 1]

            def pick3(m, fl):
                x = jnp.where(fl == 1, fcol(m, 1), fcol(m, 0))
                return jnp.where(fl == 2, fcol(m, 2), x)

            binlo = pick3(mat_lo, lhs_f)             # [win, m_lhs]
            lhs = jnp.where(binlo == lhs_lo, pay_b,
                            0.0).astype(jnp.bfloat16)
            binhi = pick3(mat_hi, rhs_f)             # [win, n_rhs]
            rhs = jnp.where(binhi == rhs_hi, jnp.float32(1),
                            jnp.float32(0)).astype(jnp.bfloat16)
            out_ref[gidx] += jax.lax.dot_general(
                lhs, rhs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [m_lhs, n_rhs]
        return 0

    jax.lax.fori_loop(0, nblk, block_body, 0)


@register_jit("hist_segment_nibble")
@functools.partial(
    jax.jit,
    static_argnames=("num_features", "num_bins", "blk", "interpret",
                     "nibble_cap"))
def _histogram_segment_nibble(mat, begin, count, *, num_features: int,
                              num_bins: int,
                              nibble_cap: int = MAX_NIBBLE_F,
                              blk: int = 2048,
                              interpret: bool = False):
    """Nibble-kernel call -> [F, B, 3] histogram.

    ``nibble_cap`` rides as a STATIC arg resolved by the caller
    (histogram_segment): a module global read here would freeze into
    the jit cache on first trace.
    """
    if blk % ALIGN:
        raise ValueError(f"blk must be a multiple of {ALIGN}, got {blk}")
    _, cols = mat.shape
    f = num_features
    hi_n = -(-num_bins // LO)                        # ceil(B / LO)
    scal = jnp.stack([jnp.asarray(begin, jnp.int32),
                      jnp.asarray(count, jnp.int32)])
    def specs(nf: int) -> dict:
        # sliced (compact) calls stream only nf+PAYB columns per block
        buf_cols = (nf + PAYB) if nf != f else cols
        return dict(
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, blk + ALIGN, buf_cols), jnp.uint8),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )

    def slice_hist(f_lo: int, nf: int) -> jnp.ndarray:
        """[nf, B, PAY] histogram of features [f_lo, f_lo+nf)."""
        ngroups = -(-nf // GRP)
        raw = pl.pallas_call(
            functools.partial(_hist_nibble_kernel_grouped, blk=blk,
                              cols=cols, feat0=f, ngroups=ngroups,
                              hi_n=hi_n, f_lo=f_lo, nf=nf),
            out_shape=jax.ShapeDtypeStruct(
                (ngroups, GRP * LO * PAY, GRP * hi_n), jnp.float32),
            **specs(nf),
        )(scal, mat)
        # [NG, (fl,lo,p), (fr,hi)] -> diagonal fl == fr -> [nf,B,P]
        raw = raw.reshape(ngroups, GRP, LO, PAY, GRP, hi_n)
        diag = jnp.einsum("gjlpjh->gjhlp", raw)      # [NG,GRP,H,LO,P]
        return diag.reshape(ngroups * GRP, hi_n * LO,
                            PAY)[:nf, :num_bins]

    if f <= nibble_cap:
        hist = slice_hist(0, f)
    else:
        # wide datasets: one bounded-program kernel call per feature
        # slice (at most 2 distinct compiled widths: full + tail)
        hist = jnp.concatenate(
            [slice_hist(lo, min(nibble_cap, f - lo))
             for lo in range(0, f, nibble_cap)], axis=0)
    g = hist[..., 0] + hist[..., 1]
    h = hist[..., 2] + hist[..., 3]
    return jnp.stack([g, h, hist[..., 4]], axis=-1)  # [F, B, 3]


def histogram_segment(mat, begin, count, num_bins: int, num_features: int,
                      blk: int = 2048,
                      interpret: bool = False) -> jnp.ndarray:
    """Histogram of rows [begin, begin+count) -> [F, B, 3] f32 by the
    nibble kernel; datasets wider than its unroll cap (MAX_NIBBLE_F)
    run one kernel call per feature slice. ``ops/histogram.py`` is the
    reference the tests compare it with."""
    return _histogram_segment_nibble(
        mat, begin, count, num_features=num_features,
        num_bins=num_bins, blk=blk, interpret=interpret,
        nibble_cap=MAX_NIBBLE_F)


def histogram_pallas(binned, ghc, num_bins: int, blk: int = 2048,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in full-range histogram (ops/histogram.py "pallas" method).

    binned [N, F] int, ghc [N, 3] f32 -> [F, B, 3] f32. Builds the
    training matrix on the fly — the partitioned learner keeps it
    resident instead.
    """
    if interpret is None:
        interpret = not on_tpu()
    n, f = binned.shape
    mat = build_matrix(binned, blk)
    mat = pack_gh(mat, f, ghc[:, 0], ghc[:, 1], ghc[:, 2])
    return histogram_segment(mat, 0, n, num_bins, f, blk=blk,
                             interpret=interpret)
