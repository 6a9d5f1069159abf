"""Exclusive Feature Bundling (EFB).

Reference analog: ``FindGroups`` / ``FastFeatureBundling``
(src/io/dataset.cpp:41-314) + ``FeatureGroup`` offsets
(include/LightGBM/feature_group.h:32-50). Mutually-(nearly-)exclusive
features share one physical column: the TPU training matrix shrinks
from ``[N, F]`` to ``[N, G]`` uint8, which divides BOTH the histogram
kernel work and HBM traffic by F/G on wide-sparse data (the Bosch /
Criteo shape; SURVEY §7 "lean on EFB bundling to densify").

Layout per multi-feature group: value 0 = every member at its default
bin; member ``i`` with ``num_bin_i`` bins owns the value range
``[offset_i, offset_i + num_bin_i - 2]`` (its bins 1..num_bin_i-1),
with ``offset_{i+1} = offset_i + num_bin_i - 1`` and group total
``1 + sum(num_bin_i - 1) <= 256``. Per-feature histograms are
reconstructed at scan time by slicing the group histogram and deriving
bin 0 from the leaf totals (the reference's ``FixHistogram`` trick,
dataset.cpp:1424-1442).

Eligibility: numerical features whose default AND most-frequent bin is
0 (the sparse-feature shape). Others get singleton groups that keep
raw bin values (offset 0), so dense datasets pass through unchanged.

Conflict rule: the reference's at v2.3.2 with its default
``max_conflict_rate = 0.0`` (config.h), as a constant: a feature joins
a group only when it shares no non-default row with the group in the
rows the plan saw, so a bundle is lossless on those rows, not an
approximation. (v3 dropped the parameter for a fixed budget of
``total_sample_cnt / 10000`` rows a group; nothing here grants one.)
The group's bin budget stays <= 256; candidate groups are searched
newest-first with a random sample capped at 100 (dataset.cpp:97-185).
Two greedy passes (natural order and by-descending-nonzero-count) run
and the one with fewer groups wins (FastFeatureBundling,
dataset.cpp:238-302).

Low-density groups (under 40 % of the rows non-default) stay physical
byte columns while the training matrix's row does not grow by them
(``utils/matrix_layout.py matrix_cols``: a row is padded to 128 bytes,
so up to 112 columns cost what one costs). The reference's later CPU
storage choice (v3: dissolve them into a row-wise multi-val bin) is
kept only for what cannot be a column: a set of sparse groups that
would widen the row.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..utils.matrix_layout import matrix_cols

MAX_BIN_PER_GROUP = 256
MAX_SEARCH_GROUP = 100
# multi-val slot encoding stride: slot = pseudo_local * MV_SLOT_STRIDE
# + offset + bin - 1 (build_mv_slots); every decoder must use this
MV_SLOT_STRIDE = MAX_BIN_PER_GROUP


def decode_feature_bin(col, off, nbf):
    """Group-column value -> this feature's bin (0 = default bin).

    ``off == 0`` means raw passthrough. Arithmetic-only so the same
    helper serves numpy host paths and jitted jax paths (no
    module-specific ``where``).
    """
    in_range = (col >= off) & (col < off + nbf - 1)
    fb = (col - off + 1) * in_range
    return fb * (off > 0) + col * (off == 0)


def encode_feature_bin(out_col: np.ndarray, bins: np.ndarray,
                       off: int) -> int:
    """Write a feature's non-default bins into its group column in
    place (FeatureGroup::PushData semantics; host-side). Returns the
    rows in which an earlier member of the group was non-default and
    is overwritten: the values a bundle with conflicts loses."""
    nz = bins != 0
    lost = int(np.count_nonzero(out_col[nz]))
    out_col[nz] = (bins[nz].astype(np.int64) + off - 1).astype(
        out_col.dtype)
    return lost


class BundlePlan:
    """Result of bundling: per-inner-feature column/offset maps.

    Multi-val (dataset.cpp:186-231 second round, multi_val_sparse_bin
    .hpp): features whose combined conflicts overflow the shared-
    column budget live in PSEUDO-groups — group ids >= mv_group_start
    that have NO physical matrix column; their per-row values ride a
    padded row-wise slot matrix (Dataset.mv_slots) encoded as
    pseudo_local * 256 + in-group value, and their histograms are
    scatter-accumulated then concatenated after the dense groups'.
    """

    def __init__(self, feature_group: np.ndarray,
                 feature_offset: np.ndarray, num_groups: int,
                 group_num_bins: np.ndarray,
                 mv_group_start: Optional[int] = None):
        self.feature_group = feature_group    # [F] i32 matrix column
        self.feature_offset = feature_offset  # [F] i32, 0 = raw bins
        self.num_groups = num_groups          # incl. mv pseudo-groups
        self.group_num_bins = group_num_bins  # [G] i32
        # first mv pseudo-group id; == num_groups when no multi-val
        self.mv_group_start = (num_groups if mv_group_start is None
                               else mv_group_start)
        # rows in which ``bundle_matrix`` found a second member of a
        # group non-default and overwrote the first (its last call)
        self.conflict_rows = 0

    @property
    def num_dense_groups(self) -> int:
        return self.mv_group_start

    @property
    def has_multival(self) -> bool:
        return self.mv_group_start < self.num_groups

    @property
    def is_identity(self) -> bool:
        return self.num_groups == len(self.feature_group) \
            and (self.feature_offset == 0).all() \
            and not self.has_multival


# rows of a candidate looked at before the rest: the first shared row
# already refuses the group, and two frequent columns share one within
# a few entries
_CONFLICT_PROBE = 4096


def _shares_a_row(mark: np.ndarray, idx: np.ndarray) -> bool:
    """Whether a row of ``idx`` is marked in the group, O(nnz)."""
    return bool(mark[idx[:_CONFLICT_PROBE]].any()
                or mark[idx[_CONFLICT_PROBE:]].any())


def _find_groups(nz_idx: List[Optional[np.ndarray]], nbins: np.ndarray,
                 order: np.ndarray, total: int,
                 seed: int) -> Tuple[List[List[int]], List[int]]:
    """One greedy pass (FindGroups, dataset.cpp:97-185, at a conflict
    budget of 0). ``nz_idx[f]`` is the sorted array of non-default
    sample-row indices of eligible feature f (None = ineligible ->
    singleton). Per-feature storage is O(nnz) like the reference's
    index lists; only per-GROUP marks are dense bool arrays."""
    rng = np.random.RandomState(seed)
    groups: List[List[int]] = []
    marks: List[np.ndarray] = []
    used_cnt: List[int] = []        # non-default rows of the group
    nbin: List[int] = []

    singletons: List[List[int]] = []
    for f in order:
        f = int(f)
        if nz_idx[f] is None:
            singletons.append([f])
            continue
        idx = nz_idx[f]
        nnz = len(idx)
        add_bins = int(nbins[f]) - 1
        available = [g for g in range(len(groups))
                     if used_cnt[g] + nnz <= total
                     and nbin[g] + add_bins <= MAX_BIN_PER_GROUP]
        search: List[int] = []
        if available:
            search.append(available[-1])  # newest first
            rest = available[:-1]
            if len(rest) > MAX_SEARCH_GROUP - 1:
                pick = rng.choice(len(rest), MAX_SEARCH_GROUP - 1,
                                  replace=False)
                rest = [rest[i] for i in pick]
            search.extend(rest)
        best = next((g for g in search
                     if not _shares_a_row(marks[g], idx)), -1)
        if best >= 0:
            groups[best].append(f)
            used_cnt[best] += nnz
            marks[best][idx] = True
            nbin[best] += add_bins
        else:
            groups.append([f])
            mark = np.zeros(total, bool)
            mark[idx] = True
            marks.append(mark)
            used_cnt.append(nnz)
            nbin.append(1 + add_bins)
    # SECOND round (dataset.cpp:186-231): dissolve groups whose used-
    # row density is below 0.4 — their features are candidates for the
    # row-wise multi-val representation when they share rows
    DENSE_THRESHOLD = 0.4
    dense = [used_cnt[g] >= DENSE_THRESHOLD * total
             for g in range(len(groups))]
    # ... unless they fit the row as they are: a byte column of a
    # 128-byte row that is mostly zeros costs the chip nothing extra,
    # and a physical column is what the segment kernels stream
    if matrix_cols(len(groups) + len(singletons)) \
            <= matrix_cols(max(sum(dense) + len(singletons), 1)):
        return groups + singletons, []
    kept = [feats for g, feats in enumerate(groups) if dense[g]]
    second = [fidx for g, feats in enumerate(groups) if not dense[g]
              for fidx in feats]
    multival: List[int] = []
    if second:
        # no row holds two of them -> ONE shared column (the
        # reference's second-round group); else the whole set goes
        # multi-val (row-wise). Documented divergences from
        # dataset.cpp:210-231: (a) the shared column must fit the u8
        # bin budget (the reference lets second-round groups grow
        # wider bins), and (b) multi-val must actually SHRINK the
        # matrix — our slot matrix pads to the max per-row count
        # (i32), unlike the reference's CSR row_ptr, so mid-sparsity
        # sets where 4*max_nnz_per_row >= n_features stay dense
        # singletons
        row_cnt = np.zeros(total, np.int64)
        for fidx in second:
            np.add.at(row_cnt, nz_idx[fidx], 1)
        bins2 = 1 + sum(int(nbins[fidx]) - 1 for fidx in second)
        k_est = int(row_cnt.max(initial=0))
        if k_est <= 1 and bins2 <= MAX_BIN_PER_GROUP:
            kept.append(sorted(second))
        elif 4 * k_est < len(second):
            multival = sorted(second)
        else:
            kept.extend([fidx] for fidx in sorted(second))
    return kept + singletons, multival


def plan_bundles(binned: np.ndarray, num_bins: np.ndarray,
                 eligible: np.ndarray, sample_cnt: int = 100_000,
                 seed: int = 0) -> BundlePlan:
    """Greedy two-pass bundling over the binned matrix
    (FastFeatureBundling, dataset.cpp:238-302)."""
    n, f = binned.shape
    if f == 0:
        return BundlePlan(np.zeros(0, np.int32), np.zeros(0, np.int32),
                          0, np.zeros(0, np.int32))
    take = min(n, sample_cnt)
    if take < n:
        rows = np.sort(np.random.RandomState(seed).choice(
            n, take, replace=False))
        sample = binned[rows]
    else:
        sample = binned
    total = sample.shape[0]
    nz_idx: List[Optional[np.ndarray]] = [
        np.nonzero(sample[:, j])[0] if eligible[j] else None
        for j in range(f)]
    return plan_bundles_from_nonzeros(nz_idx, num_bins, total, seed)


def plan_bundles_from_nonzeros(nz_idx: List[Optional[np.ndarray]],
                               num_bins: np.ndarray, total: int,
                               seed: int = 0) -> BundlePlan:
    """Plan from per-feature non-default row-index lists directly —
    the sparse path feeds CSC column indices here so the full binned
    sample matrix never materializes (memory O(nnz)). Every bundle is
    lossless on the ``total`` rows the plan saw."""
    f = len(nz_idx)
    nnz = np.asarray([0 if ix is None else len(ix) for ix in nz_idx],
                     np.int64)

    natural = np.arange(f)
    by_cnt = np.argsort(-nnz, kind="stable")
    g1, mv1 = _find_groups(nz_idx, num_bins, natural, total, seed)
    g2, mv2 = _find_groups(nz_idx, num_bins, by_cnt, total, seed)
    if len(g2) + (1 if mv2 else 0) < len(g1) + (1 if mv1 else 0):
        groups, multival = g2, mv2
    else:
        groups, multival = g1, mv1

    # multi-val pseudo-groups: first-fit features into <=256-value
    # slots appended after the dense groups (no physical column)
    mv_groups: List[List[int]] = []
    mv_bins: List[int] = []
    for fidx in multival:
        add = int(num_bins[fidx]) - 1
        for gi in range(len(mv_groups)):
            if mv_bins[gi] + add <= MAX_BIN_PER_GROUP:
                mv_groups[gi].append(fidx)
                mv_bins[gi] += add
                break
        else:
            mv_groups.append([fidx])
            mv_bins.append(1 + add)
    groups = groups + mv_groups
    mv_group_start = len(groups) - len(mv_groups)

    feature_group = np.zeros(f, np.int32)
    feature_offset = np.zeros(f, np.int32)
    group_num_bins = np.zeros(len(groups), np.int32)
    for gid, feats in enumerate(groups):
        if len(feats) == 1 and gid < mv_group_start:
            feature_group[feats[0]] = gid
            feature_offset[feats[0]] = 0  # raw bins pass through
            group_num_bins[gid] = num_bins[feats[0]]
        else:
            off = 1
            for fidx in feats:
                feature_group[fidx] = gid
                feature_offset[fidx] = off
                off += int(num_bins[fidx]) - 1
            group_num_bins[gid] = off
    if mv_groups and mv_group_start == 0:
        # every feature went multi-val: keep ONE dummy dense group so
        # the physical matrix has a column and group ids stay aligned
        # with binned.shape[1] == mv_group_start
        feature_group += 1
        group_num_bins = np.concatenate(
            [np.asarray([2], np.int32), group_num_bins])
        mv_group_start = 1
        groups = [[]] + groups
    return BundlePlan(feature_group, feature_offset, len(groups),
                      group_num_bins, mv_group_start)


def bundle_matrix(binned: np.ndarray, plan: BundlePlan) -> np.ndarray:
    """[N, F] raw bins -> [N, G_dense] bundled columns
    (FeatureGroup::PushData semantics: non-default values land at their
    offset; where a table the plan did not see puts two members of a
    group in one row, the later feature wins and the row is counted in
    ``plan.conflict_rows``). Multi-val
    pseudo-groups get no column — their values ride the slot matrix
    (build_mv_slots)."""
    n, f = binned.shape
    g_dense = plan.num_dense_groups
    max_b = int(plan.group_num_bins[:g_dense].max(initial=2))
    dtype = np.uint8 if max_b <= 256 else np.uint16
    out = np.zeros((n, max(g_dense, 1)), dtype)
    plan.conflict_rows = 0
    for j in range(f):
        g = plan.feature_group[j]
        if g >= g_dense:
            continue
        off = plan.feature_offset[j]
        col = binned[:, j]
        if off == 0:
            out[:, g] = col.astype(dtype)
        else:
            plan.conflict_rows += encode_feature_bin(out[:, g], col,
                                                     int(off))
    return out


def dense_feature_bins(raw: np.ndarray):
    """``feature_bins`` callback for build_mv_slots over a dense raw-
    bins matrix: (nonzero rows, their bins > 0) of column j — the slot
    encoding contract (only non-default bins are stored)."""
    def feature_bins(j):
        col = raw[:, j]
        rows = np.nonzero(col)[0]
        return rows, col[rows]
    return feature_bins


def build_mv_slots(plan: BundlePlan, n: int,
                   feature_bins) -> np.ndarray:
    """Row-wise padded slot matrix for the multi-val pseudo-groups
    (MultiValSparseBin analog, multi_val_sparse_bin.hpp:26): slot value
    = (pseudo_local * 256 + offset + bin - 1), 0-padded. Bin 0 of each
    pseudo-group is never encoded (offsets start at 1), so padding
    lands in slots the debundle never reads.

    ``feature_bins(j)`` -> (row_idx, bins) of feature j's non-default
    sampled rows (bins in the feature's own space, > 0)."""
    counts = np.zeros(n, np.int64)
    encoded: List[Tuple[np.ndarray, np.ndarray]] = []
    for j in range(len(plan.feature_group)):
        g = plan.feature_group[j]
        if g < plan.mv_group_start:
            continue
        rows, bins = feature_bins(j)
        enc = ((g - plan.mv_group_start) * MV_SLOT_STRIDE
               + plan.feature_offset[j] + bins.astype(np.int64) - 1)
        encoded.append((rows, enc))
        np.add.at(counts, rows, 1)
    k = int(counts.max(initial=0))
    slots = np.zeros((n, max(k, 1)), np.int32)
    fill = np.zeros(n, np.int64)
    for rows, enc in encoded:
        slots[rows, fill[rows]] = enc
        np.add.at(fill, rows, 1)
    return slots
