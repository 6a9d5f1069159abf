"""Constructed (binned) dataset + metadata.

TPU-native analog of the reference ``Dataset``/``Metadata``
(``include/LightGBM/dataset.h:41-678``, ``src/io/dataset.cpp``,
``src/io/metadata.cpp``): after binning, the feature matrix is a dense
``uint8``/``uint16`` array ``[num_data, num_used_features]`` that is shipped
to TPU HBM verbatim — there are no FeatureGroup objects on device; EFB-style
bundling (dataset.cpp:97-314) collapses *columns before upload* instead of
packing bins at access time (see ``lightgbm_tpu/data/bundling.py``).

``Metadata`` mirrors dataset.h:41-249: label / weight / query boundaries /
query weights / init_score, including query-boundary construction from group
sizes (metadata.cpp).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..config import Config
from ..observability import scopes
from ..observability.telemetry import get_telemetry
from ..utils.log import log_fatal, log_info, log_warning
from .binning import (BIN_TYPE_CATEGORICAL, BIN_TYPE_NUMERICAL,
                      BinMapper, kZeroThreshold)


def _bundle_eligible(m: BinMapper) -> bool:
    """A column EFB may put in a shared byte column: numeric, its
    default and most frequent bin both 0 (the sparse-feature shape),
    bins in a byte."""
    return (m.bin_type == BIN_TYPE_NUMERICAL and m.most_freq_bin == 0
            and m.default_bin == 0 and m.num_bin <= 256)


def load_forced_bins(path: str) -> Dict[int, List[float]]:
    """Parse a forced-bin-bounds JSON file
    (``forcedbins_filename``; DatasetLoader::GetForcedBins,
    src/io/dataset_loader.cpp:1203-1236): a list of
    ``{"feature": i, "bin_upper_bound": [...]}`` entries."""
    import json
    if not path:
        return {}
    if not os.path.exists(path):
        log_warning(f"Forced bins file {path} does not exist")
        return {}
    with open(path) as fh:
        entries = json.load(fh)
    out: Dict[int, List[float]] = {}
    for e in entries:
        out[int(e["feature"])] = [float(v)
                                  for v in e["bin_upper_bound"]]
    return out


def is_sparse(data) -> bool:
    """True for scipy sparse matrices (guarded import)."""
    try:
        import scipy.sparse as sp
        return sp.issparse(data)
    except ImportError:  # pragma: no cover
        return False


class Metadata:
    """Labels and side information (dataset.h:41-249)."""

    def __init__(self, num_data: int = 0):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None          # float32 [N]
        self.weights: Optional[np.ndarray] = None        # float32 [N]
        self.query_boundaries: Optional[np.ndarray] = None  # int32 [nq+1]
        self.query_weights: Optional[np.ndarray] = None  # float32 [nq]
        self.init_score: Optional[np.ndarray] = None     # float64 [N*k]

    def set_label(self, label: Sequence[float]) -> None:
        label = np.asarray(label, dtype=np.float32).ravel()
        if self.num_data and len(label) != self.num_data:
            log_fatal(f"Length of label ({len(label)}) doesn't match "
                      f"num_data ({self.num_data})")
        self.label = label
        self.num_data = len(label)

    def set_weights(self, weights: Optional[Sequence[float]]) -> None:
        if weights is None:
            self.weights = None
            return
        weights = np.asarray(weights, dtype=np.float32).ravel()
        if self.num_data and len(weights) != self.num_data:
            log_fatal(f"Length of weights ({len(weights)}) doesn't match "
                      f"num_data ({self.num_data})")
        self.weights = weights
        self._update_query_weights()

    def set_query(self, group: Optional[Sequence[int]]) -> None:
        """Set query structure from per-query sizes (the .query-file /
        set_group convention). Boundary arrays (first element 0, last
        num_data, nondecreasing) are also accepted when they cannot be
        row-count vectors."""
        if group is None:
            self.query_boundaries = None
            self.query_weights = None
            return
        group = np.asarray(group, dtype=np.int64).ravel()
        if len(group) == 0:
            log_fatal("group/query must be non-empty")
        if group.sum() == self.num_data:
            boundaries = np.concatenate([[0], np.cumsum(group)])
        elif group[0] == 0 and group[-1] == self.num_data \
                and (np.diff(group) >= 0).all():
            boundaries = group
        else:
            log_fatal("Sum of query counts doesn't match num_data")
        self.query_boundaries = boundaries.astype(np.int32)
        self._update_query_weights()

    def set_init_score(self, init_score: Optional[Sequence[float]]) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64).ravel()

    def _update_query_weights(self) -> None:
        # metadata.cpp: query weight = mean of member weights
        if self.weights is not None and self.query_boundaries is not None:
            qb = self.query_boundaries
            sums = np.add.reduceat(self.weights, qb[:-1])
            cnts = np.diff(qb)
            self.query_weights = (sums / np.maximum(cnts, 1)).astype(
                np.float32)

    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None \
            else len(self.query_boundaries) - 1

    def subset(self, indices: np.ndarray) -> "Metadata":
        out = Metadata(len(indices))
        if self.label is not None:
            out.label = self.label[indices]
        if self.weights is not None:
            out.weights = self.weights[indices]
        if self.init_score is not None:
            k = len(self.init_score) // max(self.num_data, 1)
            mat = self.init_score.reshape(k, self.num_data)
            out.init_score = mat[:, indices].ravel()
        # queries can't be row-subset arbitrarily; caller handles group data
        return out


class Dataset:
    """Binned dataset resident as one dense device-ready matrix.

    Reference analog: ``Dataset`` (dataset.h:326-678). Differences by design:
      * storage is row-major ``[N, F]`` small-int, no per-group Bin objects —
        the TPU histogram kernel reads the matrix directly;
      * ``most_freq_bin`` elision (sparse storage) is not used on device; the
        mapping is kept for model-file parity only.
    """

    def __init__(self):
        self.num_data: int = 0
        self.bin_mappers: List[BinMapper] = []       # per ORIGINAL feature
        self.used_feature_map: List[int] = []        # orig idx -> inner or -1
        self.real_feature_idx: List[int] = []        # inner idx -> orig idx
        self.binned: Optional[np.ndarray] = None     # [N, F_used] uint8/16
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.metadata = Metadata()
        self.max_bin: int = 255
        self.bin_construct_sample_cnt: int = 200000
        self.min_data_in_bin: int = 3
        self.use_missing: bool = True
        self.zero_as_missing: bool = False
        self.monotone_types: List[int] = []
        self.feature_penalty: List[float] = []
        # EFB bundling maps (identity when unbundled): binned is [N, G]
        # with per-inner-feature column + value offset (data/bundling.py)
        self.feature_group: Optional[np.ndarray] = None   # [F] i32
        self.feature_offset: Optional[np.ndarray] = None  # [F] i32
        self.group_num_bins: Optional[np.ndarray] = None  # [G] i32
        # multi-val (row-wise) pseudo-groups: slot matrix [N, K] i32 of
        # (pseudo_local * 256 + offset + bin - 1), 0-padded; groups >=
        # mv_group_start have no physical column (data/bundling.py)
        self.mv_slots: Optional[np.ndarray] = None
        self.mv_group_start: Optional[int] = None
        # rows of THIS table in which a second member of a bundle was
        # non-default and overwrote the first: 0 where the bundling is
        # lossless here, which every plan is on the rows it saw
        # (data/bundling.py)
        self.bundle_conflict_rows: int = 0
        # raw numeric feature values [N, F_used] f32 (NaN preserved),
        # kept only when linear_tree is on: the leaf-linear fits and
        # the linear prediction paths consume raw values, not bins
        # (docs/LinearTrees.md)
        self.raw_numeric: Optional[np.ndarray] = None
        self._binned_device = None
        self._mv_slots_device = None
        self._raw_device = None

    # ------------------------------------------------------------------
    @property
    def binned_device(self):
        """Lazy device copy of the binned matrix (uploaded once)."""
        if self._binned_device is None:
            import jax.numpy as jnp
            self._binned_device = jnp.asarray(self.binned)
        return self._binned_device

    @property
    def mv_slots_device(self):
        """Lazy device copy of the multi-val slot matrix."""
        if self._mv_slots_device is None and self.mv_slots is not None:
            import jax.numpy as jnp
            self._mv_slots_device = jnp.asarray(self.mv_slots)
        return self._mv_slots_device

    @property
    def raw_numeric_device(self):
        """Lazy device copy of the raw numeric matrix (linear trees)."""
        if self._raw_device is None and self.raw_numeric is not None:
            import jax.numpy as jnp
            self._raw_device = jnp.asarray(self.raw_numeric)
        return self._raw_device

    def _store_raw(self, data: np.ndarray) -> None:
        """Keep the inner-feature raw values for leaf-linear models
        (the reference's linear_tree forces keeping raw data too)."""
        idx = np.asarray(self.real_feature_idx, np.int64)
        self.raw_numeric = np.ascontiguousarray(
            np.asarray(data, np.float64)[:, idx], np.float32) \
            if idx.size else np.zeros((data.shape[0], 0), np.float32)

    @property
    def has_multival(self) -> bool:
        return self.mv_slots is not None

    @property
    def num_features(self) -> int:
        return len(self.real_feature_idx)

    @property
    def num_groups(self) -> int:
        """Histogram groups incl. multi-val pseudo-groups
        (== num_features when unbundled)."""
        if self.group_num_bins is not None:
            return len(self.group_num_bins)
        return self.num_features

    @property
    def num_dense_groups(self) -> int:
        """Physical matrix columns (groups below mv_group_start)."""
        if self.mv_group_start is not None:
            return self.mv_group_start
        return self.num_groups

    def bundle_maps(self):
        """(feature_group, feature_offset, group_num_bins) with identity
        defaults for unbundled datasets."""
        f = self.num_features
        if self.feature_group is None:
            return (np.arange(f, dtype=np.int32),
                    np.zeros(f, np.int32), self.num_bins_array())
        return self.feature_group, self.feature_offset, self.group_num_bins

    def count_bundle_telemetry(self) -> None:
        """What this table's bundling came to, as telemetry counters
        set once a table is constructed or loaded (the newest table's
        values stand): logical features, physical matrix columns, rows
        a conflict lost, features in multi-val pseudo-groups."""
        from ..observability.telemetry import get_telemetry
        tel = get_telemetry()
        group, _, _ = self.bundle_maps()
        tel.set_counter("data.bundle_features", self.num_features)
        tel.set_counter("data.bundle_columns", self.num_dense_groups)
        tel.set_counter("data.bundle_conflict_rows",
                        self.bundle_conflict_rows)
        tel.set_counter("data.multival_features", int(
            (np.asarray(group) >= self.num_dense_groups).sum()))

    def bundle_plan(self):
        """The dataset's stored bundling as a BundlePlan (the ONE
        reconstruction shared by valid-set extraction and the
        predictor's re-binning), or None when unbundled."""
        if self.feature_group is None:
            return None
        from .bundling import BundlePlan
        return BundlePlan(self.feature_group, self.feature_offset,
                          len(self.group_num_bins), self.group_num_bins,
                          mv_group_start=self.mv_group_start)

    def num_bin(self, inner_feature: int) -> int:
        return self.bin_mappers[self.real_feature_idx[inner_feature]].num_bin

    def num_bins_array(self) -> np.ndarray:
        return np.asarray([self.num_bin(f) for f in range(self.num_features)],
                          dtype=np.int32)

    def feature_mapper(self, inner_feature: int) -> BinMapper:
        return self.bin_mappers[self.real_feature_idx[inner_feature]]

    def inner_feature_index(self, orig_feature: int) -> int:
        return self.used_feature_map[orig_feature]

    # ------------------------------------------------------------------
    @classmethod
    def from_numpy(cls, data: np.ndarray, config: Config,
                   label: Optional[Sequence[float]] = None,
                   weight: Optional[Sequence[float]] = None,
                   group: Optional[Sequence[int]] = None,
                   init_score: Optional[Sequence[float]] = None,
                   feature_names: Optional[List[str]] = None,
                   categorical_features: Sequence[int] = (),
                   forced_bins: Optional[Dict[int, List[float]]] = None,
                   reference: Optional["Dataset"] = None) -> "Dataset":
        """Bin a raw feature matrix (CostructFromSampleData,
        dataset_loader.cpp:528-712, + ExtractFeatures push loop)."""
        data = np.asarray(data)
        if data.ndim != 2:
            log_fatal("Dataset data must be 2-dimensional")
        n, num_features = data.shape
        with get_telemetry().setup_span(
                scopes.DATA_CONSTRUCT, rows=n, columns=num_features,
                source="numpy"):
            self = cls()
            self.num_data = n
            self.num_total_features = num_features
            self.max_bin = config.max_bin
            self.bin_construct_sample_cnt = config.bin_construct_sample_cnt
            self.min_data_in_bin = config.min_data_in_bin
            self.use_missing = config.use_missing
            self.zero_as_missing = config.zero_as_missing
            self.feature_names = feature_names or [
                f"Column_{i}" for i in range(num_features)]

            if reference is not None:
                # valid set aligned with train (CreateValid, dataset.cpp:703)
                self._copy_layout_from(reference)
            else:
                self._find_bins(data, config, categorical_features,
                                forced_bins)
                self._resolve_monotone_and_penalty(config)

            from ..parallel.ingest import row_shards
            self._extract_features(data, row_shards(config))
            if config.linear_tree or (reference is not None
                                      and reference.raw_numeric is not None):
                self._store_raw(data)
            if reference is None:
                self._maybe_bundle(config)
            elif self.feature_group is not None:
                self._bundle_reference_layout()
            self.metadata.num_data = n
            if label is not None:
                self.metadata.set_label(label)
            self.metadata.set_weights(weight)
            self.metadata.set_query(group)
            self.metadata.set_init_score(init_score)
            self.count_bundle_telemetry()
        return self

    def _find_bins(self, data: np.ndarray, config: Config,
                   categorical_features: Sequence[int],
                   forced_bins: Optional[Dict[int, List[float]]]) -> None:
        n = data.shape[0]
        sample_cnt = min(n, self.bin_construct_sample_cnt)
        with get_telemetry().setup_span(
                scopes.DATA_FIND_BINS, sample_rows=sample_cnt,
                columns=data.shape[1]):
            rng = np.random.RandomState(config.data_random_seed)
            if sample_cnt < n:
                sample_idx = np.sort(rng.choice(n, sample_cnt,
                                                replace=False))
            else:
                sample_idx = np.arange(n)
            self._find_bins_from_sample(
                np.asarray(data[sample_idx], np.float64), n, config,
                categorical_features, forced_bins)

    def _find_bins_from_sample(
            self, sample: np.ndarray, n: int, config: Config,
            categorical_features: Sequence[int],
            forced_bins: Optional[Dict[int, List[float]]]) -> None:
        """BinMapper construction from an already-drawn row sample
        (shared by the in-memory and two_round loaders)."""
        num_features = sample.shape[1]
        # distributed bin finding (dataset_loader.cpp:824-1001): with
        # pre-partitioned shards the hosts agree on one global sample
        from ..parallel.distributed import maybe_gather_bin_sample
        sample, n_global = maybe_gather_bin_sample(sample, config, n)
        sample_cnt = sample.shape[0]
        cat_set = set(int(c) for c in categorical_features)
        # feature_pre_filter uses min_data_in_leaf scaled to the sample
        # over the GLOBAL row count (dataset_loader.cpp scaling)
        filter_cnt = int(max(
            config.min_data_in_leaf * sample_cnt / max(n_global, 1), 1)) \
            if config.feature_pre_filter else 0

        self.bin_mappers = []
        for j in range(num_features):
            col = sample[:, j]
            # sample only non-trivial values like the sparse sampler:
            # zeros are implicit (counted via total_sample_cnt)
            nonzero = col[(np.abs(col) > kZeroThreshold) | np.isnan(col)]
            mapper = BinMapper()
            bt = BIN_TYPE_CATEGORICAL if j in cat_set else BIN_TYPE_NUMERICAL
            fb = (forced_bins or {}).get(j, ())
            mapper.find_bin(
                nonzero, total_sample_cnt=sample_cnt,
                max_bin=_max_bin_for(config, j),
                min_data_in_bin=self.min_data_in_bin,
                min_split_data=filter_cnt,
                pre_filter=config.feature_pre_filter,
                bin_type=bt, use_missing=self.use_missing,
                zero_as_missing=self.zero_as_missing,
                forced_upper_bounds=fb)
            self.bin_mappers.append(mapper)

        self._finalize_used_features()

    def _copy_layout_from(self, reference: "Dataset") -> None:
        """Adopt a constructed reference's bin/bundle layout so the new
        dataset aligns with it bit-for-bit (CreateValid,
        dataset.cpp:703 — shared by every loader)."""
        self.bin_mappers = reference.bin_mappers
        self.used_feature_map = reference.used_feature_map
        self.real_feature_idx = reference.real_feature_idx
        self.max_bin = reference.max_bin
        self.feature_names = reference.feature_names
        self.monotone_types = reference.monotone_types
        self.feature_penalty = reference.feature_penalty
        self.feature_group = reference.feature_group
        self.feature_offset = reference.feature_offset
        self.group_num_bins = reference.group_num_bins
        self.mv_group_start = reference.mv_group_start

    def _finalize_used_features(self) -> None:
        self.used_feature_map = []
        self.real_feature_idx = []
        for j, m in enumerate(self.bin_mappers):
            if m.is_trivial:
                self.used_feature_map.append(-1)
            else:
                self.used_feature_map.append(len(self.real_feature_idx))
                self.real_feature_idx.append(j)
        if not self.real_feature_idx:
            log_warning("There are no meaningful features, as all feature "
                        "values are constant.")

    def _maybe_bundle(self, config: Config) -> None:
        """EFB (FindGroups/FastFeatureBundling, dataset.cpp:41-314):
        collapse nearly-exclusive features into shared columns. No-op
        for dense data (every group ends up a singleton)."""
        if not config.enable_bundle or self.num_features < 2:
            return
        from .bundling import plan_bundles
        nb = self.num_bins_array()
        eligible = np.asarray([_bundle_eligible(self.feature_mapper(i))
                               for i in range(self.num_features)])
        if not eligible.any():
            return
        with get_telemetry().setup_span(scopes.DATA_BUNDLE) as sp:
            plan = plan_bundles(self.binned, nb, eligible,
                                sample_cnt=self.bin_construct_sample_cnt,
                                seed=config.data_random_seed)
            sp.set(groups=plan.num_groups)
            if plan.num_groups >= self.num_features \
                    and not plan.has_multival:
                return
            log_info(f"EFB: bundled {self.num_features} features into "
                     f"{plan.num_groups} columns"
                     + (f" ({plan.num_groups - plan.mv_group_start} "
                        "multi-val)" if plan.has_multival else ""))
            self._bundle_binned(plan)
        self.mv_group_start = plan.mv_group_start \
            if plan.has_multival else None
        self.feature_group = plan.feature_group
        self.feature_offset = plan.feature_offset
        self.group_num_bins = plan.group_num_bins

    def _bundle_reference_layout(self) -> None:
        """A valid set's columns into its reference's bundles."""
        plan = self.bundle_plan()
        with get_telemetry().setup_span(scopes.DATA_BUNDLE,
                                        groups=plan.num_groups):
            self._bundle_binned(plan)

    def _bundle_binned(self, plan) -> None:
        """``self.binned`` from per-feature bins to the plan's group
        columns (and slot matrix), counting the rows a conflict lost."""
        from .bundling import (build_mv_slots, bundle_matrix,
                               dense_feature_bins)
        raw = self.binned
        self.binned = bundle_matrix(raw, plan)
        self.bundle_conflict_rows = plan.conflict_rows
        if plan.has_multival:
            self.mv_slots = build_mv_slots(plan, raw.shape[0],
                                           dense_feature_bins(raw))

    def _resolve_monotone_and_penalty(self, config: Config) -> None:
        mt = list(config.monotone_constraints)
        fp = list(config.feature_contri)
        self.monotone_types = [
            (mt[j] if j < len(mt) else 0) for j in self.real_feature_idx] \
            if mt else []
        self.feature_penalty = [
            (fp[j] if j < len(fp) else 1.0) for j in self.real_feature_idx] \
            if fp else []

    def _extract_features(self, data: np.ndarray, shards: int = 1) -> None:
        """The bins of every row. With ``shards`` row shards (the mesh
        learners' count, ``parallel/ingest.py`` ``row_shards``) each
        shard's rows are binned by a worker of their own: the bins are
        those of one worker, bit for bit."""
        from ..parallel import ingest
        n = data.shape[0]
        width = max(self.num_features, 1)
        max_b = max([self.num_bin(f) for f in range(self.num_features)],
                    default=2)
        dtype = np.uint8 if max_b <= 256 else np.uint16
        out = np.zeros((n, width), dtype=dtype)
        tel = get_telemetry()
        parent = tel.current_path()

        def bin_shard(s: int) -> None:
            lo, hi = ingest.shard_bounds(n, shards, s)
            fields = {"shard": s, "rows": hi - lo} if shards > 1 else {}
            with tel.under(parent), tel.setup_span(
                    scopes.DATA_BIN_ROWS,
                    bytes=(hi - lo) * width * np.dtype(dtype).itemsize,
                    **fields):
                for inner, orig in enumerate(self.real_feature_idx):
                    mapper = self.bin_mappers[orig]
                    out[lo:hi, inner] = mapper.values_to_bins(np.asarray(
                        data[lo:hi, orig], dtype=np.float64)).astype(dtype)
        ingest.per_shard(shards, bin_shard)
        self.binned = out

    # ------------------------------------------------------------------
    @classmethod
    def from_file_two_round(
            cls, path: str, config: Config,
            label=None, weight=None, group=None, init_score=None,
            feature_names: Optional[List[str]] = None,
            categorical_features: Sequence[int] = (),
            forced_bins: Optional[Dict[int, List[float]]] = None,
            reference: Optional["Dataset"] = None) -> "Dataset":
        """Memory-bounded two-pass file ingestion (``two_round=true``,
        DatasetLoader::LoadFromFile two_round branch,
        dataset_loader.cpp:201-216): sample + metadata stream in pass
        1, features bin chunk-by-chunk straight into the packed matrix
        in pass 2. Explicit label/weight/group/init_score arguments
        override the file's columns, like the in-memory path."""
        from .file_loader import TwoRoundLoader
        with get_telemetry().setup_span(scopes.DATA_CONSTRUCT,
                                        source="file") as sp:
            loader = TwoRoundLoader(path, config)
            n = loader.count_rows()
            self = cls()
            self.num_data = n
            self.max_bin = config.max_bin
            self.bin_construct_sample_cnt = config.bin_construct_sample_cnt
            self.min_data_in_bin = config.min_data_in_bin
            self.use_missing = config.use_missing
            self.zero_as_missing = config.zero_as_missing

            # ---- pass 1: sample rows (same sorted-choice stream as the
            # in-memory path -> bit-identical BinMappers) + label columns
            sample_cnt = min(n, self.bin_construct_sample_cnt)
            rng = np.random.RandomState(config.data_random_seed)
            if sample_cnt < n:
                sample_idx = np.sort(rng.choice(n, sample_cnt,
                                                replace=False))
            else:
                sample_idx = np.arange(n)
            sample_parts: List[np.ndarray] = []
            labels: List[np.ndarray] = []
            weights: List[np.ndarray] = []
            qids: List[np.ndarray] = []
            r = 0
            num_features = 0
            for X, lab, wt, qid in loader.iter_chunks():
                m = X.shape[0]
                num_features = X.shape[1]
                lo = np.searchsorted(sample_idx, r)
                hi = np.searchsorted(sample_idx, r + m)
                if hi > lo:
                    sample_parts.append(X[sample_idx[lo:hi] - r])
                labels.append(np.asarray(lab, np.float64))
                if wt is not None:
                    weights.append(np.asarray(wt, np.float64))
                if qid is not None:
                    qids.append(np.asarray(qid, np.float64))
                r += m
            if r != n:
                log_fatal(f"two_round load of {path}: pass 1 saw {r} rows "
                          f"but the file has {n}")
            self.num_total_features = num_features
            sp.set(rows=n, columns=num_features)
            self.feature_names = feature_names or loader.feature_names \
                or [f"Column_{i}" for i in range(num_features)]
            sample = (np.concatenate(sample_parts) if sample_parts
                      else np.zeros((0, num_features)))

            if reference is not None:
                self._copy_layout_from(reference)
            else:
                self._find_bins_from_sample(sample, n, config,
                                            categorical_features,
                                            forced_bins)
                self._resolve_monotone_and_penalty(config)

            # ---- pass 2: chunked extraction into the packed matrix
            width = max(self.num_features, 1)
            max_b = max([self.num_bin(f)
                         for f in range(self.num_features)], default=2)
            dtype = np.uint8 if max_b <= 256 else np.uint16
            out = np.zeros((n, width), dtype=dtype)
            r = 0
            for X, _, _, _ in loader.iter_chunks():
                m = X.shape[0]
                for inner, orig in enumerate(self.real_feature_idx):
                    mapper = self.bin_mappers[orig]
                    out[r:r + m, inner] = mapper.values_to_bins(
                        np.asarray(X[:, orig], np.float64)).astype(dtype)
                r += m
            self.binned = out

            if reference is None:
                self._maybe_bundle(config)
            elif self.feature_group is not None:
                self._bundle_reference_layout()

            # ---- metadata: file columns, sidecars, explicit overrides
            f_weight, f_group, f_init = loader.load_sidecars()
            if label is None and labels:
                label = np.concatenate(labels)
            if weight is None:
                weight = f_weight if f_weight is not None else (
                    np.concatenate(weights) if weights else None)
            if group is None:
                if f_group is not None:
                    group = f_group
                elif qids:
                    from .file_loader import _qid_to_group_sizes
                    group = _qid_to_group_sizes(np.concatenate(qids))
            if init_score is None:
                init_score = f_init
            self.metadata.num_data = n
            if label is not None:
                self.metadata.set_label(label)
            self.metadata.set_weights(weight)
            self.metadata.set_query(
                None if group is None else np.asarray(group, np.int64))
            self.metadata.set_init_score(init_score)
            log_info(f"Loaded {n} rows x {num_features} features from "
                     f"{path} in two passes ({loader.fmt})")
            self.count_bundle_telemetry()
        return self

    # ------------------------------------------------------------------
    @classmethod
    def from_scipy(cls, data, config: Config,
                   label: Optional[Sequence[float]] = None,
                   weight: Optional[Sequence[float]] = None,
                   group: Optional[Sequence[int]] = None,
                   init_score: Optional[Sequence[float]] = None,
                   feature_names: Optional[List[str]] = None,
                   categorical_features: Sequence[int] = (),
                   forced_bins: Optional[Dict[int, List[float]]] = None,
                   reference: Optional["Dataset"] = None) -> "Dataset":
        """Bin a scipy sparse matrix without densifying the raw values.

        The SparseBin / MultiValSparseBin story TPU-style
        (src/io/sparse_bin.hpp, multi_val_sparse_bin.hpp): the raw
        float matrix never materializes — bin finding samples each
        CSC column's stored entries (zeros are implicit, exactly the
        reference's sparse sampler), extraction writes binned nonzeros
        straight into the (EFB-bundled) uint8 training matrix, and the
        bundling plan itself is computed from a row sample. Peak extra
        memory is O(nnz + N * num_groups) — for a Bosch-shaped matrix
        that is ~F/G * 64x smaller than densifying to float64.
        """
        import scipy.sparse as sp
        if not sp.issparse(data):
            log_fatal("Dataset.from_scipy requires a scipy.sparse matrix")
        with get_telemetry().setup_span(
                scopes.DATA_CONSTRUCT, rows=data.shape[0],
                columns=data.shape[1], source="scipy"):
            csc = data.tocsc()
            if not csc.has_canonical_format:
                # scipy semantics: duplicate entries SUM. Canonicalize on a
                # copy when tocsc() aliased the caller's arrays — the
                # user's matrix must never be mutated behind their back.
                if csc is data:
                    csc = csc.copy()
                csc.sum_duplicates()
            n, num_features = csc.shape
            self = cls()
            self.num_data = n
            self.num_total_features = num_features
            self.max_bin = config.max_bin
            self.bin_construct_sample_cnt = config.bin_construct_sample_cnt
            self.min_data_in_bin = config.min_data_in_bin
            self.use_missing = config.use_missing
            self.zero_as_missing = config.zero_as_missing
            self.feature_names = feature_names or [
                f"Column_{i}" for i in range(num_features)]

            if reference is not None:
                self._copy_layout_from(reference)
            else:
                self._find_bins_sparse(csc, config, categorical_features,
                                       forced_bins)
                self._resolve_monotone_and_penalty(config)
            self._extract_sparse(csc, config, reference)
            self.metadata.num_data = n
            if label is not None:
                self.metadata.set_label(label)
            self.metadata.set_weights(weight)
            self.metadata.set_query(group)
            self.metadata.set_init_score(init_score)
            self.count_bundle_telemetry()
        return self

    @classmethod
    def from_sampled_columns(cls, col_values: List[np.ndarray],
                             col_indices: List[np.ndarray],
                             num_sample_row: int, num_total_row: int,
                             config: Config,
                             forced_bins: Optional[
                                 Dict[int, List[float]]] = None
                             ) -> "Dataset":
        """Pre-allocate a dataset from per-column NONZERO value samples
        (LGBM_DatasetCreateFromSampledColumn,
        dataset_loader.cpp:CostructFromSampleData): bin mappers and the
        EFB plan come from the sample; rows arrive later through
        ``push_rows`` and are binned straight into the packed matrix —
        the streaming-ingestion path Spark-style integrations use.
        Conflict-overflow (multi-val) bundling is not supported here;
        such plans fall back to unbundled columns."""
        self = cls()
        num_features = len(col_values)
        self.num_data = int(num_total_row)
        self.num_total_features = num_features
        self.max_bin = config.max_bin
        self.bin_construct_sample_cnt = config.bin_construct_sample_cnt
        self.min_data_in_bin = config.min_data_in_bin
        self.use_missing = config.use_missing
        self.zero_as_missing = config.zero_as_missing
        self.feature_names = [f"Column_{i}"
                              for i in range(num_features)]
        filter_cnt = int(max(
            config.min_data_in_leaf * num_sample_row
            / max(num_total_row, 1), 1)) \
            if config.feature_pre_filter else 0
        self.bin_mappers = []
        for j in range(num_features):
            colv = np.asarray(col_values[j], np.float64)
            colv = colv[(np.abs(colv) > kZeroThreshold)
                        | np.isnan(colv)]
            mapper = BinMapper()
            mapper.find_bin(
                colv, total_sample_cnt=num_sample_row,
                max_bin=_max_bin_for(config, j),
                min_data_in_bin=self.min_data_in_bin,
                min_split_data=filter_cnt,
                pre_filter=config.feature_pre_filter,
                bin_type=BIN_TYPE_NUMERICAL,
                use_missing=self.use_missing,
                zero_as_missing=self.zero_as_missing,
                forced_upper_bounds=(forced_bins or {}).get(j, ()))
            self.bin_mappers.append(mapper)
        self._finalize_used_features()
        self._resolve_monotone_and_penalty(config)

        max_b = max([self.num_bin(f)
                     for f in range(self.num_features)], default=2)
        self._push_dtype = np.uint8 if max_b <= 256 else np.uint16

        # EFB plan straight from the per-column nonzero samples at
        # their TRUE sampled-row positions (plan_bundles_from_nonzeros
        # — O(sample nnz), no dense sample materializes); multi-val
        # overflow plans are skipped — pushed rows stay unbundled then
        self._push_plan = None
        if config.enable_bundle and self.num_features >= 2:
            from .bundling import plan_bundles_from_nonzeros
            nz_idx: List[Optional[np.ndarray]] = []
            for inner, orig in enumerate(self.real_feature_idx):
                m = self.bin_mappers[orig]
                if not _bundle_eligible(m):
                    nz_idx.append(None)
                    continue
                vals = np.asarray(col_values[orig], np.float64)
                idx = np.asarray(col_indices[orig], np.int64)
                bins = m.values_to_bins(vals)
                nz_idx.append(idx[bins != 0].astype(np.int32))
            if any(ix is not None for ix in nz_idx):
                cand = plan_bundles_from_nonzeros(
                    nz_idx, self.num_bins_array(), num_sample_row,
                    seed=config.data_random_seed)
                if cand.num_groups < self.num_features \
                        and not cand.has_multival:
                    self._push_plan = cand
                    self.feature_group = cand.feature_group
                    self.feature_offset = cand.feature_offset
                    self.group_num_bins = cand.group_num_bins

        width = max(self._push_plan.num_groups if self._push_plan
                    else self.num_features, 1)
        self.binned = np.zeros((int(num_total_row), width),
                               self._push_dtype)
        self._push_filled = 0
        self.metadata.num_data = int(num_total_row)
        return self

    def _bin_rows_raw(self, X: np.ndarray) -> np.ndarray:
        """Bin a raw float block into UNBUNDLED u8/u16 columns."""
        dtype = getattr(self, "_push_dtype", np.uint8)
        out = np.zeros((X.shape[0], max(self.num_features, 1)), dtype)
        for inner, orig in enumerate(self.real_feature_idx):
            out[:, inner] = self.bin_mappers[orig].values_to_bins(
                np.asarray(X[:, orig], np.float64)).astype(dtype)
        return out

    def push_rows(self, X_block: np.ndarray, start_row: int) -> None:
        """Bin a block of raw rows into [start_row, start_row+m)
        (LGBM_DatasetPushRows)."""
        if not hasattr(self, "_push_filled"):
            log_fatal("push_rows needs a dataset created from sampled "
                      "columns (LGBM_DatasetCreateFromSampledColumn)")
        m = X_block.shape[0]
        if start_row < 0 or start_row + m > self.num_data:
            log_fatal(f"push_rows out of range: [{start_row}, "
                      f"{start_row + m}) vs {self.num_data} rows")
        raw = self._bin_rows_raw(np.asarray(X_block, np.float64))
        if self._push_plan is not None:
            from .bundling import bundle_matrix
            raw = bundle_matrix(raw, self._push_plan)
            self.bundle_conflict_rows += self._push_plan.conflict_rows
        self.binned[start_row:start_row + m] = raw
        self._push_filled += m

    def _find_bins_sparse(self, csc, config: Config,
                          categorical_features: Sequence[int],
                          forced_bins) -> None:
        """Per-column FindBin over the CSC nonzeros of a row sample
        (the sparse branch of dataset_loader.cpp sampling: only stored
        values are pushed, zeros ride total_sample_cnt)."""
        n, num_features = csc.shape
        sample_cnt = min(n, self.bin_construct_sample_cnt)
        with get_telemetry().setup_span(
                scopes.DATA_FIND_BINS, sample_rows=sample_cnt,
                columns=num_features):
            rng = np.random.RandomState(config.data_random_seed)
            in_sample = None
            if sample_cnt < n:
                sample_idx = rng.choice(n, sample_cnt, replace=False)
                in_sample = np.zeros(n, bool)
                in_sample[sample_idx] = True
            cat_set = set(int(c) for c in categorical_features)

            indptr, indices, vals = csc.indptr, csc.indices, csc.data
            col_samples: List[np.ndarray] = []
            for j in range(num_features):
                colv = vals[indptr[j]:indptr[j + 1]]
                if in_sample is not None:
                    rows_j = indices[indptr[j]:indptr[j + 1]]
                    colv = colv[in_sample[rows_j]]
                colv = np.asarray(colv, np.float64)
                col_samples.append(colv[(np.abs(colv) > kZeroThreshold)
                                        | np.isnan(colv)])
            # distributed bin finding (dataset_loader.cpp:824-1001, sparse
            # branch): pre-partitioned hosts merge their per-feature
            # nonzero samples so every host derives IDENTICAL BinMappers
            from ..parallel.distributed import maybe_gather_sparse_bin_sample
            col_samples, sample_cnt, n_global = maybe_gather_sparse_bin_sample(
                col_samples, sample_cnt, config, n)
            filter_cnt = int(max(
                config.min_data_in_leaf * sample_cnt / max(n_global, 1), 1)) \
                if config.feature_pre_filter else 0

            self.bin_mappers = []
            for j in range(num_features):
                mapper = BinMapper()
                bt = BIN_TYPE_CATEGORICAL if j in cat_set \
                    else BIN_TYPE_NUMERICAL
                fb = (forced_bins or {}).get(j, ())
                mapper.find_bin(
                    col_samples[j], total_sample_cnt=sample_cnt,
                    max_bin=_max_bin_for(config, j),
                    min_data_in_bin=self.min_data_in_bin,
                    min_split_data=filter_cnt,
                    pre_filter=config.feature_pre_filter,
                    bin_type=bt, use_missing=self.use_missing,
                    zero_as_missing=self.zero_as_missing,
                    forced_upper_bounds=fb)
                self.bin_mappers.append(mapper)
            self._finalize_used_features()

    def _extract_sparse(self, csc, config: Config, reference) -> None:
        """CSC nonzeros -> (bundled) binned matrix, no [N, F]
        intermediate of any type: the EFB plan comes from the columns'
        non-default row lists, which the CSC structure holds already,
        and the matrix is written group column by group column."""
        tel = get_telemetry()
        n = csc.shape[0]
        f_used = self.num_features
        indptr, indices = csc.indptr, csc.indices
        vals = csc.data

        nbins = self.num_bins_array()
        max_b = int(nbins.max(initial=2))
        dtype = np.uint8 if max_b <= 256 else np.uint16

        zero_bin = np.zeros(max(f_used, 1), np.int64)
        bins_nz: List[np.ndarray] = []
        nz_rows: List[np.ndarray] = []      # the rows bins_nz is of
        with tel.setup_span(scopes.DATA_EXTRACT):
            for inner, orig in enumerate(self.real_feature_idx):
                m = self.bin_mappers[orig]
                zero_bin[inner] = int(m.values_to_bins(np.zeros(1))[0])
                bj = m.values_to_bins(np.asarray(
                    vals[indptr[orig]:indptr[orig + 1]],
                    np.float64)).astype(dtype)
                rows_j = indices[indptr[orig]:indptr[orig + 1]]
                if not zero_bin[inner]:
                    # stored values that fall in bin 0, where the
                    # implicit zeros are, need no write and are no
                    # non-default rows to the planner
                    nz = bj != 0
                    if not nz.all():
                        bj, rows_j = bj[nz], rows_j[nz]
                bins_nz.append(bj)
                nz_rows.append(rows_j)

        plan = None
        if reference is not None:
            plan = self.bundle_plan()
        elif config.enable_bundle and f_used >= 2:
            with tel.setup_span(scopes.DATA_BUNDLE_PLAN):
                plan = self._plan_sparse_bundles(nz_rows, nbins, n, config)

        g_dense = plan.num_dense_groups if plan is not None \
            else max(f_used, 1)
        with tel.setup_span(scopes.DATA_EXTRACT):
            # written a group column at a time into a column-major
            # scratch (a column's rows ascend, so each write walks one
            # contiguous array), then turned into rows
            cols = np.zeros((max(g_dense, 1), n), dtype)
            lost = 0
            for inner in range(f_used):
                if plan is not None \
                        and plan.feature_group[inner] >= g_dense:
                    continue  # multi-val: rides the slot matrix below
                rows_j, bj = nz_rows[inner], bins_nz[inner]
                if plan is None or plan.feature_offset[inner] == 0:
                    col = cols[inner if plan is None
                               else plan.feature_group[inner]]
                    if zero_bin[inner]:
                        col[:] = dtype(zero_bin[inner])
                    col[rows_j] = bj
                else:
                    col = cols[plan.feature_group[inner]]
                    off = int(plan.feature_offset[inner])
                    lost += int(np.count_nonzero(col[rows_j]))
                    col[rows_j] = bj + dtype(off - 1)
            out = np.empty((n, cols.shape[0]), dtype)
            for r0 in range(0, n, 1 << 16):
                out[r0:r0 + (1 << 16)] = cols[:, r0:r0 + (1 << 16)].T
            del cols
        self.binned = out
        self.bundle_conflict_rows = lost
        if plan is not None and plan.has_multival:
            from .bundling import build_mv_slots

            def feature_bins(inner):
                return nz_rows[inner], bins_nz[inner].astype(np.int64)

            self.mv_slots = build_mv_slots(plan, n, feature_bins)
            self.mv_group_start = plan.mv_group_start
        if plan is not None and reference is None:
            self.feature_group = plan.feature_group
            self.feature_offset = plan.feature_offset
            self.group_num_bins = plan.group_num_bins

    def _plan_sparse_bundles(self, nz_rows: List[np.ndarray],
                             nbins: np.ndarray, n: int, config: Config):
        """The EFB plan of a sparse table, or None where nothing
        bundles. The planner needs per-feature NON-DEFAULT row sets,
        taken straight from the CSC structure, and it gets them for
        ALL rows, not for a sample, so that no two members of a group
        share a non-default row anywhere in the table: what a plan
        made from a sample cannot promise of rare columns (seven rows
        in the sample miss a group that holds a tenth of the table
        every other time). The planner keeps one bool a row for each
        group it opens, at most what the matrix it plans will take."""
        from .bundling import plan_bundles_from_nonzeros
        eligible = [_bundle_eligible(self.feature_mapper(inner))
                    for inner in range(len(nz_rows))]
        if not any(eligible):
            return None
        cand = plan_bundles_from_nonzeros(
            [r if ok else None for r, ok in zip(nz_rows, eligible)],
            nbins, n, seed=config.data_random_seed)
        if cand.num_groups >= len(nz_rows) and not cand.has_multival:
            return None
        log_info(f"EFB: bundled {len(nz_rows)} sparse features into "
                 f"{cand.num_groups} columns"
                 + (f" ({cand.num_groups - cand.mv_group_start}"
                    " multi-val)" if cand.has_multival else ""))
        return cand

    def create_valid(self, data: np.ndarray,
                     label: Optional[Sequence[float]] = None,
                     weight: Optional[Sequence[float]] = None,
                     group: Optional[Sequence[int]] = None,
                     init_score: Optional[Sequence[float]] = None
                     ) -> "Dataset":
        cfg = Config(max_bin=self.max_bin,
                     bin_construct_sample_cnt=self.bin_construct_sample_cnt,
                     min_data_in_bin=self.min_data_in_bin,
                     use_missing=self.use_missing,
                     zero_as_missing=self.zero_as_missing)
        ctor = Dataset.from_scipy if is_sparse(data) \
            else Dataset.from_numpy
        return ctor(data, cfg, label=label, weight=weight,
                    group=group, init_score=init_score, reference=self)

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Append another dataset's features in place
        (``Dataset::AddFeaturesFrom``, src/io/dataset.cpp /
        dataset.h:497). Both datasets must hold the same rows; ``self``
        keeps its metadata (label/weight/query). Bundled layouts are
        preserved — the other dataset's group columns are appended with
        shifted group ids."""
        if other.num_data != self.num_data:
            log_fatal("Cannot add features from a dataset with "
                      f"{other.num_data} rows to one with "
                      f"{self.num_data} rows")
        if self.has_multival or other.has_multival:
            log_fatal("add_features_from is not supported for multi-val "
                      "datasets (pseudo-group ids cannot be appended)")
        f_self = self.num_features
        base_orig = self.num_total_features

        if self.feature_group is not None \
                or other.feature_group is not None:
            g_s, o_s, b_s = self.bundle_maps()
            g_o, o_o, b_o = other.bundle_maps()
            self.feature_group = np.concatenate(
                [g_s, g_o + len(b_s)]).astype(np.int32)
            self.feature_offset = np.concatenate([o_s, o_o]).astype(
                np.int32)
            self.group_num_bins = np.concatenate([b_s, b_o]).astype(
                np.int32)

        dtype = self.binned.dtype \
            if self.binned.dtype.itemsize >= other.binned.dtype.itemsize \
            else other.binned.dtype
        self.binned = np.concatenate(
            [self.binned.astype(dtype, copy=False),
             other.binned.astype(dtype, copy=False)], axis=1)
        self._binned_device = None

        self.bin_mappers = list(self.bin_mappers) + list(other.bin_mappers)
        self.used_feature_map = list(self.used_feature_map) + [
            (-1 if m < 0 else m + f_self) for m in other.used_feature_map]
        self.real_feature_idx = list(self.real_feature_idx) + [
            r + base_orig for r in other.real_feature_idx]
        self.num_total_features += other.num_total_features
        self.feature_names = list(self.feature_names) + \
            list(other.feature_names)

        def _ext(a, b, fill, n_a, n_b):
            a = list(a) if a else [fill] * n_a
            b = list(b) if b else [fill] * n_b
            return a + b
        f_other = other.num_features
        if self.monotone_types or other.monotone_types:
            self.monotone_types = _ext(self.monotone_types,
                                       other.monotone_types, 0,
                                       f_self, f_other)
        if self.feature_penalty or other.feature_penalty:
            self.feature_penalty = _ext(self.feature_penalty,
                                        other.feature_penalty, 1.0,
                                        f_self, f_other)
        return self

    def subset(self, indices: np.ndarray) -> "Dataset":
        """CopySubset (dataset.cpp) for bagging-style row subsets."""
        indices = np.asarray(indices)
        out = Dataset()
        out.__dict__.update({
            k: v for k, v in self.__dict__.items()
            if k not in ("binned", "metadata", "num_data", "mv_slots",
                         "_binned_device", "_mv_slots_device")})
        out.binned = self.binned[indices]
        out._binned_device = None
        out._mv_slots_device = None
        out.mv_slots = self.mv_slots[indices] \
            if self.mv_slots is not None else None
        out.num_data = len(indices)
        out.metadata = self.metadata.subset(indices)
        return out

    # ------------------------------------------------------------------
    def save_binary(self, path: str) -> None:
        """Binary dataset cache (SaveBinaryFile, dataset.cpp)."""
        import json
        meta = {
            "mappers": [m.to_dict() for m in self.bin_mappers],
            "used_feature_map": self.used_feature_map,
            "real_feature_idx": self.real_feature_idx,
            "feature_names": self.feature_names,
            "num_total_features": self.num_total_features,
            "max_bin": self.max_bin,
            "min_data_in_bin": self.min_data_in_bin,
            "use_missing": self.use_missing,
            "zero_as_missing": self.zero_as_missing,
            "feature_group": None if self.feature_group is None
            else [int(v) for v in self.feature_group],
            "feature_offset": None if self.feature_offset is None
            else [int(v) for v in self.feature_offset],
            "group_num_bins": None if self.group_num_bins is None
            else [int(v) for v in self.group_num_bins],
            "mv_group_start": self.mv_group_start,
            "bundle_conflict_rows": int(self.bundle_conflict_rows),
        }
        with get_telemetry().setup_span(scopes.DATA_SAVE_BINARY) as sp:
            # write to the EXACT path the caller gave (reference .bin
            # convention) — a bare np.savez would silently append .npz
            with open(path, "wb") as fh:
                np.savez(
                    fh, binned=self.binned,
                    mv_slots=self.mv_slots if self.mv_slots is not None
                    else np.zeros((0, 0), np.int32),
                    label=self.metadata.label
                    if self.metadata.label is not None
                    else np.zeros(0, np.float32),
                    weights=self.metadata.weights
                    if self.metadata.weights is not None
                    else np.zeros(0, np.float32),
                    query_boundaries=self.metadata.query_boundaries
                    if self.metadata.query_boundaries is not None
                    else np.zeros(0, np.int32),
                    init_score=self.metadata.init_score
                    if self.metadata.init_score is not None
                    else np.zeros(0, np.float64),
                    meta=np.frombuffer(json.dumps(meta).encode(),
                                       dtype=np.uint8))
            sp.set(bytes=os.path.getsize(path))
        log_info(f"Saved binary dataset to {path}")

    @staticmethod
    def is_binary_file(path: str) -> bool:
        """True when ``path`` is a saved binary dataset
        (DatasetLoader::CheckCanLoadFromBin analog). The zip magic
        alone is not enough — any ``PK``-prefixed file (a real zip, a
        text file starting with "PK") would be routed to the binary
        loader; verify the expected npz members instead and fall
        through to text parsing otherwise."""
        import zipfile
        try:
            with open(path, "rb") as fh:
                if fh.read(2) != b"PK":
                    return False
            with np.load(path, allow_pickle=False) as z:
                return "binned" in z.files and "meta" in z.files
        except (OSError, ValueError, KeyError, zipfile.BadZipFile):
            return False

    def bin_layout_fingerprint(self) -> str:
        """Stable digest of everything that determines where a raw
        value lands in the binned matrix: per-feature bin mappers,
        used-feature map and the EFB group/offset layout. Two datasets
        with equal fingerprints produce bin-compatible matrices; the
        binary-load alignment check (basic.py Dataset.construct, the
        reference's ``CheckAlign``) compares these instead of silently
        evaluating against a mismatched layout."""
        import hashlib
        import json
        payload = {
            "mappers": [m.to_dict() for m in self.bin_mappers],
            "used_feature_map": [int(v) for v in self.used_feature_map],
            "num_total_features": int(self.num_total_features),
            "feature_group": None if self.feature_group is None
            else [int(v) for v in self.feature_group],
            "feature_offset": None if self.feature_offset is None
            else [int(v) for v in self.feature_offset],
            "mv_group_start": self.mv_group_start,
        }
        blob = json.dumps(payload, sort_keys=True, default=float)
        return hashlib.sha1(blob.encode()).hexdigest()

    @classmethod
    def load_binary(cls, path: str) -> "Dataset":
        import json
        tel = get_telemetry()
        with tel.setup_span(scopes.DATA_CONSTRUCT,
                            source="binary") as root:
            with tel.setup_span(scopes.DATA_LOAD_BINARY,
                                bytes=os.path.getsize(path)), \
                    np.load(path) as z:
                meta = json.loads(bytes(z["meta"]).decode())
                self = cls()
                self.bin_mappers = [BinMapper.from_dict(d)
                                    for d in meta["mappers"]]
                self.used_feature_map = meta["used_feature_map"]
                self.real_feature_idx = meta["real_feature_idx"]
                self.feature_names = meta["feature_names"]
                self.num_total_features = meta["num_total_features"]
                self.max_bin = meta["max_bin"]
                self.min_data_in_bin = meta["min_data_in_bin"]
                self.use_missing = meta["use_missing"]
                self.zero_as_missing = meta["zero_as_missing"]
                if meta.get("feature_group") is not None:
                    self.feature_group = np.asarray(meta["feature_group"],
                                                    np.int32)
                    self.feature_offset = np.asarray(meta["feature_offset"],
                                                     np.int32)
                    self.group_num_bins = np.asarray(meta["group_num_bins"],
                                                     np.int32)
                self.binned = z["binned"]
                if meta.get("mv_group_start") is not None:
                    self.mv_group_start = meta["mv_group_start"]
                    self.mv_slots = z["mv_slots"]
                self.bundle_conflict_rows = int(
                    meta.get("bundle_conflict_rows", 0))
                self.num_data = len(self.binned)
                md = Metadata(self.num_data)
                if len(z["label"]):
                    md.set_label(z["label"])
                if len(z["weights"]):
                    md.set_weights(z["weights"])
                if len(z["query_boundaries"]):
                    md.query_boundaries = z["query_boundaries"]
                    md._update_query_weights()
                if len(z["init_score"]):
                    md.init_score = z["init_score"]
                self.metadata = md
            self.count_bundle_telemetry()
            root.set(rows=self.num_data,
                     columns=self.num_total_features)
        return self


def _max_bin_for(config: Config, feature_idx: int) -> int:
    if config.max_bin_by_feature \
            and feature_idx < len(config.max_bin_by_feature):
        return int(config.max_bin_by_feature[feature_idx])
    return config.max_bin
