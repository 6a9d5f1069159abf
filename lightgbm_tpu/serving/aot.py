"""AOT-compiled predict artifacts: the zero-Python serving hot path.

A text-published model (the pipeline's lingua franca) carries no bin
mappers, so process workers historically served it through the host
route only (ROADMAP item 1). This module closes that gap at PUBLISH
time: the parent — which still holds the dataset-backed booster —
stacks the tree arrays, snapshots the bin mappers and bundle layout,
AOT-lowers and compiles the shape-bucketed leaf-index scan
(``predictor._scan_leaf_idx``) into the persistent compile cache, and
writes everything into one npz bundle next to the cache
(:func:`lightgbm_tpu.utils.compile_cache.artifact_dir`). Workers
replay the bundle: rebuild the stacked arrays from the artifact (no
dataset needed), execute the already-serialized executables (zero
retraces, zero compiles), and gather the float64 leaf values on host
in tree order — bit-identical to host prediction of the same model
text, which is the pipeline's promotion parity standard.

Why a leaf-index scan instead of the existing f32 ``_scan_trees``
accumulator: the f32 device sum differs from the host float64 loop by
~1 ulp, which fails the byte-identical promotion gate. Leaf indices
are exact; the f64 gather + in-order accumulation reproduces the host
loop bit for bit.

Scope cuts (artifact builds refuse, serving degrades to host route):
linear-leaf forests (leaf values depend on raw features, a different
program) and multi-val/EFB-sparse datasets (slot matrices have
data-dependent shapes that defeat shape-bucketed AOT compiles).
"""

from __future__ import annotations

import hashlib
import json
import os
import zipfile
from typing import Optional, Sequence

import numpy as np

from ..utils.log import log_info, log_warning
from .errors import ModelLoadError

AOT_FORMAT = "lightgbm_tpu.serving.aot.v1"


class AotUnavailable(Exception):
    """The model/dataset shape cannot be served via an AOT artifact;
    callers degrade to the host route (never a publish failure)."""


def text_sha(text: str) -> str:
    return hashlib.sha256(text.encode("utf-8")).hexdigest()


def publish_text(source) -> str:
    """Normalize a fleet ``load_model`` source into the model text the
    workers will parse — the string the artifact's sha256 binds to.
    Mirrors procfleet's ``set_model_source`` normalization."""
    if isinstance(source, str):
        if "\n" in source:
            return source
        with open(source, "r") as f:
            return f.read()
    if hasattr(source, "model_to_string"):
        return source.model_to_string()
    raise AotUnavailable(
        f"cannot derive model text from source type "
        f"{type(source).__name__}")


def _resolve_donor(donor):
    """The dataset-backed GBDT behind a donor handle (basic.Booster via
    ``_src()``, or a GBDT/LoadedBooster directly)."""
    if hasattr(donor, "_src"):
        return donor._src()
    if hasattr(donor, "models") and hasattr(donor,
                                            "num_tree_per_iteration"):
        return donor
    raise AotUnavailable(
        f"donor type {type(donor).__name__} is not a booster")


def _np_default(o):
    if hasattr(o, "item"):
        return o.item()
    raise TypeError(f"not JSON serializable: {type(o).__name__}")


def build_artifact(donor, model_text: str,
                   buckets: Sequence[int] = (),
                   out_dir: Optional[str] = None,
                   compile: bool = True) -> str:
    """Build + AOT-compile the predict artifact for ``model_text``.

    ``donor`` supplies the dataset (bin mappers, bundle layout) and the
    finalized trees; ``model_text`` is what the workers will actually
    parse, so when the two are distinct objects the donor's own
    serialization must hash identically — a mismatch would ship an
    artifact for a different model than the text being promoted.

    Returns the artifact path (``<cache>/aot/<sha16>.npz``). Raises
    :class:`AotUnavailable` for unsupported shapes and
    :class:`ModelLoadError` for donor/text disagreement.
    """
    from ..predictor import stack_tree_arrays
    from ..utils.compile_cache import (artifact_dir,
                                       maybe_enable_compile_cache)

    src = _resolve_donor(donor)
    if hasattr(src, "finalize_trees"):
        src.finalize_trees()
    dataset = getattr(src, "learner", None)
    dataset = dataset.dataset if dataset is not None else None
    if dataset is None:
        raise AotUnavailable("donor has no dataset (text-loaded?)")
    if not src.models:
        raise AotUnavailable("donor has no trees")
    if any(not hasattr(m, "threshold_bin") or not hasattr(m, "_col")
           for m in src.models):
        # refit candidates deep-copy text-parsed trees: raw thresholds
        # only, never bound to the window dataset's bin mappers, so no
        # binned traversal exists to compile
        raise AotUnavailable(
            "donor trees carry no binned representation (text-loaded "
            "or refit structures); host route")
    if any(getattr(m, "is_linear", False) for m in src.models):
        raise AotUnavailable("linear-leaf forests serve host-route")
    if dataset.has_multival:
        raise AotUnavailable(
            "multi-val (EFB sparse) datasets have data-dependent slot "
            "shapes; host route")
    sha = text_sha(model_text)
    if donor is not model_text and hasattr(donor, "model_to_string"):
        if text_sha(donor.model_to_string()) != sha:
            raise ModelLoadError(
                "AOT donor booster does not serialize to the model "
                "text being published; refusing to ship a mismatched "
                "artifact")

    k = int(src.num_tree_per_iteration)
    st = stack_tree_arrays(src.models, k)
    t, s1 = st.leaf_vals.shape
    leaf_vals64 = np.zeros((t, s1), np.float64)
    for i, m in enumerate(src.models):
        leaf_vals64[i, :m.num_leaves] = np.asarray(m.leaf_value,
                                                   np.float64)
    group, offset, group_num_bins = dataset.bundle_maps()
    mappers = [dataset.feature_mapper(i).to_dict()
               for i in range(dataset.num_features)]

    out_dir = out_dir or artifact_dir()
    if out_dir is None:
        raise AotUnavailable(
            "no persistent compile cache on this backend "
            "(utils/compile_cache.py); host route")
    path = os.path.join(out_dir, f"{sha[:16]}.npz")
    payload = {
        "format": np.asarray(AOT_FORMAT),
        "model_sha": np.asarray(sha),
        "k": np.asarray(k),
        "num_trees": np.asarray(t),
        "average_output": np.asarray(
            bool(getattr(src, "average_output", False))),
        "num_total_features": np.asarray(
            int(dataset.num_total_features)),
        "binned_dtype": np.asarray(str(dataset.binned.dtype)),
        "feature_group": np.asarray(group, np.int32),
        "feature_offset": np.asarray(offset, np.int32),
        "group_num_bins": np.asarray(group_num_bins, np.int32),
        "num_dense_groups": np.asarray(int(dataset.num_dense_groups)),
        "real_feature_idx": np.asarray(dataset.real_feature_idx,
                                       np.int64),
        "mappers_json": np.asarray(
            json.dumps(mappers, default=_np_default)),
        "leaf_vals64": leaf_vals64,
        "buckets": np.asarray([int(b) for b in buckets], np.int64),
    }
    from ..predictor import StackedTrees
    for f in StackedTrees._BASE_FIELDS:
        payload["st_" + f] = getattr(st, f)
    tmp = path + f".tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as fh:
            np.savez(fh, **payload)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass

    # round-trip through the worker's own load path: a torn or buggy
    # bundle rejects the publish here instead of poisoning the fleet
    art = load_artifact(path, expected_sha=sha)
    if compile:
        maybe_enable_compile_cache()
        n = art.aot_compile(buckets)
        log_info(f"serving aot: artifact {os.path.basename(path)} "
                 f"({t} trees, k={k}) compiled {n} bucket program(s)")
    return path


def maybe_build_artifact(donor, source,
                         buckets: Sequence[int]) -> Optional[str]:
    """Fleet-facing convenience: build the artifact for a publish, or
    return None (host route) when the shape is unsupported or the
    build fails — artifact loss must never fail a model publish."""
    if donor is None:
        return None
    try:
        text = publish_text(source)
        return build_artifact(donor, text, buckets=buckets)
    except AotUnavailable as e:
        log_info(f"serving aot: artifact unavailable ({e}); workers "
                 "serve the host route")
        return None
    except ModelLoadError:
        raise
    except Exception as e:
        log_warning(f"serving aot: artifact build failed ({e}); "
                    "workers serve the host route")
        return None


def load_artifact(path: str, expected_sha: Optional[str] = None
                  ) -> "AotPredict":
    """Load an artifact bundle into an executable :class:`AotPredict`.

    ``expected_sha`` binds the artifact to the model text being loaded
    alongside it (sha256); a mismatch is a publish-pipeline bug and
    raises. Torn/unreadable bundles raise :class:`ModelLoadError`.
    """
    from ..data.binning import BinMapper
    from ..predictor import StackedTrees
    try:
        with np.load(path, allow_pickle=False) as z:
            fmt = str(z["format"])
            if fmt != AOT_FORMAT:
                raise ModelLoadError(
                    f"AOT artifact {path!r} has format {fmt!r}; "
                    f"expected {AOT_FORMAT!r}", path=path)
            sha = str(z["model_sha"])
            if expected_sha is not None and sha != expected_sha:
                raise ModelLoadError(
                    f"AOT artifact {path!r} was built for a different "
                    f"model text (sha {sha[:12]} != "
                    f"{expected_sha[:12]})", path=path)
            k = int(z["k"])
            base = {f: np.asarray(z["st_" + f])
                    for f in StackedTrees._BASE_FIELDS}
            t, s1 = base["leaf_vals"].shape
            st = StackedTrees(
                k, any_linear=False, **base,
                lin_const=np.zeros((t, s1), np.float32),
                lin_coeff=np.zeros((t, s1, 1), np.float32),
                lin_feat=np.full((t, s1, 1), -1, np.int32))
            mappers = [BinMapper.from_dict(d)
                       for d in json.loads(str(z["mappers_json"]))]
            spec = BinSpec(
                mappers,
                feature_group=z["feature_group"],
                feature_offset=z["feature_offset"],
                group_num_bins=z["group_num_bins"],
                num_dense_groups=int(z["num_dense_groups"]),
                real_feature_idx=z["real_feature_idx"],
                num_total_features=int(z["num_total_features"]),
                binned_dtype=np.dtype(str(z["binned_dtype"])))
            return AotPredict(
                st, np.asarray(z["leaf_vals64"], np.float64), spec,
                average_output=bool(z["average_output"]),
                model_sha=sha,
                buckets=tuple(int(b) for b in z["buckets"]),
                path=path)
    except ModelLoadError:
        raise
    except (zipfile.BadZipFile, KeyError, ValueError, OSError) as e:
        raise ModelLoadError(
            f"AOT artifact {path!r} is torn or unreadable: {e}",
            path=path) from e


class BinSpec:
    """Duck-typed stand-in for the Dataset surface that
    ``predictor._bin_data`` consumes — rebuilt from artifact metadata
    so workers can re-bin request rows without any dataset."""

    has_multival = False

    def __init__(self, mappers, feature_group, feature_offset,
                 group_num_bins, num_dense_groups, real_feature_idx,
                 num_total_features, binned_dtype):
        self._mappers = list(mappers)
        self.num_features = len(self._mappers)
        self.binned = np.zeros((0, 0), binned_dtype)  # dtype carrier
        self._group = np.asarray(feature_group, np.int32)
        self._offset = np.asarray(feature_offset, np.int32)
        self._group_num_bins = np.asarray(group_num_bins, np.int32)
        self.num_dense_groups = int(num_dense_groups)
        self.real_feature_idx = np.asarray(real_feature_idx, np.int64)
        self.num_total_features = int(num_total_features)

    def bundle_maps(self):
        return self._group, self._offset, self._group_num_bins

    def feature_mapper(self, inner_feature: int):
        return self._mappers[inner_feature]


class AotPredict:
    """Executable rebuilt from an artifact bundle: device leaf-index
    scan + host float64 gather, bit-identical to the host route."""

    def __init__(self, stacked, leaf_vals64, binspec, average_output,
                 model_sha, buckets, path):
        self.stacked = stacked
        self.leaf_vals64 = leaf_vals64
        self.binspec = binspec
        self.average_output = bool(average_output)
        self.model_sha = model_sha
        self.buckets = tuple(buckets)
        self.path = path
        self.k = int(stacked.k)
        self.num_trees = int(stacked.num_trees)
        self.num_total_features = int(binspec.num_total_features)

    def nbytes(self) -> int:
        return int(self.stacked.nbytes() + self.leaf_vals64.nbytes)

    def aot_compile(self, buckets: Sequence[int] = ()) -> int:
        """``.lower().compile()`` the scan for every row bucket — the
        executables land in the persistent compile cache so any later
        process (worker warm-up, respawn) replays them without
        compiling. Returns the number of programs compiled."""
        import jax.numpy as jnp
        from .. import predictor
        want = sorted({int(b) for b in (tuple(buckets) or self.buckets)
                       if int(b) > 0})
        g = max(self.binspec.num_dense_groups, 1)
        dev = self.stacked.device()
        n = 0
        for b in want:
            zb = jnp.zeros((b, g), self.binspec.binned.dtype)
            predictor._scan_leaf_idx.lower(zb, *dev, None,
                                           False).compile()
            n += 1
        return n

    def warm(self, buckets: Sequence[int] = ()) -> int:
        """Execute one dispatch per bucket through the normal call
        path, populating the in-process jit cache from the persistent
        cache (cache hits, not compiles)."""
        want = sorted({int(b) for b in (tuple(buckets) or self.buckets)
                       if int(b) > 0})
        for b in want:
            self.leaf_idx(np.zeros((b, self.num_total_features)))
        return len(want)

    def leaf_idx(self, data: np.ndarray) -> np.ndarray:
        """[N, T] leaf index per row per tree via the device scan —
        exactly ``Tree.predict_leaf_index`` per tree."""
        import jax
        import jax.numpy as jnp
        from .. import predictor
        data = np.asarray(data, np.float64)
        n = data.shape[0]
        if n == 0:
            return np.zeros((0, self.num_trees), np.int64)
        binned, _ = predictor._bin_data(data, self.binspec)
        if predictor.buckets_enabled():
            b = predictor.bucket_rows(n)
            if b > n:
                binned = np.concatenate(
                    [binned, np.zeros((b - n,) + binned.shape[1:],
                                      binned.dtype)])
        idx = predictor._scan_leaf_idx(
            jnp.asarray(binned), *self.stacked.device(), None, False)
        return np.asarray(jax.device_get(idx), np.int64)[:n]

    def predict_raw(self, data: np.ndarray) -> np.ndarray:
        """Raw scores, bit-identical to the host float64 loop: device
        leaf indices, then an in-order host accumulation of the f64
        leaf values (the explicit per-tree loop matters — pairwise/
        vectorized summation is NOT bit-identical to sequential +=)."""
        idx = self.leaf_idx(data)
        n = idx.shape[0]
        raw = np.zeros((n, self.k))
        for t in range(self.num_trees):
            raw[:, t % self.k] += self.leaf_vals64[t][idx[:, t]]
        if self.average_output and self.num_trees:
            raw /= max(self.num_trees // self.k, 1)
        return raw if self.k > 1 else raw[:, 0]

    def describe(self) -> dict:
        return {"path": self.path, "model_sha": self.model_sha[:16],
                "num_trees": self.num_trees, "k": self.k,
                "buckets": list(self.buckets),
                "nbytes": self.nbytes()}
