"""Public ``Dataset`` / ``Booster`` API.

Reference analog: ``python-package/lightgbm/basic.py`` (Dataset
``:730-1703``, Booster ``:1704-2951``). The reference wraps the C library
through ctypes; here both classes are thin layers over the in-package
framework (``data.Dataset``, ``models.GBDT``, ``io.model_text``) — the
"library boundary" is a Python call, not a C ABI.

Supported data inputs: numpy 2-D arrays, pandas DataFrames (categorical
dtypes auto-detected), python lists, and file paths (CSV/TSV/LibSVM via
``data.file_loader``).
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional

import numpy as np

from .config import Config
from .data.dataset import Dataset as _InnerDataset
from .utils.log import LightGBMError, log_fatal

__all__ = ["Dataset", "Booster", "LightGBMError"]


def _is_pandas_df(data) -> bool:
    try:
        import pandas as pd
        return isinstance(data, pd.DataFrame)
    except ImportError:  # pragma: no cover
        return False


def _data_from_pandas(data, feature_name, categorical_feature):
    """Pandas -> float ndarray + names + categorical indices
    (reference basic.py:331-418 pandas handling)."""
    import pandas as pd
    df = data.copy()
    if feature_name == "auto":
        feature_name = [str(c) for c in df.columns]
    cat_cols = [i for i, c in enumerate(df.columns)
                if isinstance(df[c].dtype, pd.CategoricalDtype)]
    if categorical_feature == "auto":
        categorical_idx = cat_cols
    else:
        categorical_idx = _resolve_categorical(
            categorical_feature, feature_name, len(df.columns))
    # categorical dtype -> integer codes (-1 missing -> NaN)
    pandas_categorical = []
    for i in cat_cols:
        col = df.columns[i]
        pandas_categorical.append(list(df[col].cat.categories))
        codes = df[col].cat.codes.astype(np.float64)
        codes = codes.where(codes >= 0, np.nan)
        df[col] = codes
    mat = df.astype(np.float64).to_numpy()
    return mat, feature_name, categorical_idx, pandas_categorical


def _categorical_from_params(categorical_feature, cfg: Config):
    """The ``categorical_feature`` keyword, or, where it was left at
    ``"auto"``, what ``params`` gave under that name or an alias
    (``cat_feature``, ``categorical_column``, ``cat_column``; basic.py
    Dataset._lazy_init reads the same keys): column indices
    ``"0,3,7"``, or names behind ``name:`` as in a config file."""
    spec = cfg.categorical_feature.strip()
    if categorical_feature != "auto" or not spec:
        return categorical_feature
    if spec.startswith("name:"):
        return [c for c in spec[len("name:"):].split(",") if c]
    return [int(c) for c in spec.split(",") if c.strip()]


def _resolve_categorical(categorical_feature, feature_name,
                         num_features) -> List[int]:
    if categorical_feature in ("auto", None):
        return []
    out = []
    for c in categorical_feature:
        if isinstance(c, str):
            if feature_name in ("auto", None) or c not in feature_name:
                log_fatal(f"Unknown categorical feature name {c}")
            out.append(feature_name.index(c))
        else:
            out.append(int(c))
    return sorted(set(out))


from .data.dataset import is_sparse as _is_sparse


def _to_matrix(data):
    if isinstance(data, np.ndarray):
        return data if data.ndim == 2 else data.reshape(len(data), -1)
    if isinstance(data, (list, tuple)):
        return np.asarray(data, np.float64)
    try:
        import scipy.sparse as sp
        if sp.issparse(data):
            return np.asarray(data.todense(), np.float64)
    except ImportError:  # pragma: no cover
        pass
    raise TypeError(f"Cannot construct Dataset from {type(data).__name__}")


class Dataset:
    """Dataset wrapper with lazy (deferred) construction
    (reference basic.py:730-1703)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"]
                 = None, weight=None, group=None, init_score=None,
                 feature_name="auto", categorical_feature="auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = False):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = copy.deepcopy(params) or {}
        self.free_raw_data = free_raw_data
        self.pandas_categorical: List = []
        self.used_indices: Optional[np.ndarray] = None
        self._inner: Optional[_InnerDataset] = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        """Lazy init (basic.py Dataset._lazy_init)."""
        if self._inner is not None:
            return self
        if self.reference is not None:
            self.reference.construct()
        if self.used_indices is not None:
            # subset of a constructed reference (basic.py:1023-1048)
            parent = self.reference.construct()._inner
            self._inner = parent.subset(self.used_indices)
            if self.group is not None:
                self._inner.metadata.set_query(self.group)
            elif parent.metadata.query_boundaries is not None:
                # whole-query folds: rebuild query sizes from parent ids
                qb = parent.metadata.query_boundaries
                qid = np.repeat(np.arange(len(qb) - 1),
                                np.diff(qb))[self.used_indices]
                change = np.nonzero(np.diff(qid))[0]
                bounds = np.concatenate([[0], change + 1, [len(qid)]])
                self._inner.metadata.set_query(np.diff(bounds))
            return self

        cfg = Config.from_params(self._merged_params())
        # before the table is built, so that a JSONL asked for through
        # the environment or the parameters holds its construction
        from .observability.telemetry import get_telemetry
        tel = get_telemetry()
        tel.ensure_started(cfg)
        data = self.data
        feature_name = self.feature_name
        categorical = _categorical_from_params(self.categorical_feature,
                                               cfg)
        cat_idx: List[int] = []
        if isinstance(data, str) \
                and _InnerDataset.is_binary_file(data):
            # saved binary dataset (DatasetLoader::CheckCanLoadFromBin,
            # dataset_loader.cpp:218): load the cache instead of
            # re-parsing/re-binning text
            self._inner = _InnerDataset.load_binary(data)
            if self.reference is not None \
                    and self.reference._inner is not None:
                # a binary load carries its own frozen bin layout; when
                # the set is bound to a reference (e.g. a valid set on
                # a Booster) the layouts must MATCH — evaluating
                # through mismatched bin boundaries silently produces
                # wrong metrics (Dataset::CheckAlign analog)
                ref = self.reference._inner
                if ref.bin_layout_fingerprint() != \
                        self._inner.bin_layout_fingerprint():
                    log_fatal(
                        f"binary dataset {data!r} was saved with a "
                        "different bin layout than its reference "
                        "(train) set; re-save it with "
                        "reference=<train set> so the bin mappers "
                        "align, or load the text file instead")
            md = self._inner.metadata
            if self.label is not None:
                md.set_label(self.label)
            else:
                self.label = md.label
            if self.weight is not None:
                md.set_weights(self.weight)
            else:
                self.weight = md.weights
            if self.group is not None:
                md.set_query(self.group)
            elif md.query_boundaries is not None:
                self.group = np.diff(md.query_boundaries)
            if self.init_score is not None:
                md.set_init_score(self.init_score)
            else:
                self.init_score = md.init_score
            if self.free_raw_data:
                self.data = None
            return self
        if isinstance(data, str) and cfg.two_round:
            # memory-bounded two-pass ingestion (dataset_loader.cpp
            # two_round branch): the raw float matrix never
            # materializes, so categorical indices resolve against the
            # header names only
            from .data.dataset import load_forced_bins
            from .data.file_loader import TwoRoundLoader
            names = TwoRoundLoader(data, cfg).resolve_feature_names()
            if feature_name == "auto":
                feature_name = None
            ref_inner = self.reference._inner \
                if self.reference is not None else None
            cat_idx = _resolve_categorical(
                categorical, names or feature_name, None)
            self._inner = _InnerDataset.from_file_two_round(
                data, cfg, label=self.label, weight=self.weight,
                group=self.group, init_score=self.init_score,
                feature_names=feature_name,
                categorical_features=cat_idx, reference=ref_inner,
                forced_bins={} if ref_inner is not None
                else load_forced_bins(cfg.forcedbins_filename))
            # backfill from the file/sidecars like the one-round str
            # branch, so get_label()/get_init_score() etc. see them
            md = self._inner.metadata
            if self.label is None:
                self.label = md.label
            if self.weight is None:
                self.weight = md.weights
            if self.group is None and md.query_boundaries is not None:
                self.group = np.diff(md.query_boundaries)
            if self.init_score is None:
                self.init_score = md.init_score
            if self.free_raw_data:
                self.data = None
            return self
        if isinstance(data, str):
            from .data.file_loader import load_file
            data, label, weight, group, init, fn = load_file(data, cfg)
            if self.label is None:
                self.label = label
            if self.weight is None:
                self.weight = weight
            if self.group is None:
                self.group = group
            if self.init_score is None:
                self.init_score = init
            if feature_name == "auto" and fn:
                feature_name = fn
            cat_idx = _resolve_categorical(
                categorical, feature_name,
                data.shape[1])
        elif _is_pandas_df(data):
            data, feature_name, cat_idx, self.pandas_categorical = \
                _data_from_pandas(data, feature_name, categorical)
        elif _is_sparse(data):
            # stays sparse end to end (Dataset.from_scipy): the raw
            # matrix is never densified (reference CSR/CSC push path,
            # c_api.cpp LGBM_DatasetCreateFromCSR/CSC)
            if feature_name == "auto":
                feature_name = None
            cat_idx = _resolve_categorical(
                categorical, feature_name, data.shape[1])
        else:
            data = _to_matrix(data)
            if feature_name == "auto":
                feature_name = None
            cat_idx = _resolve_categorical(
                categorical, feature_name, data.shape[1])

        ref_inner = self.reference._inner if self.reference is not None \
            else None
        ctor = _InnerDataset.from_scipy if _is_sparse(data) \
            else _InnerDataset.from_numpy
        from .data.dataset import load_forced_bins
        # reference-bound datasets copy the reference's mappers;
        # forced bins only matter when bins are found here
        forced = {} if ref_inner is not None \
            else load_forced_bins(cfg.forcedbins_filename)
        self._inner = ctor(
            data, cfg, label=self.label, weight=self.weight,
            group=self.group, init_score=self.init_score,
            feature_names=feature_name if feature_name != "auto"
            else None,
            categorical_features=cat_idx, reference=ref_inner,
            forced_bins=forced)
        tel.count("data.rows_binned", self._inner.num_data)
        tel.count("data.cells_binned",
                  self._inner.num_data * self._inner.num_features)
        if self.free_raw_data:
            self.data = None
        return self

    def _merged_params(self) -> Dict[str, Any]:
        if self.reference is not None:
            merged = dict(self.reference.params)
            merged.update(self.params)
            return merged
        return dict(self.params)

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        """basic.py:996-1022."""
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       feature_name=self.feature_name,
                       categorical_feature=self.categorical_feature,
                       params=params or self.params)

    def subset(self, used_indices, params=None) -> "Dataset":
        """basic.py:1322-1341."""
        out = Dataset(None, reference=self,
                      feature_name=self.feature_name,
                      categorical_feature=self.categorical_feature,
                      params=params or self.params)
        out.used_indices = np.sort(np.asarray(used_indices, np.int64))
        return out

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()._inner.save_binary(filename)
        return self

    # ------------------------------------------------------------------
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._inner is not None and label is not None:
            self._inner.metadata.set_label(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._inner is not None:
            self._inner.metadata.set_weights(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._inner is not None:
            self._inner.metadata.set_query(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._inner is not None:
            self._inner.metadata.set_init_score(init_score)
        return self

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Append the other dataset's features to this one in place
        (reference basic.py Dataset.add_features_from ->
        Dataset::AddFeaturesFrom). Both must be constructed and hold
        the same rows; this dataset keeps its label/weight/group."""
        self.construct()
        other.construct()
        self._inner.add_features_from(other._inner)
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        self.reference = reference
        return self

    def get_label(self):
        if self._inner is not None and self._inner.metadata.label \
                is not None:
            return np.asarray(self._inner.metadata.label)
        return self.label

    def get_weight(self):
        if self._inner is not None and self._inner.metadata.weights \
                is not None:
            return np.asarray(self._inner.metadata.weights)
        return self.weight

    def get_group(self):
        if self._inner is not None \
                and self._inner.metadata.query_boundaries is not None:
            return np.diff(self._inner.metadata.query_boundaries)
        return self.group

    def get_init_score(self):
        return self.init_score

    def get_data(self):
        return self.data

    def num_data(self) -> int:
        return self.construct()._inner.num_data

    def num_feature(self) -> int:
        return self.construct()._inner.num_total_features

    def get_feature_name(self) -> List[str]:
        return list(self.construct()._inner.feature_names)

    def get_ref_chain(self, ref_limit: int = 100):
        chain, head = set(), self
        while head is not None and len(chain) < ref_limit:
            chain.add(head)
            head = head.reference
        return chain


class Booster:
    """Booster (reference basic.py:1704-2951): training, evaluation,
    prediction, model IO."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._train_data_name = "training"
        self._gbdt = None
        self._loaded = None
        self.train_set = None
        self.valid_sets: List[Dataset] = []
        self.name_valid_sets: List[str] = []
        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance, "
                                f"met {type(train_set).__name__}")
            train_set.params = {**self.params, **train_set.params} \
                if train_set.params else dict(self.params)
            train_set.construct()
            self.train_set = train_set
            self.config = Config.from_params(self.params)
            from .models.variants import create_boosting
            self._gbdt = create_boosting(self.config, train_set._inner)
            self.pandas_categorical = train_set.pandas_categorical
        elif model_file is not None:
            from .io.model_text import load_model_from_string
            with open(model_file) as f:
                text = f.read()
            self._loaded = load_model_from_string(text)
            self.pandas_categorical = _parse_pandas_categorical(text)
        elif model_str is not None:
            from .io.model_text import load_model_from_string
            self._loaded = load_model_from_string(model_str)
            self.pandas_categorical = _parse_pandas_categorical(model_str)
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file or model string to create Booster "
                            "instance")

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if self._gbdt is None:
            raise LightGBMError("Booster was loaded from a model file; "
                                "cannot add validation data")
        if data.reference is None:
            data.set_reference(self.train_set)
        elif data.reference is not self.train_set \
                and not (data.get_ref_chain()
                         & self.train_set.get_ref_chain()):
            # no shared ancestor -> bins would not align with training
            data.set_reference(self.train_set)
        data.construct()
        self.valid_sets.append(data)
        self.name_valid_sets.append(name)
        self._gbdt.add_valid(data._inner, name)
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """basic.py Booster.reset_parameter: learning-rate etc. mid
        training (used by reset_parameter callback)."""
        self.params.update(params)
        if self._gbdt is not None:
            if "learning_rate" in params:
                self._gbdt.shrinkage_rate = float(params["learning_rate"])
            self._gbdt.config = Config.from_params(self.params)
        return self

    def reset_training_data(self, train_set: "Dataset") -> "Booster":
        """GBDT::ResetTrainingData analog (c_api.cpp
        LGBM_BoosterResetTrainingData, gbdt.cpp:244-262): swap the
        training dataset under the existing model. The trained trees
        are kept and their raw contribution seeds the new score cache
        (the init_from_models continued-training path), so the next
        ``update()`` boosts on the correct residuals of the NEW data.

        Must come before ``add_valid``: validation bins reference the
        training dataset's mappers, and rebasing them under a
        different bin layout would mis-bin every valid row."""
        if self._gbdt is None:
            raise LightGBMError("Booster was loaded from a model "
                                "file; cannot reset training data")
        if self.valid_sets:
            raise LightGBMError(
                "reset_training_data must be called before adding "
                "validation data (valid bins reference the old "
                "training mappers)")
        if not isinstance(train_set, Dataset):
            raise TypeError("Training data should be Dataset "
                            f"instance, met {type(train_set).__name__}")
        train_set.params = {**self.params, **train_set.params} \
            if train_set.params else dict(self.params)
        train_set.construct()
        old = self._gbdt
        if train_set._inner.num_features \
                != self.train_set._inner.num_features:
            raise LightGBMError(
                "reset_training_data: new dataset has "
                f"{train_set._inner.num_features} features, model "
                f"expects {self.train_set._inner.num_features}")
        from .models.variants import create_boosting
        gbdt = create_boosting(self.config, train_set._inner)
        models = list(old.models)
        if models:
            X = train_set.data
            if X is None:
                raise LightGBMError(
                    "reset_training_data needs the raw feature "
                    "matrix to seed scores; construct the Dataset "
                    "with free_raw_data=False and not via subset()")
            if _is_pandas_df(X):
                X = _apply_pandas_categorical(X,
                                              train_set.pandas_categorical)
            else:
                X = _to_matrix(X)
            X = np.asarray(X, np.float64)
            k = gbdt.num_tree_per_iteration
            raw = np.zeros((X.shape[0], k))
            for i, t in enumerate(models):
                raw[:, i % k] += t.predict(X)
            gbdt.init_from_models(models, raw, [])
        self._gbdt = gbdt
        self.train_set = train_set
        self.pandas_categorical = train_set.pandas_categorical
        return self

    # ------------------------------------------------------------------
    def update(self, train_set: Optional[Dataset] = None, fobj=None) \
            -> bool:
        """One boosting iteration; returns True if no further splits are
        possible (basic.py:2080-2130 -> LGBM_BoosterUpdateOneIter)."""
        if self._gbdt is None:
            raise LightGBMError("Cannot update a loaded-model Booster")
        if train_set is not None and train_set is not self.train_set:
            raise LightGBMError("change of train set is not supported; "
                                "create a new Booster")
        if fobj is None:
            return self._gbdt.train_one_iter()
        grad, hess = fobj(self.__inner_predict_train(), self.train_set)
        return self._gbdt.train_one_iter(np.asarray(grad, np.float32),
                                         np.asarray(hess, np.float32))

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def _src(self):
        """The backing model: trained GBDT if present, else the parsed
        LoadedBooster. Every model-IO/inspection method dispatches
        through here so loaded models are first-class."""
        src = self._gbdt if self._gbdt is not None else self._loaded
        if src is None:
            raise LightGBMError("Booster has neither a trained nor a "
                                "loaded model")
        return src

    def current_iteration(self) -> int:
        return self._src().num_iterations_trained

    def num_trees(self) -> int:
        return len(self._src().models)

    def num_model_per_iteration(self) -> int:
        return self._src().num_tree_per_iteration

    def __inner_predict_train(self) -> np.ndarray:
        import jax
        sc = np.asarray(jax.device_get(self._gbdt.train_score),
                        np.float64)
        return sc[:, 0] if sc.shape[1] == 1 else sc.T.reshape(-1)

    # ------------------------------------------------------------------
    def eval(self, data: Dataset, name: str, feval=None) -> List:
        """Evaluate on a dataset (must be train or an added valid)."""
        if data is self.train_set:
            return self.eval_train(feval)
        if data in self.valid_sets:
            i = self.valid_sets.index(data)
            return self._eval_one(self._gbdt.valid_metrics[i],
                                  self._gbdt.valid_scores[i],
                                  self.name_valid_sets[i], feval, data)
        raise LightGBMError("Data should be train set or a set added by "
                            "add_valid")

    def eval_train(self, feval=None) -> List:
        from .metric import create_metrics
        g = self._gbdt
        metrics = g.training_metrics
        if not metrics:
            metrics = create_metrics(g.config.resolved_metrics(), g.config)
            for m in metrics:
                m.init(g.train_data.metadata, g.num_data)
            g.training_metrics = metrics
        return self._eval_one(metrics, g.train_score,
                              self._train_data_name, feval,
                              self.train_set)

    def eval_valid(self, feval=None) -> List:
        out = []
        for i, name in enumerate(self.name_valid_sets):
            out += self._eval_one(self._gbdt.valid_metrics[i],
                                  self._gbdt.valid_scores[i], name, feval,
                                  self.valid_sets[i])
        return out

    def eval_all(self, feval=None, include_train: bool = True) -> List:
        """Training + every valid set in ONE batched device->host fetch
        per call (the per-iteration engine loop's eval boundary). Order
        matches eval_train() + eval_valid()."""
        jobs = []
        if include_train:
            from .metric import create_metrics
            g = self._gbdt
            if not g.training_metrics:
                g.training_metrics = create_metrics(
                    g.config.resolved_metrics(), g.config)
                for m in g.training_metrics:
                    m.init(g.train_data.metadata, g.num_data)
            jobs.append((g.training_metrics, g.train_score,
                         self._train_data_name, self.train_set))
        for i, name in enumerate(self.name_valid_sets):
            jobs.append((self._gbdt.valid_metrics[i],
                         self._gbdt.valid_scores[i], name,
                         self.valid_sets[i]))
        return self._eval_sets(jobs, feval)

    def _eval_one(self, metrics, score, name, feval, dataset) -> List:
        return self._eval_sets([(metrics, score, name, dataset)], feval)

    def _eval_sets(self, jobs, feval) -> List:
        """Shared eval driver: one batched fetch for all datasets on
        the device-eval path (LGBM_TPU_DEVICE_EVAL=0 restores the
        legacy per-metric fetches)."""
        from .metric.metrics import batched_eval, device_eval_enabled
        from .observability.telemetry import get_telemetry
        g = self._gbdt
        tel = get_telemetry()
        scs = [score if g.num_tree_per_iteration > 1 else score[:, 0]
               for _metrics, score, _name, _ds in jobs]
        if device_eval_enabled():
            tel.count_iter("host.syncs")
            tel.count_iter("host.dispatches", len(jobs))
            per_job = batched_eval(
                [(metrics, sc, name)
                 for (metrics, _s, name, _ds), sc in zip(jobs, scs)],
                g.objective)
        else:
            per_job = []
            import jax
            for (metrics, _s, name, _ds), sc in zip(jobs, scs):
                sc_h = jax.device_get(sc)
                # legacy accounting: score fetch + per-metric convert
                # round trip (upload + convert dispatch + result fetch)
                tel.count_iter("host.syncs", 1 + len(metrics))
                tel.count_iter("host.dispatches", 2 * len(metrics))
                rows = []
                for m in metrics:
                    vals = m.eval(sc_h, g.objective)
                    for mname, v in zip(m.names, vals):
                        rows.append((name, mname, v,
                                     m.factor_to_bigger_better > 0))
                per_job.append(rows)
        out = []
        for (metrics, _s, name, dataset), sc, rows in zip(jobs, scs,
                                                          per_job):
            out.extend(rows)
            if feval is not None:
                flat = np.asarray(sc, np.float64)
                if flat.ndim == 2:
                    flat = flat.T.reshape(-1)
                res = feval(flat, dataset)
                if res is not None:
                    if isinstance(res, tuple):
                        res = [res]
                    for mname, v, bigger in res:
                        out.append((name, mname, v, bigger))
        return out

    # ------------------------------------------------------------------
    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        """basic.py:2580-2680 -> Predictor."""
        if _is_pandas_df(data):
            data = _apply_pandas_categorical(data,
                                             self.pandas_categorical)
        elif _is_sparse(data):
            # Bosch/Criteo-scale CSR must not densify whole
            # (predictor.hpp:39-131 predicts sparse rows directly):
            # stream fixed-size row chunks through the dense path —
            # fixed so the device scan compiles ONCE; the ragged tail
            # is zero-padded and sliced off
            import os as _os
            chunk = int(_os.environ.get(
                "LGBM_TPU_SPARSE_PREDICT_CHUNK_ROWS", 65536))
            n = data.shape[0]
            if n > chunk:
                csr = data.tocsr()
                parts = []
                for lo in range(0, n, chunk):
                    sub = np.asarray(
                        csr[lo:lo + chunk].todense(), np.float64)
                    m = sub.shape[0]
                    if m < chunk:
                        sub = np.concatenate(
                            [sub, np.zeros((chunk - m, sub.shape[1]))])
                    parts.append(self.predict(
                        sub, num_iteration=num_iteration,
                        raw_score=raw_score, pred_leaf=pred_leaf,
                        pred_contrib=pred_contrib, **kwargs)[:m])
                return np.concatenate(parts)
            data = _to_matrix(data)
        else:
            data = _to_matrix(data)
        data = np.asarray(data, np.float64)
        if num_iteration is None:
            num_iteration = self.best_iteration \
                if self.best_iteration > 0 else -1
        es_kw = {k: v for k, v in kwargs.items()
                 if k in ("pred_early_stop", "pred_early_stop_freq",
                          "pred_early_stop_margin")}
        from .predictor import predict as _predict
        return _predict(self._src(), data, num_iteration=num_iteration,
                        raw_score=raw_score, pred_leaf=pred_leaf,
                        pred_contrib=pred_contrib, **es_kw)

    # ------------------------------------------------------------------
    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Refit the existing model on new data (reference
        basic.py:2614-2659): keep tree structures, refit leaf values by
        sequential replay with
        ``leaf = decay_rate*old + (1-decay_rate)*new``."""
        import copy
        src = self._src()
        obj = getattr(src, "objective", None)
        obj_str = getattr(src, "objective_str", "")
        if obj is None and not obj_str:
            raise LightGBMError(
                "Cannot refit due to null objective function.")
        # all trees, even past best_iteration (reference passes -1)
        kwargs.setdefault("num_iteration", -1)
        leaf_preds = self.predict(data, pred_leaf=True, **kwargs)
        new_params = dict(self.params)
        new_params["refit_decay_rate"] = decay_rate
        if "objective" not in new_params and obj_str:
            # loaded model: recover the objective from its model line
            # ("binary sigmoid:1", "multiclass num_class:3", ...)
            toks = obj_str.split()
            new_params["objective"] = toks[0]
            for tok in toks[1:]:
                key, _, val = tok.partition(":")
                if key and val:
                    new_params.setdefault(key, val)
        is_linear = any(getattr(t, "is_linear", False)
                        for t in src.models)
        raw = None
        if is_linear:
            # the per-leaf ridge coefficients are RE-FIT from the new
            # labels (never silently dropped): the replay needs the
            # ORIGINAL-index raw matrix, and the new Dataset keeps raw
            # values like any linear_tree training set
            new_params.setdefault("linear_tree", True)
            raw = data
            if _is_pandas_df(raw):
                raw = _apply_pandas_categorical(
                    raw, self.pandas_categorical)
            else:
                raw = _to_matrix(raw)
            raw = np.asarray(raw, np.float64)
        train_set = Dataset(data, label=label)
        new_booster = Booster(new_params, train_set)
        getattr(src, "finalize_trees", lambda: None)()
        new_booster._gbdt.models = [copy.deepcopy(t) for t in src.models]
        new_booster._gbdt.iter = len(src.models) \
            // src.num_tree_per_iteration
        new_booster._gbdt.refit(leaf_preds, raw=raw)
        return new_booster

    # ------------------------------------------------------------------
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        import json
        from .io.model_text import save_model_to_string
        ni = num_iteration if num_iteration is not None else \
            (self.best_iteration if self.best_iteration > 0 else -1)
        text = save_model_to_string(self._src(), start_iteration, ni)
        # pandas-categorical round trip (reference basic.py appends the
        # category order as a trailing JSON line)
        return text + "\npandas_categorical:" \
            + json.dumps(self.pandas_categorical, default=str) + "\n"

    def save_model(self, filename: str,
                   num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration, start_iteration))
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict:
        import json
        from .io.model_text import dump_model_json
        ni = num_iteration if num_iteration is not None else \
            (self.best_iteration if self.best_iteration > 0 else -1)
        return json.loads(dump_model_json(self._src(), start_iteration,
                                          ni))

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        from .io.model_text import feature_importance
        imp = feature_importance(
            self._src(), importance_type,
            iteration if iteration is not None else 0)
        return imp.astype(np.int64) if importance_type == "split" else imp

    def feature_name(self) -> List[str]:
        if self._gbdt is not None:
            return list(self.train_set.get_feature_name())
        return list(self._loaded.feature_names)

    def num_feature(self) -> int:
        if self._gbdt is not None:
            return self.train_set.num_feature()
        return self._loaded.max_feature_idx + 1


def _parse_pandas_categorical(text: str) -> List:
    """Read back the trailing pandas_categorical JSON line
    (reference basic.py:331-360)."""
    import json
    tail = text[-min(len(text), 1 << 16):]
    marker = "pandas_categorical:"
    pos = tail.rfind(marker)
    if pos < 0:
        return []
    line = tail[pos + len(marker):].splitlines()[0].strip()
    try:
        return json.loads(line) or []
    except json.JSONDecodeError:
        return []


def _apply_pandas_categorical(df, pandas_categorical):
    """Map categorical columns through the training-time category order
    (basic.py pandas-categorical round trip)."""
    import pandas as pd
    df = df.copy()
    cat_cols = [c for c in df.columns
                if isinstance(df[c].dtype, pd.CategoricalDtype)]
    for i, col in enumerate(cat_cols):
        if i < len(pandas_categorical):
            df[col] = df[col].cat.set_categories(pandas_categorical[i])
        codes = df[col].cat.codes.astype(np.float64)
        df[col] = codes.where(codes >= 0, np.nan)
    return df.astype(np.float64).to_numpy()
