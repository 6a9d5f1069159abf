"""BoosterBatch: B independent boosters trained as ONE program.

The batch shares a single constructed Dataset (one BinMapper pass,
one device binned matrix) and one SerialTreeLearner; per-model state
is stacked along a leading model axis:

* ``score``      [B, N] f32 — every model's train score column
* ``attrs``      per-model objective operands (label / weights / ...)
* ``masks``      [B, N] f32 row-inclusion weights (cv folds, tenant
                 row partitions) — zero rows contribute zeros to the
                 scatter-add histograms, exactly like an out-of-bag row
* ``hyp``        :class:`~.program.HyperBatch` of traced axes

Models whose STATIC shape or code differs (num_leaves, max_bin,
objective class, bagging_freq, ...) cannot share a trace; callers
split them into buckets with :func:`bucket_models` first — one
compiled program per bucket, vmapped over the models inside it.

The driver loop mirrors ``GBDT._train_impl`` exactly: a sync
iteration 0 (boost_from_average, host f64 shrink, constant-tree
fallback), then async iterations whose stop flags flush every
``_ASYNC_FLUSH`` rounds, with per-model truncation at the first
no-split iteration. Each finished model materializes through the
standard ``save_model_to_string`` writer, so the serving contract —
model text, AOT artifacts, C API — is untouched.
"""

from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..models.gbdt import _constant_tree, kEpsilon
from ..models.tree import Tree, TreeArrays
from ..objective.base import create_objective
from ..observability.telemetry import get_telemetry
from ..utils.log import log_info
from .program import HyperBatch, build_grow_program, mb_score_add

#: hyperparameter axes vmapped along the model axis; every other param
#: is static (shape- or code-affecting) and buckets instead
VMAPPED_PARAMS = (
    "learning_rate", "lambda_l1", "lambda_l2", "max_delta_step",
    "min_data_in_leaf", "min_sum_hessian_in_leaf", "min_gain_to_split",
    "bagging_fraction", "bagging_seed")

#: objectives whose gradients are elementwise in their operands
#: (``grad_operands``), so a model's slice of them is its own labels
ELIGIBLE_OBJECTIVES = (
    "regression", "huber", "fair", "poisson", "gamma", "tweedie",
    "binary", "cross_entropy", "cross_entropy_lambda")

_ASYNC_FLUSH = 16  # == GBDT._ASYNC_FLUSH stop-flag batching


class MultiboostError(RuntimeError):
    """Batch construction failed; callers fall back to the loop."""


@dataclass
class ModelSpec:
    """One model of a batch: its params and (optionally) the sorted
    row subset it trains on (cv fold, tenant partition)."""
    params: Dict[str, Any]
    row_index: Optional[np.ndarray] = None
    name: str = ""

    def resolve(self) -> Config:
        return Config.from_params(self.params)


def multiboost_mode(cfg: Config) -> str:
    mode = str(getattr(cfg, "multiboost", "auto")).lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"multiboost must be auto|on|off, got {mode!r}")
    return mode


def multiboost_ineligible_reason(cfg: Config,
                                 inner=None) -> Optional[str]:
    """Why this config cannot ride the batched program (None = can).

    The list is exactly the set of features whose serial-path numerics
    are NOT reproduced by the vmapped body: host-RNG sampling, label-
    stat-dependent class weights, leaf refits, CEGB state, custom
    learners. Ineligible models train through the per-model loop.
    """
    import os
    if str(getattr(cfg, "boosting", "gbdt")) != "gbdt":
        return f"boosting={cfg.boosting}"
    if cfg.tree_learner != "serial":
        return f"tree_learner={cfg.tree_learner}"
    if int(cfg.num_class) != 1:
        return f"num_class={cfg.num_class}"
    if cfg.objective not in ELIGIBLE_OBJECTIVES:
        return f"objective={cfg.objective}"
    if cfg.objective == "binary" and cfg.is_unbalance:
        return "is_unbalance (label-stat class weights)"
    if cfg.linear_tree:
        return "linear_tree"
    if float(cfg.cegb_tradeoff) > 0.0 and (
            float(cfg.cegb_penalty_split) > 0.0
            or any(float(c) > 0.0
                   for c in cfg.cegb_penalty_feature_lazy)
            or any(float(c) > 0.0
                   for c in cfg.cegb_penalty_feature_coupled)):
        return "cegb"
    if cfg.forcedsplits_filename:
        return "forced splits"
    if cfg.extra_trees:
        return "extra_trees (per-tree host RNG)"
    if cfg.feature_fraction < 1.0 or cfg.feature_fraction_bynode < 1.0:
        return "feature sampling (per-tree host RNG)"
    if cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0:
        return "balanced bagging"
    if cfg.guard_policy != "off":
        return f"guard_policy={cfg.guard_policy}"
    if cfg.faults:
        return "fault injection"
    if int(cfg.checkpoint_freq) > 0:
        return "mid-train checkpointing"
    if int(cfg.num_machines) > 1 or cfg.is_parallel:
        return "parallel learner"
    if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0 \
            and os.environ.get("LGBM_TPU_HOST_BAG", "") == "1":
        return "host-RNG bagging (LGBM_TPU_HOST_BAG=1)"
    if inner is not None:
        md = inner.metadata
        if getattr(md, "init_score", None) is not None:
            return "init_score metadata"
        if getattr(md, "group", None) is not None:
            return "group metadata"
        if inner.num_features == 0:
            return "no usable features"
    return None


def bucket_key(cfg: Config) -> Tuple:
    """Models sharing a key share ONE compiled program; the key is
    every canonical param that is not a vmapped axis."""
    items = []
    for k, v in sorted(cfg.to_params().items()):
        if k in VMAPPED_PARAMS:
            continue
        if isinstance(v, list):
            v = tuple(v)
        items.append((k, v))
    return tuple(items)


def bucket_models(specs: Sequence[ModelSpec],
                  configs: Optional[Sequence[Config]] = None,
                  max_batch: int = 0
                  ) -> List[List[Tuple[int, ModelSpec, Config]]]:
    """Group specs into static-shape buckets (stable order), chunked
    at ``max_batch`` models (0 = unbounded)."""
    cfgs = list(configs) if configs is not None \
        else [s.resolve() for s in specs]
    buckets: Dict[Tuple, List[Tuple[int, ModelSpec, Config]]] = {}
    order: List[Tuple] = []
    for i, (spec, cfg) in enumerate(zip(specs, cfgs)):
        key = bucket_key(cfg)
        if key not in buckets:
            buckets[key] = []
            order.append(key)
        buckets[key].append((i, spec, cfg))
    out: List[List[Tuple[int, ModelSpec, Config]]] = []
    for key in order:
        group = buckets[key]
        if max_batch and max_batch > 0:
            for j in range(0, len(group), max_batch):
                out.append(group[j:j + max_batch])
        else:
            out.append(group)
    return out


def _meta_view(md, idx: Optional[np.ndarray]):
    """Metadata restricted to a sorted row subset (host views) — what
    ``Dataset.subset`` would hand the fold's objective/metrics."""
    if idx is None:
        return md
    lbl = None if md.label is None else np.asarray(md.label)[idx]
    w = None if md.weights is None else np.asarray(md.weights)[idx]
    return SimpleNamespace(label=lbl, weights=w, init_score=None,
                           group=None)


def _boost_from_average(cfg: Config, objective, num_features: int
                        ) -> float:
    """gbdt.cpp:312-335 semantics for a fresh booster with no init
    score: the objective's boost_from_score when enabled and above
    kEpsilon, else 0."""
    if cfg.boost_from_average or num_features == 0:
        s = float(objective.boost_from_score(0))
        if abs(s) > kEpsilon:
            return s
    return 0.0


def _tree_slice(host: TreeArrays, b: int) -> TreeArrays:
    return TreeArrays(*(np.asarray(a)[b] for a in host))


class _ModelShim:
    """Duck-typed GBDT stand-in for ``save_model_to_string`` /
    ``feature_importance``: host trees + the model's own Config and
    objective over the shared dataset."""

    num_tree_per_iteration = 1
    num_class = 1
    average_output = False

    def __init__(self, models: List, config: Config, objective,
                 dataset):
        self.models = models
        self.config = config
        self.objective = objective
        self.learner = SimpleNamespace(dataset=dataset)

    def finalize_trees(self) -> None:
        pass


class BoosterBatch:
    """B boosters growing one tree each per compiled iteration.

    Drive with :meth:`train` (whole run, async flag flushing) or
    step-wise via :meth:`setup` / :meth:`step` / :meth:`finalize`
    (``engine.cv`` evaluates ``scores`` between steps). Models come
    out via :meth:`model_text` / :meth:`booster`, byte-identical to
    their unbatched ``engine.train`` twins.
    """

    def __init__(self, train_set, specs: Sequence[ModelSpec],
                 num_boost_round: int,
                 configs: Optional[Sequence[Config]] = None):
        if not specs:
            raise MultiboostError("empty batch")
        if int(num_boost_round) < 1:
            raise MultiboostError("num_boost_round must be >= 1")
        # Booster-style non-overriding merge so the bin layout sees the
        # bucket's dataset params (max_bin, ...) exactly like the twin
        p0 = dict(specs[0].params or {})
        train_set.params = {**p0, **train_set.params} \
            if train_set.params else p0
        train_set.construct()
        self.train_set = train_set
        self.inner = train_set._inner
        self.specs = list(specs)
        self.configs = list(configs) if configs is not None \
            else [s.resolve() for s in specs]
        self.num_boost_round = int(num_boost_round)
        self.B = len(self.specs)
        self.N = int(self.inner.num_data)
        self._built = False
        self._finalized = False

    # -- construction --------------------------------------------------
    def setup(self) -> "BoosterBatch":
        if self._built:
            return self
        from ..parallel.learners import create_tree_learner
        tel = get_telemetry()
        cfg0 = self.configs[0]
        for cfg in self.configs:
            reason = multiboost_ineligible_reason(cfg, self.inner)
            if reason:
                raise MultiboostError(reason)
        self.learner = create_tree_learner(
            cfg0.tree_learner, self.inner, cfg0, hist_method="auto")
        self.L = int(self.learner.num_leaves)
        md = self.inner.metadata
        nf = int(self.inner.num_features)

        self._lr = [float(c.learning_rate) for c in self.configs]
        self._obj_eval: List[Any] = []
        obj_grad: List[Any] = []
        self._init: List[float] = []
        masks = None
        for spec, cfg in zip(self.specs, self.configs):
            oe = create_objective(cfg)
            idx = spec.row_index
            if idx is not None:
                idx = np.sort(np.asarray(idx, np.int64))
                spec.row_index = idx
                oe.init(_meta_view(md, idx), int(len(idx)))
                og = create_objective(cfg)
                og.init(md, self.N)
                if masks is None:
                    masks = np.zeros((self.B, self.N), np.float32)
                masks[len(self._obj_eval), idx] = 1.0
            else:
                oe.init(md, self.N)
                og = oe
            if cfg.objective == "binary" and not og.need_train:
                raise MultiboostError("binary single-class rows")
            self._obj_eval.append(oe)
            obj_grad.append(og)
            self._init.append(_boost_from_average(cfg, oe, nf))
        has_mask = masks is not None
        if has_mask:
            ones = np.asarray(
                [s.row_index is None for s in self.specs])
            masks[ones] = 1.0

        ops = [og.grad_operands()[0] for og in obj_grad]
        if len({jax.tree.structure(o) for o in ops}) > 1:
            raise MultiboostError(
                "models disagree on objective operand presence")
        self._attrs = jax.tree.map(lambda *xs: jnp.stack(xs), *ops)

        use_bagging = cfg0.bagging_freq > 0 and any(
            c.bagging_fraction < 1.0 for c in self.configs)
        if use_bagging and has_mask:
            raise MultiboostError("bagging combined with row masks")
        self._hyp = HyperBatch(
            learning_rate=jnp.asarray(
                [c.learning_rate for c in self.configs], jnp.float32),
            lambda_l1=jnp.asarray(
                [c.lambda_l1 for c in self.configs], jnp.float32),
            lambda_l2=jnp.asarray(
                [c.lambda_l2 for c in self.configs], jnp.float32),
            max_delta_step=jnp.asarray(
                [c.max_delta_step for c in self.configs], jnp.float32),
            min_data_in_leaf=jnp.asarray(
                [c.min_data_in_leaf for c in self.configs],
                jnp.float32),
            min_sum_hessian_in_leaf=jnp.asarray(
                [c.min_sum_hessian_in_leaf for c in self.configs],
                jnp.float32),
            min_gain_to_split=jnp.asarray(
                [c.min_gain_to_split for c in self.configs],
                jnp.float32),
            bagging_fraction=jnp.asarray(
                [c.bagging_fraction for c in self.configs],
                jnp.float32),
            init_score=jnp.asarray(self._init, jnp.float32),
            bag_key=jnp.stack([
                jax.random.PRNGKey(int(c.bagging_seed))
                for c in self.configs]))
        self._masks = None if masks is None else jnp.asarray(masks)
        # SplitParams numerics enter the grow graph traced ONLY when
        # they vary across the bucket; uniform values stay static so
        # XLA folds them exactly like the twin (split_gain ulps)
        numeric = ("lambda_l1", "lambda_l2", "max_delta_step",
                   "min_data_in_leaf", "min_sum_hessian_in_leaf",
                   "min_gain_to_split")
        traced = tuple(
            f for f in numeric
            if len({float(getattr(c, f)) for c in self.configs}) > 1)
        self._traced_fields = traced
        self._program = build_grow_program(
            self.learner, obj_grad[0], use_bagging=use_bagging,
            bagging_freq=int(cfg0.bagging_freq), has_mask=has_mask,
            traced_fields=traced)

        self._score = jnp.zeros((self.B, self.N), jnp.float32)
        self._models: List[List[Any]] = [[] for _ in range(self.B)]
        self._stop: List[Optional[int]] = [None] * self.B
        self._it = 0
        self._pending_ok: List[Any] = []
        self._tree_stack: List[TreeArrays] = []
        self._flushed = 0   # async iterations already flag-checked
        self._built = True
        tel.count("multiboost.batches")
        tel.count("multiboost.models", self.B)
        log_info(f"multiboost: batch of {self.B} models x "
                 f"{self.num_boost_round} rounds on {self.N} rows "
                 f"(bagging={'on' if use_bagging else 'off'}, "
                 f"masks={'on' if has_mask else 'off'})")
        return self

    # -- one iteration for ALL models ----------------------------------
    def step(self) -> None:
        self.setup()
        tel = get_telemetry()
        it = self._it
        if it == 0:
            tel.count_iter("host.dispatches")
            score, trees, leaf_id, ok = self._program(
                self._score, jnp.int32(0), self._attrs, self._masks,
                self._hyp, sync0=True)
            tel.count_iter("host.syncs")
            host, ok_h = jax.device_get((trees, ok))
            leaf_pad = np.zeros((self.B, self.L), np.float32)
            for b in range(self.B):
                if bool(ok_h[b]):
                    t = Tree(_tree_slice(host, b), dataset=self.inner)
                    t.shrink(self._lr[b])
                    # score moves by the f64-shrunk, rounded-back f32
                    # leaf values BEFORE the bias lands on the tree —
                    # the exact train_one_iter ordering
                    nl = int(t.num_leaves)
                    leaf_pad[b, :nl] = np.asarray(t.leaf_value,
                                                  np.float32)
                    if abs(self._init[b]) > kEpsilon:
                        t.add_bias(self._init[b])
                    self._models[b].append(t)
                else:
                    # constant-tree fallback; this model is done
                    self._models[b].append(
                        _constant_tree(self._init[b]))
                    self._stop[b] = 1
                    leaf_pad[b, :] = np.float32(self._init[b])
            tel.count_iter("host.dispatches")
            self._score = mb_score_add(score, jnp.asarray(leaf_pad),
                                       leaf_id)
            self._it = 1
            return
        tel.count_iter("host.dispatches")
        self._score, trees, ok = self._program(
            self._score, jnp.int32(it), self._attrs, self._masks,
            self._hyp, sync0=False)
        self._tree_stack.append(trees)
        self._pending_ok.append(ok)
        self._it = it + 1

    @property
    def scores(self):
        """Current [B, N] device train score (cv evaluates from it)."""
        return self._score

    def poll_stops(self) -> bool:
        """Flush pending stop flags (ONE device sync); True when every
        model has hit its first no-split iteration."""
        if self._pending_ok:
            get_telemetry().count_iter("host.syncs")
            flags = np.asarray(
                jax.device_get(jnp.stack(self._pending_ok)))
            for b in range(self.B):
                if self._stop[b] is None:
                    bad = np.nonzero(~flags[:, b])[0]
                    if len(bad):
                        # kept trees: iteration 0 + async iterations
                        # strictly before the first no-split one
                        self._stop[b] = 1 + self._flushed + int(bad[0])
            self._flushed += flags.shape[0]
            self._pending_ok = []
        return all(s is not None for s in self._stop)

    # -- whole-run driver ----------------------------------------------
    def train(self) -> "BoosterBatch":
        self.setup()
        while self._it < self.num_boost_round:
            self.step()
            if self._it == 1:
                if all(s is not None for s in self._stop):
                    break
                continue
            if len(self._pending_ok) >= _ASYNC_FLUSH \
                    or self._it == self.num_boost_round:
                if self.poll_stops():
                    break
        self.finalize()
        return self

    def finalize(self) -> None:
        """Materialize every kept tree with ONE batched device->host
        transfer (the finalize_trees analog), truncating each model at
        its first no-split iteration."""
        if self._finalized:
            return
        self.setup()
        self.poll_stops()
        if self._tree_stack:
            get_telemetry().count_iter("host.syncs")
            hosts = jax.device_get(self._tree_stack)
            for i, host in enumerate(hosts):     # async iteration 1+i
                for b in range(self.B):
                    kept = self._stop[b] if self._stop[b] is not None \
                        else self._it
                    if 1 + i < kept:
                        t = Tree(_tree_slice(host, b),
                                 dataset=self.inner)
                        t.shrink(self._lr[b])
                        self._models[b].append(t)
            self._tree_stack = []
        for b in range(self.B):
            kept = self._stop[b] if self._stop[b] is not None \
                else self._it
            del self._models[b][kept:]
        self._finalized = True

    # -- results -------------------------------------------------------
    def models(self, b: int) -> List[Any]:
        self.finalize()
        return self._models[b]

    def model_text(self, b: int) -> str:
        """Full model text, byte-compatible with the twin Booster's
        ``model_to_string`` (trailing pandas_categorical included)."""
        import json
        from ..io.model_text import save_model_to_string
        self.finalize()
        shim = _ModelShim(self._models[b], self.configs[b],
                          self._obj_eval[b], self.inner)
        pc = getattr(self.train_set, "pandas_categorical", None) or []
        return save_model_to_string(shim) + "\npandas_categorical:" \
            + json.dumps(pc, default=str) + "\n"

    def booster(self, b: int):
        from ..basic import Booster
        bst = Booster(model_str=self.model_text(b))
        bst.best_iteration = -1
        return bst

    def describe(self) -> Dict[str, Any]:
        return {"models": self.B, "rounds": self.num_boost_round,
                "rows": self.N, "num_leaves": getattr(self, "L", None),
                "stopped": sum(s is not None for s in self._stop)
                if self._built else 0}


__all__ = [
    "BoosterBatch", "ModelSpec", "MultiboostError", "VMAPPED_PARAMS",
    "ELIGIBLE_OBJECTIVES", "bucket_key", "bucket_models",
    "multiboost_ineligible_reason", "multiboost_mode", "mb_score_add"]
