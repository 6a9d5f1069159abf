"""GBDT boosting driver.

Reference analog: ``GBDT`` (``src/boosting/gbdt.cpp:42-780``, ``gbdt.h``).
The host orchestrates iterations; each tree is one fused XLA program
(learner), gradients are one jitted function of the score, and scores
live on device between iterations. Host work per iteration is O(1) plus
optional metric evaluation.

Covered here: init wiring (gbdt.cpp:42-120), TrainOneIter with
boost-from-average / bagging / per-class trees / renewal / shrinkage /
score update / constant-tree fallback (gbdt.cpp:301-419), RollbackOneIter
(gbdt.cpp:421-437), eval + early stopping (gbdt.cpp:439-542), bagging
(gbdt.cpp:163-243). DART/GOSS/RF subclass this in ``variants.py``.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.dataset import Dataset
from ..metric import create_metrics
from ..objective import create_objective
from ..observability import scopes
from ..observability.telemetry import get_telemetry, memory_snapshot
from ..observability.tracing import (get_tracer, profile_boundary,
                                     profile_close)
from ..robustness.guards import NonFiniteGradientError
from ..utils.device import on_tpu
from ..utils.jit_registry import register_dynamic, register_jit
from ..utils.log import log_fatal, log_info, log_warning
from .tree import (DeferredStackTree, DeferredTree, Tree, TreeStack,
                   traverse_tree_arrays)

kEpsilon = 1e-15


# ----------------------------------------------------------------------
# Module-jitted score updaters: one device program per update instead of
# the eager gather + scatter-add pair (each eager jnp op is its own
# dispatch). The score
# buffer is donated — boosting only ever moves forward, so the previous
# iteration's buffer is dead the moment the update launches.
@register_jit("score_add_leaf", donate=(0,))
@functools.partial(jax.jit, static_argnames=("tid",),
                   donate_argnums=(0,))
def _score_add_leaf(score, leaf_vals, leaf_id, *, tid: int):
    return score.at[:, tid].add(leaf_vals[leaf_id])


@register_jit("score_add_col", donate=(0,))
@functools.partial(jax.jit, static_argnames=("tid",),
                   donate_argnums=(0,))
def _score_add_col(score, add, *, tid: int):
    return score.at[:, tid].add(add)


@register_jit("score_add_leaf_linear", donate=(0,))
@functools.partial(jax.jit, static_argnames=("tid",),
                   donate_argnums=(0,))
def _score_add_leaf_linear(score, leaf_vals, lin_const, lin_coeff,
                           lin_feat, leaf_id, raw, *, tid: int):
    """Linear-leaf train-score update: the leaf assignment is already
    known (no traversal) — gather each row's leaf model, evaluate
    ``const + w . x`` with the constant fallback for NaN rows, add to
    the donated score column. One program, like _score_add_leaf."""
    from .linear import linear_leaf_values
    return score.at[:, tid].add(linear_leaf_values(
        leaf_id, raw, leaf_vals, lin_const, lin_coeff, lin_feat))


@register_jit("refit_tree", donate=(0,))
@functools.partial(jax.jit,
                   static_argnames=("nl", "tid", "l1", "l2", "mds"),
                   donate_argnums=(0,))
def _refit_tree(score, lp, grad, hess, old_leaf, shrink, decay, *,
                nl: int, tid: int, l1: float, l2: float, mds: float):
    """One refit replay step on device: per-leaf grad/hess sums over
    the fixed leaf assignment ``lp``, the regularized leaf output, the
    decayed leaf values, and the score update — one program, score
    donated. Returns (score, raw refit output [nl]); the host combines
    the raw output with the f64 leaf values for model export."""
    from ..ops.split import leaf_output_no_constraint
    sum_g = jnp.zeros((nl,), jnp.float32).at[lp].add(grad)
    sum_h = jnp.zeros((nl,), jnp.float32).at[lp].add(hess) + kEpsilon
    out = leaf_output_no_constraint(sum_g, sum_h, l1, l2, mds)
    new_leaf = decay * old_leaf + (1.0 - decay) * out * shrink
    return score.at[:, tid].add(new_leaf[lp]), out


@register_jit("refit_tree_linear", donate=(0,))
@functools.partial(jax.jit,
                   static_argnames=("nl", "tid", "l1", "l2", "mds",
                                    "lam", "l2lin"),
                   donate_argnums=(0,))
def _refit_tree_linear(score, lp, grad, hess, raw, feats, old_leaf,
                       old_const, old_coeff, shrink, decay, *,
                       nl: int, tid: int, l1: float, l2: float,
                       mds: float, lam: float, l2lin: float):
    """Linear-leaf refit replay step: the constant refit output (the
    fallback), PLUS a fresh per-leaf ridge solve over the leaf's
    existing model features from the NEW labels' grad/hess — the
    models/linear.py normal equations with the refit leaf assignment
    ``lp`` standing in for the grow loop's leaf_id. The decayed leaf
    model blends old and new like the constant path
    (``decay*old + (1-decay)*new*shrink`` elementwise on const and
    coeffs); a leaf whose new solve is gated (too few rows, singular,
    exploding coefficients) decays toward the constant refit output
    instead — with decay=1.0 the model is unchanged exactly.

    Returns (score, (out, fit_const, fit_coeff, ok)); the host redoes
    the blend in f64 on the tree arrays for model export."""
    from ..ops.split import leaf_output_no_constraint
    from .linear import kCoeffBound, kLinEps, linear_leaf_values
    sum_g = jnp.zeros((nl,), jnp.float32).at[lp].add(grad)
    sum_h = jnp.zeros((nl,), jnp.float32).at[lp].add(hess) + kEpsilon
    out = leaf_output_no_constraint(sum_g, sum_h, l1, l2, mds)
    new_leaf = decay * old_leaf + (1.0 - decay) * out * shrink
    # ridge statistics (every row in-bag; NaN rows excluded like fit)
    n = raw.shape[0]
    c = feats.shape[1]
    rows = jnp.arange(n)
    ft = feats[lp]                                        # [N, C]
    m = ft >= 0
    x = raw[rows[:, None], jnp.clip(ft, 0, raw.shape[1] - 1)]
    bad = ~jnp.isfinite(x) & m
    row_ok = ~bad.any(axis=1)
    xz = jnp.where(m & ~bad, x, 0.0)
    w = hess * row_ok
    gw = grad * row_ok
    xb = jnp.concatenate([xz, jnp.ones((n, 1), xz.dtype)], axis=1)
    outer = xb[:, :, None] * xb[:, None, :] * w[:, None, None]
    a_mat = jax.ops.segment_sum(outer, lp, num_segments=nl)
    b_vec = jax.ops.segment_sum(xb * gw[:, None], lp, num_segments=nl)
    cnt = jax.ops.segment_sum(row_ok.astype(jnp.float32), lp,
                              num_segments=nl)
    active = feats >= 0                                    # [L, C]
    diag = jnp.concatenate(
        [jnp.where(active, jnp.float32(lam), jnp.float32(1.0)),
         jnp.full((nl, 1), jnp.float32(l2lin) + jnp.float32(kLinEps))],
        axis=1)
    a_mat = a_mat + jnp.eye(c + 1, dtype=a_mat.dtype) * diag[:, None, :]
    sol = -jnp.linalg.solve(a_mat, b_vec[..., None])[..., 0]
    ca = active.sum(axis=1).astype(jnp.float32)
    ok = (jnp.isfinite(sol).all(axis=1)
          & (jnp.abs(sol) < kCoeffBound).all(axis=1)
          & (cnt > ca) & (ca > 0))
    fit_coeff = jnp.where(ok[:, None], sol[:, :c], 0.0)
    fit_const = jnp.where(ok, sol[:, c], out)
    bc = decay * old_const + (1.0 - decay) * fit_const * shrink
    bw = decay * old_coeff + (1.0 - decay) * fit_coeff * shrink
    score = score.at[:, tid].add(linear_leaf_values(
        lp, raw, new_leaf, bc, bw, feats))
    return score, (out, fit_const, fit_coeff, ok)


# ----------------------------------------------------------------------
# Device bagging (gbdt.cpp:163-243 BaggingHelper, re-keyed): the mask
# is a pure function of (bagging_seed, iteration), drawn with
# jax.random instead of the host MT19937, so sampling adds ZERO
# host->device transfers per iteration and the same stream is
# reproducible from a traced iteration index inside the fused scan.
def _bag_mask_core(key0, it, label, *, freq: int, n: int, frac: float,
                   pos_frac: float, neg_frac: float):
    """Per-row bagging weights for iteration ``it`` (traced or not).

    ``it`` is collapsed to its bagging_freq boundary, so iterations
    inside one bagging period share the draw exactly like the cached
    host mask did. ``label`` is the device label vector for balanced
    (pos/neg) bagging, else None."""
    it_eff = it - it % jnp.int32(max(freq, 1))
    key = jax.random.fold_in(key0, it_eff)
    if label is None:
        u = jax.random.uniform(key, (n,))
        return (u < jnp.float32(frac)).astype(jnp.float32)
    u = jax.random.uniform(key, label.shape)
    thr = jnp.where(label > 0, jnp.float32(pos_frac),
                    jnp.float32(neg_frac))
    return (u < thr).astype(jnp.float32)


@register_jit("bag_mask")
@functools.partial(jax.jit, static_argnames=("freq", "n", "frac",
                                             "pos_frac", "neg_frac"))
def _bag_mask_jit(key0, it, label=None, *, freq, n, frac, pos_frac,
                  neg_frac):
    return _bag_mask_core(key0, it, label, freq=freq, n=n, frac=frac,
                          pos_frac=pos_frac, neg_frac=neg_frac)


def _named(name, fn):
    """``fn`` as a ``functools.partial`` called ``name``: jax.jit names
    the compiled module (``jit_<name>`` on a profile's ``XLA Modules``
    line, ``jit(<name>)/`` at the head of every op path) after the
    callable, so a registered program shows under its registry name."""
    named = functools.partial(fn)
    named.__name__ = name
    return named


def _fused_iter_block(mat, ws, score, vscores, lr, it0, gops=(),
                      valid_data=(), bops=(), lops=None, *, learner,
                      grad_fn, bag_fn, m, k):
    """``m`` boosting iterations as one device program (lax.scan over
    gradients -> [sampling] -> grow -> score update; ``k`` trees per
    iteration for multiclass; ``bag_fn(it, grad, hess, *bops)``
    supplies device-computed row weights — bagging/GOSS — or None for
    no sampling). ``vscores``/``valid_data`` carry the valid-set scores
    through the scan: each tree is traversed on device against every
    valid set's binned matrix, so eval-bearing configs fuse too.

    Every array whose values come from a table is an ARGUMENT, never a
    constant, so two tables of one shape lower to one program and the
    second hits the persistent compile cache: ``gops`` are the
    objective's ``grad_operands()`` (labels, weights, a ranking
    objective's query layout) that ``grad_fn`` takes after the score,
    ``valid_data`` the valid sets' binned matrices, ``bops`` the
    sampling's operands (``GBDT._bag_operands``), ``lops`` the
    learner's (``grow_operands()``: the per-feature metadata, placed
    replicated on a mesh learner's devices). NOT
    module-jitted: the learner captures the training matrix layout, so
    each booster wraps this in its OWN jax.jit (``GBDT._fused_block``)
    — the compiled-program cache then dies with the booster instead of
    pinning its device buffers in a process-lifetime module cache."""
    meta = learner.meta if lops is None else lops

    def body(carry, it):
        mat, ws, score, vscores = carry
        with jax.named_scope(scopes.GRADIENTS):
            grad, hess = grad_fn(score if k > 1 else score[:, 0], *gops)
            if k == 1:
                grad = grad[:, None]
                hess = hess[:, None]
        bag = None
        if bag_fn is not None:
            with jax.named_scope(scopes.SAMPLE):
                bag = bag_fn(it, grad, hess, *bops)
        trees_k = []
        ok = None
        for tid in range(k):
            with jax.named_scope(scopes.GROW):
                mat, ws, tree, (row_ids, pos_value) = \
                    learner.traceable_grow(mat, ws, grad[:, tid],
                                           hess[:, tid], bag=bag,
                                           meta=meta)
            ok_t = tree.num_leaves > 1
            scale = jnp.where(ok_t, lr, jnp.float32(0.0))
            # one scatter-add in segment order: row_ids is a
            # permutation of [0, N), pos_value the leaf's value per
            # POSITION, so no table is read by position
            with jax.named_scope(scopes.SCORE_UPDATE):
                score = score.at[row_ids, tid].add(pos_value * scale)
            vscores = tuple(
                vs.at[:, tid].add(traverse_tree_arrays(
                    tree, vb, meta, scale, vmv))
                for vs, (vb, vmv) in zip(vscores, valid_data))
            trees_k.append(tree)
            ok = ok_t if ok is None else (ok | ok_t)
        trees = jax.tree.map(lambda *xs: jnp.stack(xs), *trees_k)
        return (mat, ws, score, vscores), (trees, ok)

    (mat, ws, score, vscores), (trees, oks) = jax.lax.scan(
        body, (mat, ws, score, vscores),
        it0 + jnp.arange(m, dtype=jnp.int32))
    # trees: TreeArrays stacked [m, k, ...]
    return mat, ws, score, vscores, trees, oks


class GBDT:
    """Gradient Boosting Decision Tree driver."""

    def __init__(self, config: Config, train_data: Optional[Dataset],
                 objective=None, hist_method: str = "auto"):
        self.config = config
        self.train_data = train_data
        self.objective = objective if objective is not None \
            else create_objective(config)
        self.num_class = int(config.num_class)
        self.num_tree_per_iteration = (
            self.objective.num_model_per_iteration
            if self.objective is not None else self.num_class)
        self.models: List[Tree] = []
        self.iter = 0
        self.shrinkage_rate = float(config.learning_rate)
        self.best_iter: Dict = {}
        self.best_score: Dict = {}
        self.best_msg: Dict = {}
        self.valid_sets: List[Dataset] = []
        self.valid_names: List[str] = []
        self.valid_metrics: List[list] = []
        self.valid_scores: List[jnp.ndarray] = []
        self.training_metrics: list = []
        self._grad_fn = None
        self._grad_operands: tuple = ()
        self.evals_result: Dict[str, Dict[str, list]] = {}

        if train_data is not None:
            self._setup_train(train_data, hist_method)

    # ------------------------------------------------------------------
    def _setup_train(self, train_data: Dataset, hist_method: str) -> None:
        cfg = self.config
        tel = get_telemetry()
        tel.ensure_started(cfg)
        tel.count("train.rows", train_data.num_data)
        # persistent compile cache (utils/compile_cache.py): wire
        # BEFORE the first compile so a warmed cache covers learner
        # construction too
        from ..utils.compile_cache import maybe_enable_compile_cache
        maybe_enable_compile_cache()
        from ..parallel import create_tree_learner
        with tel.setup_span(scopes.SETUP,
                            rows=train_data.num_data) as root:
            with tel.setup_span(scopes.SETUP_LEARNER) as sp:
                self.learner = create_tree_learner(
                    cfg.tree_learner, train_data, cfg,
                    hist_method=hist_method)
                plan = getattr(self.learner, "split_plan", None)
                sp.set(plan=str(plan()) if plan is not None else None)
            root.set(learner=type(self.learner).__name__)
            self.num_data = train_data.num_data
            with tel.setup_span(scopes.SETUP_OBJECTIVE) as sp:
                self._setup_objective(train_data)
                if self.objective is not None:
                    sp.set(**self.objective.setup_facts())
            with tel.setup_span(scopes.SETUP_SCORES):
                self._setup_scores(train_data)
            self._setup_guards()

    def _setup_objective(self, train_data: Dataset) -> None:
        if self.objective is not None:
            self.objective.init(train_data.metadata, self.num_data)
            self._grad_operands = self.objective.grad_operands()
            # objectives with per-call host randomness (rank_xendcg)
            # jit internally instead
            self._grad_fn = register_dynamic(
                "gbdt_grad", jax.jit(_named(
                    "gbdt_grad", self.objective.gradients))) \
                if getattr(self.objective, "jittable", True) \
                else self.objective.gradients

    def _setup_scores(self, train_data: Dataset) -> None:
        """The training score, the training metrics and the bagging
        state."""
        cfg = self.config
        k = self.num_tree_per_iteration
        init = train_data.metadata.init_score
        if init is not None:
            arr = np.asarray(init, np.float64)
            if arr.size == self.num_data * k:
                score0 = arr.reshape(k, self.num_data).T
            else:
                score0 = np.tile(arr[:, None], (1, k))
            self._has_init_score = True
        else:
            score0 = np.zeros((self.num_data, k))
            self._has_init_score = False
        self.train_score = jnp.asarray(score0, jnp.float32)
        self.class_need_train = [
            self.objective.class_need_train(i)
            if self.objective is not None
            and hasattr(self.objective, "class_need_train") else True
            for i in range(k)]
        if cfg.is_provide_training_metric:
            self.training_metrics = create_metrics(
                cfg.resolved_metrics(), cfg)
            for m in self.training_metrics:
                m.init(train_data.metadata, self.num_data)
        self._bag_rng = np.random.RandomState(cfg.bagging_seed)
        self._bag_key = jax.random.PRNGKey(cfg.bagging_seed)
        self._bag_label = None  # device label, built lazily (balanced)
        self.bag_weight: Optional[jnp.ndarray] = None
        self._feature_rng = np.random.RandomState(cfg.feature_fraction_seed)

    def _setup_guards(self) -> None:
        cfg = self.config
        # non-finite guard (robustness/guards.py): policy + the finite
        # flag folded into the combined gradient program when active
        self._guard_policy = str(getattr(cfg, "guard_policy", "off")
                                 or "off")
        self._last_grad_ok = None
        # leaf-linear models (models/linear.py): the fit rides the
        # host-stepped per-iteration path (the host tree is in hand
        # there anyway); async/fused paths are pinned off below
        self._linear_on = bool(cfg.linear_tree)
        if self._linear_on:
            if self.objective is not None and getattr(
                    self.objective, "is_renew_tree_output", False):
                log_warning(
                    "linear_tree is not supported with objective "
                    f"{self.objective.name()} (its percentile leaf "
                    "refit overwrites leaf outputs); using constant "
                    "leaves")
                self._linear_on = False
            elif not (hasattr(self.learner, "fit_linear_leaves")
                      and self.learner.linear_fit_available()):
                log_warning(
                    "linear_tree needs the raw numeric matrix on a "
                    "single-device learner (in-memory dense data); "
                    "using constant leaves")
                self._linear_on = False

    # ------------------------------------------------------------------
    def add_valid(self, valid_data: Dataset, name: str) -> None:
        if getattr(self, "_linear_on", False) \
                and valid_data.raw_numeric is None:
            # e.g. a sparse valid set against a dense linear train set:
            # linear valid scoring needs raw values it doesn't have
            log_warning(
                f"valid set {name!r} carries no raw numeric matrix; "
                "linear_tree falls back to constant leaves")
            self._linear_on = False
        metrics = create_metrics(self.config.resolved_metrics(), self.config)
        for m in metrics:
            m.init(valid_data.metadata, valid_data.num_data)
        self.valid_sets.append(valid_data)
        self.valid_names.append(name)
        self.valid_metrics.append(metrics)
        k = self.num_tree_per_iteration
        init = valid_data.metadata.init_score
        if init is not None:
            arr = np.asarray(init, np.float64)
            if arr.size == valid_data.num_data * k:
                score0 = arr.reshape(k, valid_data.num_data).T
            else:
                score0 = np.tile(arr[:, None], (1, k))
        else:
            score0 = np.zeros((valid_data.num_data, k))
        self.valid_scores.append(jnp.asarray(score0, jnp.float32))

    # ------------------------------------------------------------------
    # Bagging (gbdt.cpp:163-243): TPU-style = weight mask, not subset
    # copy. Default path is DEVICE-RESIDENT: the mask is a jitted
    # jax.random draw keyed by (bagging_seed, iteration) — no host mask
    # materialization/upload per iteration, and the identical stream is
    # reproducible inside the fused scan (``_traceable_bag_fn``).
    # ``LGBM_TPU_HOST_BAG=1`` restores the host-MT19937 path (parity/
    # attribution kill switch).
    def _bagging_need(self) -> bool:
        cfg = self.config
        return cfg.bagging_freq > 0 and (
            cfg.bagging_fraction < 1.0
            or cfg.pos_bagging_fraction < 1.0
            or cfg.neg_bagging_fraction < 1.0)

    @staticmethod
    def _device_bagging() -> bool:
        return os.environ.get("LGBM_TPU_HOST_BAG", "") != "1"

    def _bag_balanced_label(self) -> jnp.ndarray:
        if self._bag_label is None:
            self._bag_label = jnp.asarray(
                np.asarray(self.train_data.metadata.label), jnp.float32)
        return self._bag_label

    def _bagging_weight(self, it: int, grad=None,
                        hess=None) -> Optional[jnp.ndarray]:
        """grad/hess [N, K] are passed for gradient-based sampling (GOSS)."""
        cfg = self.config
        if not self._bagging_need():
            return None
        if not self._device_bagging():
            return self._bagging_weight_host(it)
        if it % cfg.bagging_freq != 0 and self.bag_weight is not None:
            return self.bag_weight
        get_telemetry().count_iter("host.dispatches")
        self.bag_weight = _bag_mask_jit(
            self._bag_key, jnp.int32(it),
            self._bag_balanced_label() if self._balanced_bagging()
            else None,
            freq=int(cfg.bagging_freq), n=self.num_data,
            frac=float(cfg.bagging_fraction),
            pos_frac=float(cfg.pos_bagging_fraction),
            neg_frac=float(cfg.neg_bagging_fraction))
        return self.bag_weight

    def _grad_hess_bag(self, score, it: int):
        """Gradients (+ the bagging mask when the base-class device
        draw is active) in ONE jitted program — the mask costs no
        extra dispatch. Returns ``(grad, hess, bag-or-None)``; a None
        bag means the caller must ask ``_bagging_weight`` (GOSS's
        gradient-dependent draw, host bagging, no sampling)."""
        tel = get_telemetry()
        combined = (self._bagging_need() and self._device_bagging()
                    and type(self)._bagging_weight
                    is GBDT._bagging_weight
                    and getattr(self.objective, "jittable", True))
        if not combined:
            tel.count_iter("host.dispatches")
            grad, hess = self._grad_fn(score, *self._grad_operands)
            self._last_grad_ok = None
            return grad, hess, None
        fn = getattr(self, "_grad_bag_jit", None)
        if fn is None:
            bag_core = self._traceable_bag_fn()
            grad_fn = self._grad_fn
            guard_on = self._guard_policy != "off"

            def _fused(s, i, gops=(), bops=()):
                g, h = grad_fn(s, *gops)
                bag = bag_core(i, g, h, *bops)
                if guard_on:
                    # guard reduction folded into the SAME program:
                    # the finite flag costs no extra dispatch
                    from ..robustness.guards import fold_finite_check
                    return g, h, bag, fold_finite_check(g, h)
                return g, h, bag

            fn = register_dynamic(
                "gbdt_grad_bag", jax.jit(_named("gbdt_grad_bag", _fused)))
            self._grad_bag_jit = fn
        tel.count_iter("host.dispatches")
        out = fn(score, jnp.int32(it), self._grad_operands,
                 self._bag_operands())
        if len(out) == 4:
            grad, hess, bag, self._last_grad_ok = out
        else:
            grad, hess, bag = out
            self._last_grad_ok = None
        self.bag_weight = bag
        return grad, hess, bag

    def _bagging_weight_host(self, it: int) -> Optional[jnp.ndarray]:
        """Legacy host-RNG mask (pre device-resident path)."""
        cfg = self.config
        if it % cfg.bagging_freq != 0 and self.bag_weight is not None:
            return self.bag_weight
        n = self.num_data
        if cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0:
            # balanced bagging (gbdt.cpp BaggingHelper balanced path)
            label = np.asarray(self.train_data.metadata.label)
            pos = label > 0
            mask = np.zeros(n, np.float32)
            mask[pos] = (self._bag_rng.rand(int(pos.sum()))
                         < cfg.pos_bagging_fraction)
            mask[~pos] = (self._bag_rng.rand(int((~pos).sum()))
                          < cfg.neg_bagging_fraction)
        else:
            mask = (self._bag_rng.rand(n)
                    < cfg.bagging_fraction).astype(np.float32)
        self.bag_weight = jnp.asarray(mask)
        return self.bag_weight

    def _feature_mask(self) -> Optional[jnp.ndarray]:
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return None
        f = self.train_data.num_features
        used = max(1, int(round(f * frac)))
        idx = self._feature_rng.choice(f, used, replace=False)
        mask = np.zeros(f, bool)
        mask[idx] = True
        return jnp.asarray(mask)

    # ------------------------------------------------------------------
    def boost_from_average(self, class_id: int) -> float:
        """gbdt.cpp:312-335."""
        cfg = self.config
        if self.models or self._has_init_score or self.objective is None:
            return 0.0
        if cfg.boost_from_average or self.train_data.num_features == 0:
            init_score = float(self.objective.boost_from_score(class_id))
            if abs(init_score) > kEpsilon:
                self.train_score = self.train_score.at[:, class_id].add(
                    init_score)
                for i in range(len(self.valid_scores)):
                    self.valid_scores[i] = \
                        self.valid_scores[i].at[:, class_id].add(init_score)
                log_info(f"Start training from score {init_score:.6f}")
                return init_score
        elif self.objective.name() in ("regression_l1", "quantile", "mape"):
            log_warning(
                f"Disabling boost_from_average in {self.objective.name()} "
                "may cause the slow convergence")
        return 0.0

    # ------------------------------------------------------------------
    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        """Returns True when training should STOP (no more valid splits),
        mirroring GBDT::TrainOneIter (gbdt.cpp:337-419)."""
        k = self.num_tree_per_iteration
        tel = get_telemetry()
        init_scores = [0.0] * k
        with tel.span("grad", phase=True):
            bag = None
            if gradients is None or hessians is None:
                for tid in range(k):
                    init_scores[tid] = self.boost_from_average(tid)
                score = self.train_score if k > 1 \
                    else self.train_score[:, 0]
                grad, hess, bag = self._grad_hess_bag(score, self.iter)
                if k == 1:
                    grad = grad[:, None]
                    hess = hess[:, None]
            else:
                grad = _coerce_custom_grad(gradients, self.num_data, k)
                hess = _coerce_custom_grad(hessians, self.num_data, k)
                self._last_grad_ok = None

            if bag is None:
                bag = self._bagging_weight(self.iter, grad, hess)
            fmask = self._feature_mask()
            try:
                grad, hess = self._check_gradients(grad, hess)
            except NonFiniteGradientError as e:
                if e.policy == "skip_iter":
                    self.skip_iteration()
                    return False
                raise

        should_continue = False
        new_trees: List[Tree] = []
        for tid in range(k):
            tree = None
            if self.class_need_train[tid] \
                    and self.train_data.num_features > 0:
                with tel.span("grow", phase=True):
                    result = self.learner.train(grad[:, tid],
                                                hess[:, tid],
                                                bag_weight=bag,
                                                feature_mask=fmask)
                with tel.span("tree", phase=True):
                    tel.count_iter("host.syncs")
                    tree = self.learner.to_host_tree(result)
            if tree is not None and tree.num_leaves > 1:
                should_continue = True
                with tel.span("update", phase=True):
                    if getattr(self, "_linear_on", False):
                        # batched per-leaf ridge solve on device; ONE
                        # explicit fetch of the coefficient triple
                        tel.count_iter("host.syncs")
                        tel.count_iter("host.dispatches")
                        self.learner.fit_linear_leaves(
                            tree, result, grad[:, tid], hess[:, tid],
                            bag_weight=bag)
                    self._renew_tree_output(tree, result, tid)
                    tree.shrink(self.shrinkage_rate)
                    self._update_scores(tree, result, tid)
                if abs(init_scores[tid]) > kEpsilon:
                    tree.add_bias(init_scores[tid])
            else:
                # constant-tree fallback, first iteration only
                output = 0.0
                if len(self.models) < k:
                    if not self.class_need_train[tid]:
                        if self.objective is not None:
                            output = float(
                                self.objective.boost_from_score(tid))
                    else:
                        output = init_scores[tid]
                    self.train_score = \
                        self.train_score.at[:, tid].add(output)
                    for i in range(len(self.valid_scores)):
                        self.valid_scores[i] = \
                            self.valid_scores[i].at[:, tid].add(output)
                tree = _constant_tree(output)
            new_trees.append(tree)

        if not should_continue:
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            # keep first-iteration constant trees, drop later no-op trees
            # (gbdt.cpp:407-415)
            if len(self.models) == 0:
                self.models.extend(new_trees)
            return True
        self.models.extend(new_trees)
        self.iter += 1
        tel.end_iteration(
            self.iter - 1, trees=k, num_data=self.num_data,
            bag_fraction=float(self.config.bagging_fraction)
            if bag is not None else 1.0)
        profile_boundary("iter")
        return False

    def _check_gradients(self, grad, hess):
        """Fault injection (``nan_grad``) + the non-finite guard
        (robustness/guards.py). Returns the (possibly poisoned)
        ``[N, K]`` pair; raises :class:`NonFiniteGradientError` when
        the guard trips under a non-``off`` policy — ``skip_iter`` is
        handled by the caller, ``raise``/``rollback`` propagate to the
        training driver."""
        from ..robustness.faults import get_fault_plan
        plan = get_fault_plan()
        injected = False
        if plan is not None:
            f = plan.take("nan_grad", iteration=self.iter)
            if f is not None:
                val = jnp.inf if str(f.params.get("value", "")) \
                    == "inf" else jnp.nan
                grad = grad.at[0, 0].set(jnp.float32(val))
                injected = True
        policy = self._guard_policy
        if policy == "off":
            return grad, hess
        tel = get_telemetry()
        ok = self._last_grad_ok
        if ok is None or injected:
            from ..robustness.guards import _finite_ok
            tel.count_iter("host.dispatches")
            ok = _finite_ok(grad, hess)
        tel.count_iter("host.syncs")
        if bool(jax.device_get(ok)):
            return grad, hess
        tel.count("guard.nonfinite_iters")
        log_warning(f"guard: non-finite gradients at iteration "
                    f"{self.iter} (policy={policy})")
        raise NonFiniteGradientError(self.iter, policy)

    def skip_iteration(self) -> None:
        """``guard_policy=skip_iter``: advance one iteration with a
        no-op constant tree per class so the model stays aligned with
        the iteration counter (checkpoint/resume and model truncation
        both index models by iteration)."""
        k = self.num_tree_per_iteration
        for _tid in range(k):
            self.models.append(_constant_tree(0.0))
        self.iter += 1
        tel = get_telemetry()
        tel.count("guard.skipped_iters")
        tel.end_iteration(self.iter - 1, trees=k, skipped=True,
                          num_data=self.num_data)

    def _renew_tree_output(self, tree: Tree, result, tid: int) -> None:
        """L1-family leaf refit (serial_tree_learner.cpp:720-758).

        Like the reference, the refit only sees in-bag rows (the
        data_partition holds bagged indices only); out-of-bag rows are
        masked out of the per-leaf percentiles here.
        """
        if self.objective is None or not getattr(
                self.objective, "is_renew_tree_output", False):
            return
        # exact-reference percentile semantics need the f64 host sort;
        # this stays a (counted) host round trip by design
        get_telemetry().count_iter("host.syncs", 2)
        score = np.asarray(jax.device_get(self.train_score[:, tid]),
                           np.float64)
        leaf_id = jax.device_get(result.leaf_id)
        if self.bag_weight is not None:
            bag = jax.device_get(self.bag_weight)
            leaf_id = np.where(bag > 0, leaf_id, -1)  # OOB rows: no leaf
        new_vals = self.objective.renew_tree_output(
            score, leaf_id, tree.num_leaves, tree.leaf_value)
        if new_vals is not None:
            tree.leaf_value = np.asarray(new_vals,
                                         np.float64)[:tree.num_leaves]

    def _update_scores(self, tree: Tree, result, tid: int) -> None:
        tel = get_telemetry()
        # train: leaf_id gather (no traversal), incl. out-of-bag rows —
        # ONE jitted donated program (gather + scatter fused)
        tel.count_iter("host.dispatches")
        if tree.is_linear:
            self.train_score = _score_add_leaf_linear(
                self.train_score, tree._padded_leaf_values(),
                *tree._padded_linear_args(), result.leaf_id,
                self.train_data.raw_numeric_device, tid=tid)
        else:
            self.train_score = _score_add_leaf(
                self.train_score,
                jnp.asarray(tree.leaf_value, jnp.float32),
                result.leaf_id, tid=tid)
        # valid: jitted bin-space traversal + add, ONE program each
        for i, vd in enumerate(self.valid_sets):
            tel.count_iter("host.dispatches")
            self.valid_scores[i] = tree.predict_binned_add(
                self.valid_scores[i], tid, vd.binned_device,
                vd.mv_slots_device,
                raw_dev=vd.raw_numeric_device if tree.is_linear
                else None)

    # ------------------------------------------------------------------
    def init_from_models(self, models: List, train_add=None,
                         valid_adds=None) -> None:
        """Continued training seed (GBDT::LoadModelFromString +
        ResetTrainingData resume semantics, boosting.cpp:35-68,
        gbdt.cpp:258-262): adopt an existing model's trees and add its
        raw contribution to the cached train/valid scores so the next
        ``train_one_iter`` boosts on the correct residuals."""
        self.models = list(models)
        self.iter = len(models) // self.num_tree_per_iteration
        if train_add is not None:
            add = np.asarray(train_add, np.float32)
            if add.ndim == 1:
                add = add[:, None]
            self.train_score = self.train_score + jnp.asarray(add)
        for i, va in enumerate(valid_adds or []):
            va = np.asarray(va, np.float32)
            if va.ndim == 1:
                va = va[:, None]
            self.valid_scores[i] = self.valid_scores[i] + jnp.asarray(va)

    # ------------------------------------------------------------------
    def refit(self, leaf_preds: np.ndarray,
              raw: Optional[np.ndarray] = None) -> None:
        """RefitTree (gbdt.cpp:266-289) + FitByExistingTree
        (serial_tree_learner.cpp:194-224): keep every tree's structure,
        refit leaf values on THIS booster's train data by sequential
        replay — per iteration, gradients at the current score, per-leaf
        sums, ``decay*old + (1-decay)*new_output*shrinkage``.

        ``linear_tree`` models refit their per-leaf ridge coefficients
        too (``_refit_tree_linear``): each leaf's existing model
        features get a fresh normal-equations solve from the new
        labels' grad/hess, blended by the same decay rule — the
        coefficients are never silently dropped. ``raw`` is the
        ORIGINAL-index raw feature matrix of the refit data
        (``Booster.refit`` passes it); without it the booster's own
        training dataset must carry the inner-index raw matrix, else a
        clear error is raised.

        Device-resident replay: gradients, per-leaf sums and score
        updates stay on device (one jitted program per tree, score
        buffer donated through the chain); the only device->host
        traffic is ONE batched fetch of the refit outputs at the end,
        applied to the host ``leaf_value`` arrays in f64. The legacy
        path fetched the full [N, K] gradients every iteration.

        ``leaf_preds`` [num_data, num_models] — each row's leaf index in
        every existing tree (from ``predict(..., pred_leaf=True)``).
        """
        self.finalize_trees()
        raw_dev = None
        use_inner = False
        if any(getattr(t, "is_linear", False) for t in self.models):
            if raw is not None:
                raw_dev = jnp.asarray(np.asarray(raw, np.float32))
            elif self.train_data is not None \
                    and self.train_data.raw_numeric is not None:
                raw_dev = self.train_data.raw_numeric_device
                use_inner = True
            else:
                from ..utils.log import LightGBMError
                raise LightGBMError(
                    "refit_linear_raw_missing: refit of a "
                    "linear_tree=true model must re-fit the per-leaf "
                    "linear coefficients, which needs the raw feature "
                    "matrix of the refit data; pass raw= (Booster."
                    "refit does) or construct the training Dataset "
                    "with linear_tree=true so it keeps raw values — "
                    "refusing to silently drop leaf coefficients")
        k = self.num_tree_per_iteration
        cfg = self.config
        decay = float(cfg.refit_decay_rate)
        leaf_preds = np.asarray(leaf_preds)
        if leaf_preds.ndim == 1:
            leaf_preds = leaf_preds.reshape(self.num_data, -1)
        if leaf_preds.shape != (self.num_data, len(self.models)):
            log_fatal(f"leaf_preds shape {leaf_preds.shape} does not "
                      f"match (num_data={self.num_data}, "
                      f"num_models={len(self.models)})")
        n_iters = len(self.models) // k
        lp_dev = jnp.asarray(leaf_preds.astype(np.int32))
        # sequential replay starts from the init score (the reference's
        # merged booster has an untouched score updater)
        self.train_score = jnp.zeros_like(self.train_score)
        pending = []  # (tree, device refit output, linear feats|None)
        for it in range(n_iters):
            sc = self.train_score if k > 1 else self.train_score[:, 0]
            grad, hess = self._grad_fn(sc, *self._grad_operands)
            if grad.ndim == 1:
                grad = grad[:, None]
                hess = hess[:, None]
            for tid in range(k):
                mi = it * k + tid
                tree = self.models[mi]
                if hasattr(tree, "materialize"):
                    tree = tree.materialize()
                    self.models[mi] = tree
                nl = max(tree.num_leaves, 1)
                if getattr(tree, "is_linear", False):
                    feats = np.asarray(
                        tree.leaf_features_inner if use_inner
                        else tree.leaf_features, np.int32)
                    self.train_score, out = _refit_tree_linear(
                        self.train_score, lp_dev[:, mi], grad[:, tid],
                        hess[:, tid], raw_dev, jnp.asarray(feats),
                        jnp.asarray(tree.leaf_value, jnp.float32),
                        jnp.asarray(tree.leaf_const, jnp.float32),
                        jnp.asarray(tree.leaf_coeff, jnp.float32),
                        jnp.float32(tree.shrinkage),
                        jnp.float32(decay),
                        nl=nl, tid=tid, l1=float(cfg.lambda_l1),
                        l2=float(cfg.lambda_l2),
                        mds=float(cfg.max_delta_step),
                        lam=float(cfg.linear_lambda),
                        l2lin=float(cfg.lambda_l2))
                    pending.append((tree, out, feats))
                else:
                    self.train_score, out = _refit_tree(
                        self.train_score, lp_dev[:, mi], grad[:, tid],
                        hess[:, tid],
                        jnp.asarray(tree.leaf_value, jnp.float32),
                        jnp.float32(tree.shrinkage), jnp.float32(decay),
                        nl=nl, tid=tid, l1=float(cfg.lambda_l1),
                        l2=float(cfg.lambda_l2),
                        mds=float(cfg.max_delta_step))
                    pending.append((tree, out, None))
        get_telemetry().count("host.syncs")
        outs = jax.device_get([o for _, o, _ in pending])  # ONE fetch
        for (tree, _, feats), out in zip(pending, outs):
            if feats is None:
                tree.leaf_value = (decay * tree.leaf_value
                                   + (1.0 - decay)
                                   * np.asarray(out, np.float64)
                                   * tree.shrinkage)
                continue
            # linear tree: redo the f32 device blend in f64 on the
            # exported arrays (same rule as the constant leaf_value).
            # everything here is HOST data already — the whole pending
            # list went through the single batched device_get above
            o, fit_const, fit_coeff, ok = out
            o64 = np.asarray(o, np.float64)
            okh = np.asarray(ok, bool)  # graftlint: allow[GL105]
            shrink = tree.shrinkage
            tree.leaf_value = (decay * tree.leaf_value
                               + (1.0 - decay) * o64 * shrink)
            fc64 = np.asarray(fit_const,  # graftlint: allow[GL105]
                              np.float64)
            fw64 = np.asarray(fit_coeff,  # graftlint: allow[GL105]
                              np.float64)
            target_c = np.where(okh, fc64, o64)
            const = decay * tree.leaf_const \
                + (1.0 - decay) * target_c * shrink
            coeff = decay * tree.leaf_coeff \
                + (1.0 - decay) * np.where(okh[:, None], fw64,
                                           0.0) * shrink
            get_telemetry().count("refit.linear_trees")
            tree.set_linear(
                feats, coeff, const,
                dataset=self.train_data if use_inner else None)

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """gbdt.cpp:421-437."""
        if self.iter <= 0:
            return
        k = self.num_tree_per_iteration
        for tid in range(k):
            tree = self.models[-k + tid]
            tree.shrink(-1.0)
            if self.train_data is not None:
                tadd = tree.predict_binned_device(
                    self.train_data.binned_device,
                    self.train_data.mv_slots_device,
                    raw_dev=self.train_data.raw_numeric_device)
                self.train_score = self.train_score.at[:, tid].add(tadd)
            for i, vd in enumerate(self.valid_sets):
                vadd = tree.predict_binned_device(
                    vd.binned_device, vd.mv_slots_device,
                    raw_dev=vd.raw_numeric_device)
                self.valid_scores[i] = \
                    self.valid_scores[i].at[:, tid].add(vadd)
        del self.models[-k:]
        self.iter -= 1

    # ------------------------------------------------------------------
    def eval_metrics(self) -> List[Tuple[str, str, float, bool]]:
        """All (dataset_name, metric_name, value, bigger_better) tuples.

        Device-resident path (default): raw scores are converted on
        device and every dataset's (score, pred) pair is pulled in ONE
        batched ``device_get`` — the legacy path fetched the score and
        round-tripped a conversion per metric per dataset. Host-side
        f64 reductions are unchanged, so values are bit-identical
        (LGBM_TPU_DEVICE_EVAL=0 restores the legacy path)."""
        from ..metric.metrics import batched_eval, device_eval_enabled
        tel = get_telemetry()
        jobs = []
        if self.training_metrics:
            jobs.append((self.training_metrics,
                         self._metric_score(self.train_score),
                         "training"))
        for i, metrics in enumerate(self.valid_metrics):
            if metrics:
                jobs.append((metrics,
                             self._metric_score(self.valid_scores[i]),
                             self.valid_names[i]))
        if not jobs:
            return []
        if device_eval_enabled():
            tel.count_iter("host.syncs")
            tel.count_iter("host.dispatches", len(jobs))
            return [row for rows in batched_eval(jobs, self.objective)
                    for row in rows]
        out = []
        for metrics, sc, name in jobs:
            sc_h = jax.device_get(sc)
            # legacy accounting: score fetch + per-metric convert
            # round trip (upload + convert dispatch + result fetch)
            tel.count_iter("host.syncs", 1 + len(metrics))
            tel.count_iter("host.dispatches", 2 * len(metrics))
            for m in metrics:
                vals = m.eval(sc_h, self.objective)
                for name_, v in zip(m.names, vals):
                    out.append((name, name_, v,
                                m.factor_to_bigger_better > 0))
        return out

    def _metric_score(self, score: jnp.ndarray):
        return score if self.num_tree_per_iteration > 1 else score[:, 0]

    def output_metric(self, it: int) -> str:
        """OutputMetric (gbdt.cpp:484-542): prints, tracks best, returns
        non-empty best message when early stopping is met."""
        cfg = self.config
        need_output = cfg.metric_freq > 0 and it % cfg.metric_freq == 0
        es_round = cfg.early_stopping_round
        ret = ""
        msg_lines = []
        results = self.eval_metrics()
        get_telemetry().eval_results(it, results)
        first_metric_seen: Dict[str, bool] = {}
        for ds_name, mname, value, bigger in results:
            line = f"Iteration:{it}, {ds_name} {mname} : {value:g}"
            if need_output:
                log_info(line)
            msg_lines.append(line)
            self.evals_result.setdefault(ds_name, {}).setdefault(
                mname, []).append(value)
            if ds_name == "training" or es_round <= 0:
                continue
            if cfg.first_metric_only and first_metric_seen.get(ds_name):
                continue
            first_metric_seen[ds_name] = True
            key = (ds_name, mname)
            cur = value if bigger else -value
            if key not in self.best_score or cur > self.best_score[key]:
                self.best_score[key] = cur
                self.best_iter[key] = it
                self.best_msg[key] = "\n".join(msg_lines)
            elif not ret and it - self.best_iter[key] >= es_round:
                ret = self.best_msg[key]
        return ret

    # ------------------------------------------------------------------
    # Async (device-resident) iteration path. train_one_iter's public
    # contract syncs every iteration — ~2 blocking host round trips per
    # tree (flag check + host tree pull). The async path keeps
    # everything on device:
    #   * score updates gather straight from the device TreeArrays;
    #   * valid-set scoring traverses TreeArrays on device;
    #   * host Tree objects are DeferredTree (batched device_get later);
    #   * the stop flag is a device bool, flushed every N iterations —
    #     safe because an un-splittable iteration contributes EXACTLY
    #     zero to every score (scale 0), so over-run iterations are
    #     no-ops that truncation removes (matching gbdt.cpp:407-415).
    _ASYNC_FLUSH = 16

    def _async_supported(self) -> bool:
        from ..robustness.faults import fault_plan_active
        return (type(self).train_one_iter is GBDT.train_one_iter
                and self.objective is not None
                and not getattr(self.objective, "is_renew_tree_output",
                                False)
                and all(self.class_need_train)
                # the leaf-linear fit needs the host tree in hand each
                # iteration (path-feature selection), so linear trees
                # pin the host-stepped path
                and not getattr(self, "_linear_on", False)
                # non-finite guards need the per-iteration sync check;
                # armed fault plans need per-iteration injection points
                and self._guard_policy == "off"
                and not fault_plan_active())

    def _train_one_iter_async(self):
        """One boosting iteration with zero host syncs. Returns a device
        bool scalar: True = a real split happened (continue)."""
        k = self.num_tree_per_iteration
        tel = get_telemetry()
        with tel.span("grad", phase=True):
            score = self.train_score if k > 1 else self.train_score[:, 0]
            grad, hess, bag = self._grad_hess_bag(score, self.iter)
            if k == 1:
                grad = grad[:, None]
                hess = hess[:, None]
            if bag is None:
                bag = self._bagging_weight(self.iter, grad, hess)
            fmask = self._feature_mask()
        flag = None
        for tid in range(k):
            with tel.span("grow", phase=True):
                result = self.learner.train(grad[:, tid], hess[:, tid],
                                            bag_weight=bag,
                                            feature_mask=fmask)
            with tel.span("update", phase=True):
                ta = result.tree
                ok = ta.num_leaves > 1
                scale = jnp.where(ok, jnp.float32(self.shrinkage_rate),
                                  jnp.float32(0.0))
                leaf_vals = ta.leaf_value * scale
                tel.count_iter("host.dispatches",
                               1 + len(self.valid_sets))
                self.train_score = self.train_score.at[:, tid].add(
                    leaf_vals[result.leaf_id])
                for i, vd in enumerate(self.valid_sets):
                    vadd = traverse_tree_arrays(ta, vd.binned_device,
                                                self.learner.meta, scale,
                                                vd.mv_slots_device)
                    self.valid_scores[i] = \
                        self.valid_scores[i].at[:, tid].add(vadd)
                self.models.append(DeferredTree(
                    ta, self.learner.dataset,
                    shrinkage=self.shrinkage_rate))
            flag = ok if flag is None else (flag | ok)
        self.iter += 1
        tel.end_iteration(
            self.iter - 1, trees=k, mode="async",
            num_data=self.num_data,
            bag_fraction=float(self.config.bagging_fraction)
            if bag is not None else 1.0)
        profile_boundary("iter")
        return flag

    def finalize_trees(self) -> None:
        """Materialize every DeferredTree with ONE batched device->host
        transfer (instead of one blocking sync per tree)."""
        deferred = [m for m in self.models
                    if isinstance(m, DeferredTree) and m._tree is None]
        if not deferred:
            return
        hosts = jax.device_get([d._arrays for d in deferred])
        for d, h in zip(deferred, hosts):
            d.materialize(host_arrays=h)

    def _truncate_surplus(self, n_iters: int) -> None:
        """Drop trailing no-op iterations recorded past the true stop
        point (their score contribution was zero by construction)."""
        k = self.num_tree_per_iteration
        del self.models[-n_iters * k:]
        self.iter -= n_iters

    # ------------------------------------------------------------------
    # Fused-scan path: whole boosting ITERATIONS chained on device.
    # The async path above still pays ~6-8 host->device dispatches per
    # iteration (gradients, grow, score-update ops). Scanning M
    # iterations inside ONE jitted program (gradients -> grow -> score
    # update per scan step, stacked TreeArrays out) drops that to one
    # dispatch + one stop-flag fetch per block. What a dispatch costs
    # on a local chip is not measured yet (ROADMAP S3).
    _FUSED_BLOCK = 64

    def _balanced_bagging(self) -> bool:
        cfg = self.config
        return cfg.pos_bagging_fraction < 1.0 \
            or cfg.neg_bagging_fraction < 1.0

    def _bag_operands(self) -> tuple:
        """What the sampling hook takes after ``(it, grad, hess)`` in a
        compiled program: balanced bagging's labels, an array the size
        of the table, as an ARGUMENT (``_fused_iter_block``)."""
        if not (self._bagging_need() and self._device_bagging()
                and self._balanced_bagging()):
            return ()
        return (self._bag_balanced_label(),)

    def _traceable_bag_fn(self):
        """Device-traceable per-iteration sampling hook for the fused
        path: a function ``(it, grad, hess, *bops) -> [N] weights`` or
        None, ``bops`` = ``_bag_operands()``: balanced bagging's labels,
        none otherwise. Base GBDT returns the device bagging draw (the
        SAME stream as ``_bagging_weight`` for equal ``it``) when bagging
        is configured and device-resident; GOSS overrides."""
        cfg = self.config
        if not self._bagging_need() or not self._device_bagging():
            return None
        key0 = self._bag_key
        freq = int(cfg.bagging_freq)
        n = self.num_data
        frac = float(cfg.bagging_fraction)
        pos_frac = float(cfg.pos_bagging_fraction)
        neg_frac = float(cfg.neg_bagging_fraction)

        def bag_fn(it, grad, hess, label=None):
            return _bag_mask_core(key0, it, label, freq=freq, n=n,
                                  frac=frac, pos_frac=pos_frac,
                                  neg_frac=neg_frac)

        return bag_fn

    def _sampling_traceable(self) -> bool:
        """True when the per-iteration row sampling (if any) can run
        inside a scanned device program: either no sampling at all, or
        a device-traceable bag fn covering the configured sampling."""
        custom = type(self)._bagging_weight is not GBDT._bagging_weight
        if not self._bagging_need() and not custom:
            return True
        return self._traceable_bag_fn() is not None

    def _fused_scan_supported(self) -> bool:
        ln = getattr(self, "learner", None)
        if os.environ.get("LGBM_TPU_NO_FUSE_ITERS"):
            return False  # attribution/kill switch (perf sequence)
        on_device = on_tpu() \
            or os.environ.get("LGBM_TPU_FUSE_ITERS") == "1"
        return (on_device
                # valid sets ride the scan carry (score traversal per
                # tree); the mesh learners keep the no-valid gate —
                # their replicated tree output meeting an unsharded
                # valid matrix inside one program is unvalidated
                and (not self.valid_sets
                     or getattr(ln, "num_shards", 1) == 1)
                # non-jittable objectives (rank_xendcg) draw host
                # randomness per gradient call; inside a scan trace
                # that draw would be frozen into the compiled program
                and getattr(self.objective, "jittable", True)
                # sampling must be device-traceable (device bagging,
                # GOSS); host-RNG bagging (LGBM_TPU_HOST_BAG) stays on
                # the per-iteration path
                and self._sampling_traceable()
                and type(self)._feature_mask is GBDT._feature_mask
                and self.config.feature_fraction >= 1.0
                and getattr(ln, "supports_fused_scan", False)
                and ln.fused_scan_ok())

    def _eval_cadence(self) -> int:
        """Iterations between eval boundaries when eval rides the fused
        path: the metric output frequency (>= 1). The per-iteration
        paths evaluate every iteration; fusing trades that granularity
        for dispatch elimination, which is exactly what metric_freq
        asks for."""
        return max(1, int(self.config.metric_freq))

    def _fused_block(self):
        """The booster's ``gbdt_fused_block`` program, built once."""
        fused = getattr(self, "_fused_jit", None)
        if fused is None:
            fused = register_dynamic(
                "gbdt_fused_block",
                jax.jit(
                    _named("gbdt_fused_block", functools.partial(
                        _fused_iter_block, learner=self.learner,
                        grad_fn=self._grad_fn,
                        bag_fn=self._traceable_bag_fn(),
                        k=self.num_tree_per_iteration)),
                    static_argnames=("m",), donate_argnums=(0, 1, 2, 3)),
                donate=(0, 1, 2))
            self._fused_jit = fused
        return fused

    def _fused_block_args(self) -> tuple:
        """The arguments of the next fused block: the carry (matrix,
        twin, scores, valid scores), the shrinkage and first iteration,
        then every array whose values come from a table
        (``_fused_iter_block``)."""
        ln = self.learner
        valid_data = tuple((vd.binned_device, vd.mv_slots_device)
                           for vd in self.valid_sets)
        return (ln.mat, ln.ws, self.train_score, tuple(self.valid_scores),
                jnp.float32(self.shrinkage_rate), jnp.int32(self.iter),
                self._grad_operands, valid_data, self._bag_operands(),
                ln.grow_operands())

    def _train_fused_blocks(self, iters: int,
                            eval_every: Optional[int] = None) -> bool:
        """Run [self.iter, iters) in <=_FUSED_BLOCK-iteration scanned
        blocks, one device dispatch per block. Over-run iterations
        after a no-split stop are zero-contribution no-ops, truncated
        exactly like the async flush path. ``eval_every`` caps blocks
        at the eval cadence and runs metric eval at each boundary
        (valid scores advance INSIDE the scan). Returns True when
        training stopped early (no-split)."""
        ln = self.learner
        k = self.num_tree_per_iteration
        fused = self._fused_block()
        while self.iter < iters:
            # largest power-of-2 block <= remaining (capped): the set of
            # compiled scan lengths stays O(log) regardless of how the
            # caller slices its train() calls, so a warmed persistent
            # cache covers every phase of a run. An eval cadence caps
            # the block at the next boundary instead of disabling
            # fusion outright.
            limit = iters - self.iter
            if eval_every is not None:
                to_boundary = eval_every - (self.iter % eval_every)
                limit = min(limit, to_boundary)
            m = self._FUSED_BLOCK
            while m > limit:
                m //= 2
            m = max(m, 1)
            tel = get_telemetry()
            t_blk = time.perf_counter()
            with tel.span("boosting", trace=scopes.BLOCK_DISPATCH):
                tel.count_iter("host.dispatches")
                tel.count("fused.block_hits")
                args = self._fused_block_args()
                # avals before the call: the arguments are donated
                new_prog = tel.enabled and scopes.remember(
                    "gbdt_fused_block", fused, args, m=m)
                (ln.mat, ln.ws, self.train_score, vs, trees,
                 oks) = fused(*args, m=m)
                if new_prog:
                    # first dispatch of this block length: the scope
                    # table now, while the compiled module is a cache
                    # lookup and the program is alive
                    new_prog.scopes()
                self.valid_scores = list(vs)
            # the stop-flag fetch is the block's real device barrier;
            # the host bookkeeping after it runs with the device idle
            with tel.span("device_sync", trace=scopes.BLOCK_SYNC):
                tel.count_iter("host.syncs")
                flags = [bool(v) for v in jax.device_get(oks)]
            with tel.span("block_trees", trace=scopes.BLOCK_TREES):
                stack = TreeStack(trees)      # TreeArrays [m, k, ...]
                for j in range(m):
                    for tid in range(k):
                        self.models.append(DeferredStackTree(
                            stack, (j, tid), ln.dataset,
                            shrinkage=self.shrinkage_rate))
                self.iter += m
                if tel.enabled:
                    # from before the dispatch to after the barrier, so
                    # this wall time covers device execution
                    dur = time.perf_counter() - t_blk
                    tel.count("learner.trees", m * k)
                    tel.count("learner.row_iters", m * self.num_data)
                    tel.record("block", iter_start=self.iter - m,
                               iters=m, num_data=self.num_data,
                               dur_s=round(dur, 6),
                               rows_per_s=round(
                                   m * self.num_data / dur, 3)
                               if dur > 0 else 0.0)
            profile_boundary("block")
            if not all(flags):
                self._truncate_surplus(len(flags) - flags.index(False))
                log_warning(
                    "Stopped training because there are no more "
                    "leaves that meet the split requirements")
                return True
            if eval_every is not None \
                    and (self.iter % eval_every == 0
                         or self.iter >= iters):
                with tel.span("eval", trace=scopes.EVAL):
                    # early stopping is gated off on this path
                    # (_train_impl), so output_metric only records
                    self.output_metric(self.iter)
        return False

    def train(self, num_iterations: Optional[int] = None) -> None:
        """Full training loop (GBDT::Train, gbdt.cpp:245-264).

        Profiling: ``LGBM_TPU_PROFILE_DIR`` (env) or ``profile_dir``
        (param) arms a ONE-SHOT ``jax.profiler`` capture window
        aligned to iteration/block span boundaries
        (observability/tracing.py ProfileWindow — skip/length tunable
        via ``LGBM_TPU_PROFILE_SKIP``/``LGBM_TPU_PROFILE_SPANS``), so
        the device trace covers steady-state iterations, not the
        compile storm. Telemetry: ``LGBM_TPU_TELEMETRY=/path.jsonl``
        (or ``telemetry_out``) for a structured trace, and
        ``LGBM_TPU_TRACE=/path.json`` (or ``trace_out``) for the
        Perfetto-loadable span timeline — see docs/Observability.md."""
        tel = get_telemetry()
        tel.ensure_started(self.config)
        it0 = self.iter
        t0 = time.perf_counter()
        try:
            with tel.span(scopes.TRAIN, ledger=True, rows=self.num_data):
                self._train_impl(num_iterations)
        finally:
            # close a profiler capture still in flight (run shorter
            # than the window) and persist the span timeline
            profile_close()
            get_tracer().flush()
        if tel.enabled:
            self.emit_train_end(it0, time.perf_counter() - t0)

    def emit_train_end(self, it0: int, dur: float) -> None:
        """Emit the ``train_end`` summary record after a training loop;
        shared with ``engine.train``'s host-stepped path, which
        bypasses ``GBDT.train``."""
        tel = get_telemetry()
        if not tel.enabled:
            return
        iters = self.iter - it0
        tel.record(
            "train_end", iters=iters, num_data=self.num_data,
            dur_s=round(dur, 6),
            rows_per_s=round(self.num_data * max(iters, 0) / dur, 3)
            if dur > 0 else 0.0,
            compile=tel.compile_stats(),
            phase_totals=tel.phase_totals(),
            counters=dict(tel.counters),
            memory=memory_snapshot())
        tel.flush()

    def _train_impl(self, num_iterations: Optional[int] = None) -> None:
        iters = num_iterations if num_iterations is not None \
            else self.config.num_iterations
        use_async = self._async_supported()
        has_eval = bool(self.training_metrics) \
            or any(len(m) > 0 for m in self.valid_metrics)
        # batching the stop-flag check is only sound when a no-split
        # iteration reproduces identically on the next iteration; host
        # RNG that advances per call (host bagging mask, feature
        # sampling) breaks that, so flush every iteration there.
        # Device bagging is a pure function of the iteration index and
        # does NOT count as host RNG.
        cfg = self.config
        host_rng_per_iter = (
            self._bagging_need() and not self._device_bagging()
        ) or cfg.feature_fraction < 1.0 or cfg.extra_trees \
            or cfg.feature_fraction_bynode < 1.0
        flush_every = 1 if (has_eval or host_rng_per_iter) \
            else self._ASYNC_FLUSH
        tel = get_telemetry()
        # eval rides the fused path at the metric_freq cadence; early
        # stopping needs its per-iteration best tracking + score
        # rollback, so it pins the per-iteration path (an overridden
        # early-stop hook — DART — is already excluded by
        # _async_supported)
        fuse_ok = use_async and not host_rng_per_iter \
            and self._fused_scan_supported() \
            and (not has_eval or cfg.early_stopping_round <= 0)
        if fuse_ok:
            if not self.models and self.iter < iters:
                # boost-from-average + constant-tree fallback need the
                # sync first iteration, exactly like the async path
                with tel.span("boosting", trace="boost_iter"):
                    if self.train_one_iter():
                        self.finalize_trees()
                        return
                if has_eval:
                    with tel.span("eval", trace="eval"):
                        self.output_metric(self.iter)
            self._train_fused_blocks(
                iters, eval_every=self._eval_cadence()
                if has_eval else None)
            self.finalize_trees()
            return
        pending: List = []
        stopped = False
        for it in range(self.iter, iters):
            if use_async and self.models:
                with tel.span("boosting", trace="boost_iter"):
                    pending.append(self._train_one_iter_async())
                if len(pending) >= flush_every or it == iters - 1:
                    with tel.span("device_sync"):
                        tel.count_iter("host.syncs")
                        flags = [bool(v) for v in jax.device_get(pending)]
                    pending.clear()
                    if not all(flags):
                        self._truncate_surplus(
                            len(flags) - flags.index(False))
                        log_warning(
                            "Stopped training because there are no more "
                            "leaves that meet the split requirements")
                        stopped = True
                if stopped:
                    break
            else:
                # first iteration (boost-from-average, constant-tree
                # fallback) and non-async boosters take the sync path
                with tel.span("boosting", trace="boost_iter"):
                    if self.train_one_iter():
                        break
            if has_eval:
                # not a phase span: end_iteration already closed this
                # iteration's record, so eval lands in span totals only
                with tel.span("eval", trace="eval"):
                    stop_early = self._eval_and_check_early_stopping()
                if stop_early:
                    break
        if pending:
            flags = [bool(v) for v in jax.device_get(pending)]
            if not all(flags):
                self._truncate_surplus(len(flags) - flags.index(False))
        self.finalize_trees()

    def _eval_and_check_early_stopping(self) -> bool:
        best_msg = self.output_metric(self.iter)
        if best_msg:
            es = self.config.early_stopping_round
            log_info(f"Early stopping at iteration {self.iter}, the best "
                     f"iteration round is {self.iter - es}")
            log_info(f"Output of best iteration round:\n{best_msg}")
            # truncate the model back to the best iteration AND keep the
            # cached scores/iteration counter consistent with it, so that
            # later eval/continued training see the truncated model
            k = self.num_tree_per_iteration
            for tree in self.models[-es * k:]:
                tree.shrink(-1.0)
            for j in range(es):
                for tid in range(k):
                    tree = self.models[-(es - j) * k + tid]
                    tadd = tree.predict_binned_device(
                        self.train_data.binned_device,
                        self.train_data.mv_slots_device,
                        raw_dev=self.train_data.raw_numeric_device)
                    self.train_score = \
                        self.train_score.at[:, tid].add(tadd)
                    for i, vd in enumerate(self.valid_sets):
                        vadd = tree.predict_binned_device(
                            vd.binned_device, vd.mv_slots_device,
                            raw_dev=vd.raw_numeric_device)
                        self.valid_scores[i] = \
                            self.valid_scores[i].at[:, tid].add(vadd)
            del self.models[-es * k:]
            self.iter -= es
            return True
        return False

    # ------------------------------------------------------------------
    @property
    def num_iterations_trained(self) -> int:
        return len(self.models) // self.num_tree_per_iteration

    def predict_raw(self, data: np.ndarray,
                    num_iteration: int = -1) -> np.ndarray:
        """PredictRaw (gbdt_prediction.cpp:13-31) over raw features."""
        self.finalize_trees()
        data = np.asarray(data, np.float64)
        n = data.shape[0]
        k = self.num_tree_per_iteration
        used = len(self.models) if num_iteration < 0 else min(
            num_iteration * k, len(self.models))
        out = np.zeros((n, k))
        for i in range(used):
            out[:, i % k] += self.models[i].predict(data)
        return out if k > 1 else out[:, 0]

    def predict(self, data: np.ndarray,
                num_iteration: int = -1) -> np.ndarray:
        raw = self.predict_raw(data, num_iteration)
        if self.objective is not None:
            return jax.device_get(
                self.objective.convert_output(jnp.asarray(raw)))
        return raw


def _coerce_custom_grad(arr, num_data: int, k: int) -> jnp.ndarray:
    """Accept [N], [N, K], [K, N] or reference-flat [K*N] layouts."""
    a = np.asarray(arr, np.float32)
    if a.ndim == 1:
        if a.size == num_data:
            a = a[:, None]
        elif a.size == num_data * k:
            a = a.reshape(k, num_data).T  # reference K contiguous blocks
        else:
            log_fatal(f"custom gradient length {a.size} does not match "
                      f"num_data*num_class {num_data * k}")
    elif a.shape == (k, num_data):
        a = a.T
    if a.shape != (num_data, k):
        log_fatal(f"custom gradient shape {a.shape} invalid")
    return jnp.asarray(a)


def _constant_tree(output: float) -> Tree:
    """Tree::AsConstantTree (tree.h:191-201)."""
    from .tree import TreeArrays
    import numpy as _np
    arrays = TreeArrays(
        num_leaves=_np.int32(1),
        split_feature=_np.zeros(1, _np.int32),
        threshold_bin=_np.zeros(1, _np.int32),
        decision_type=_np.zeros(1, _np.int32),
        left_child=_np.zeros(1, _np.int32),
        right_child=_np.zeros(1, _np.int32),
        split_gain=_np.zeros(1, _np.float32),
        internal_value=_np.zeros(1, _np.float32),
        internal_weight=_np.zeros(1, _np.float32),
        internal_count=_np.zeros(1, _np.float32),
        leaf_value=_np.full(1, output, _np.float32),
        leaf_weight=_np.zeros(1, _np.float32),
        leaf_count=_np.zeros(1, _np.float32),
        leaf_parent=_np.full(1, -1, _np.int32),
        leaf_depth=_np.zeros(1, _np.int32),
        cat_bitsets=_np.zeros((1, 8), _np.uint32))
    return Tree(arrays)
