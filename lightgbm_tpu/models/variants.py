"""Boosting variants: GOSS, DART, RF.

Reference analogs: ``src/boosting/goss.hpp`` (Gradient-based One-Side
Sampling as a bagging override), ``src/boosting/dart.hpp`` (dropout
trees with weight renormalization), ``src/boosting/rf.hpp`` (random
forest mode: no shrinkage, one-time gradients, averaged output).

TPU-first deviations (semantics preserved, mechanics re-designed):
  * GOSS selection runs fully on device as one jitted program: the
    top-``top_rate`` threshold is a quantile of |g*h| and the
    small-gradient sample is an independent Bernoulli draw with the same
    expected count as the reference's sequential exact draw
    (goss.hpp:95-122). Rows become a weight vector (0 / 1 / multiplier)
    folded into the (grad,hess,count) channels — no index compaction.
  * DART/RF score arithmetic uses the leaf_id gather / binned traversal
    paths instead of ScoreUpdater::AddScore.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.jit_registry import register_jit
from ..utils.log import log_fatal, log_info
from .gbdt import GBDT, _constant_tree, _score_add_col, kEpsilon
from .tree import Tree


# ----------------------------------------------------------------------
@register_jit("goss_weights")
@functools.partial(jax.jit, static_argnames=("top_rate", "other_rate"))
def _goss_weights(grad, hess, key, *, top_rate: float, other_rate: float):
    """Per-row GOSS weights on device. grad/hess: [N, K]."""
    s = jnp.abs(grad * hess).sum(axis=1)  # combined score (goss.hpp:84-88)
    thr = jnp.quantile(s, 1.0 - top_rate)
    top = s >= thr
    # sample the rest with the same expected count as other_rate * N
    p_rest = other_rate / max(1e-12, 1.0 - top_rate)
    sampled = (jax.random.uniform(key, s.shape) < p_rest) & ~top
    multiply = (1.0 - top_rate) / other_rate  # (cnt-top_k)/other_k
    return (top.astype(jnp.float32)
            + sampled.astype(jnp.float32) * jnp.float32(multiply))


class GOSS(GBDT):
    """Gradient-based One-Side Sampling (goss.hpp)."""

    def _setup_train(self, train_data, hist_method):
        cfg = self.config
        if not (0.0 < cfg.top_rate and 0.0 < cfg.other_rate
                and cfg.top_rate + cfg.other_rate <= 1.0):
            log_fatal("GOSS requires top_rate > 0, other_rate > 0 and "
                      "top_rate + other_rate <= 1")
        if cfg.bagging_freq > 0 and cfg.bagging_fraction != 1.0:
            log_fatal("Cannot use bagging in GOSS")
        log_info("Using GOSS")
        super()._setup_train(train_data, hist_method)
        self._goss_key = jax.random.PRNGKey(cfg.bagging_seed)

    def _bagging_weight(self, it: int, grad=None,
                        hess=None) -> Optional[jnp.ndarray]:
        # no subsampling for the first 1/learning_rate iters (goss.hpp:129)
        if it < int(1.0 / self.config.learning_rate) or grad is None:
            self.bag_weight = None
            return None
        key = jax.random.fold_in(self._goss_key, it)
        self.bag_weight = _goss_weights(
            grad, hess, key, top_rate=float(self.config.top_rate),
            other_rate=float(self.config.other_rate))
        return self.bag_weight

    def _bag_operands(self) -> tuple:
        return ()   # the selection reads the gradients, not the labels

    def _traceable_bag_fn(self):
        """Fused-path hook: the same selection with a TRACED iteration
        index (fold_in accepts traced data; the warmup cutoff becomes a
        select). Weight streams match ``_bagging_weight`` exactly for
        equal ``it``."""
        warmup = int(1.0 / self.config.learning_rate)
        top_rate = float(self.config.top_rate)
        other_rate = float(self.config.other_rate)
        key0 = self._goss_key

        def bag_fn(it, grad, hess):
            key = jax.random.fold_in(key0, it)
            w = _goss_weights(grad, hess, key, top_rate=top_rate,
                              other_rate=other_rate)
            return jnp.where(it < warmup, jnp.ones_like(w), w)

        return bag_fn


# ----------------------------------------------------------------------
class DART(GBDT):
    """Dropout Additive Regression Trees (dart.hpp)."""

    def _setup_train(self, train_data, hist_method):
        super()._setup_train(train_data, hist_method)
        # the reference's exact LCG so drop sets (and thus whole DART
        # training trajectories) bit-match the reference CLI
        from ..utils.ref_random import RefRandom
        self._drop_rng = RefRandom(self.config.drop_seed)
        self._tree_weight: List[float] = []
        self._sum_weight = 0.0
        self._drop_index: List[int] = []

    # -- score arithmetic over all datasets ----------------------------
    def _add_tree_score(self, tree: Tree, tid: int, train: bool,
                        valid: bool) -> None:
        # jitted donated column adds (models/gbdt.py): one program per
        # update instead of an eager dispatch pair
        if train:
            tadd = tree.predict_binned_device(self.train_data.binned_device)
            self.train_score = _score_add_col(self.train_score, tadd,
                                              tid=tid)
        if valid:
            for i, vd in enumerate(self.valid_sets):
                vadd = tree.predict_binned_device(vd.binned_device)
                self.valid_scores[i] = _score_add_col(
                    self.valid_scores[i], vadd, tid=tid)

    def _dropping_trees(self) -> None:
        """DroppingTrees (dart.hpp:100-146)."""
        cfg = self.config
        self._drop_index = []
        if self._drop_rng.next_float() >= cfg.skip_drop:
            drop_rate = cfg.drop_rate
            if not cfg.uniform_drop and self._sum_weight > 0:
                inv_avg = len(self._tree_weight) / self._sum_weight
                if cfg.max_drop > 0:
                    drop_rate = min(
                        drop_rate, cfg.max_drop * inv_avg / self._sum_weight)
                for i in range(self.iter):
                    if self._drop_rng.next_float() < (
                            drop_rate * self._tree_weight[i] * inv_avg):
                        self._drop_index.append(i)
                        if len(self._drop_index) >= cfg.max_drop > 0:
                            break
            else:
                if cfg.max_drop > 0 and self.iter > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / self.iter)
                for i in range(self.iter):
                    if self._drop_rng.next_float() < drop_rate:
                        self._drop_index.append(i)
                        if len(self._drop_index) >= cfg.max_drop > 0:
                            break
        # remove dropped trees from the training score
        k = self.num_tree_per_iteration
        for i in self._drop_index:
            for tid in range(k):
                tree = self.models[i * k + tid]
                tree.shrink(-1.0)
                self._add_tree_score(tree, tid, train=True, valid=False)
                tree.shrink(-1.0)  # restore
        ndrop = len(self._drop_index)
        if not cfg.xgboost_dart_mode:
            self.shrinkage_rate = cfg.learning_rate / (1.0 + ndrop)
        else:
            self.shrinkage_rate = cfg.learning_rate if ndrop == 0 else \
                cfg.learning_rate / (cfg.learning_rate + ndrop)

    def _normalize(self) -> None:
        """Normalize (dart.hpp:148-196): dropped tree ends at k/(k+1)
        (or k/(k+lr) in xgboost mode) of its old weight; train and valid
        scores both end up consistent with the new weight."""
        cfg = self.config
        kdrop = float(len(self._drop_index))
        if kdrop == 0:
            return
        k = self.num_tree_per_iteration
        factor = kdrop / (kdrop + 1.0) if not cfg.xgboost_dart_mode \
            else kdrop / (kdrop + cfg.learning_rate)
        for i in self._drop_index:
            for tid in range(k):
                tree = self.models[i * k + tid]
                # valid kept full weight: subtract the (1 - factor) slice
                tree.shrink(-(1.0 - factor))
                self._add_tree_score(tree, tid, train=False, valid=True)
                # train had the tree fully removed: add back factor * tree
                tree.shrink(-factor / (1.0 - factor))
                self._add_tree_score(tree, tid, train=True, valid=False)
                # tree now carries factor * old weight — its final value
            if not cfg.uniform_drop:
                self._sum_weight -= self._tree_weight[i] * (1.0 - factor)
                self._tree_weight[i] *= factor
        # renormalized floats: keep host copies exact for model export
        for i in self._drop_index:
            for tid in range(k):
                self.models[i * k + tid].shrinkage = 1.0

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        self._dropping_trees()
        ret = super().train_one_iter(gradients, hessians)
        if ret:
            return ret
        self._normalize()
        if not self.config.uniform_drop:
            self._tree_weight.append(self.shrinkage_rate)
            self._sum_weight += self.shrinkage_rate
        return False

    def _eval_and_check_early_stopping(self) -> bool:
        # DART cannot early-stop: dropped-tree bookkeeping would be
        # inconsistent with a truncated model (dart.hpp:93-96)
        self.output_metric(self.iter)
        return False


# ----------------------------------------------------------------------
class RF(GBDT):
    """Random forest mode (rf.hpp): bagged trees on one-time gradients,
    averaged output, no shrinkage."""

    def __init__(self, config, train_data, objective=None,
                 hist_method: str = "auto"):
        cfg = config
        if not (cfg.bagging_freq > 0 and 0.0 < cfg.bagging_fraction < 1.0):
            log_fatal("RF mode requires bagging "
                      "(bagging_freq > 0 and bagging_fraction in (0,1))")
        if not (0.0 < cfg.feature_fraction <= 1.0):
            log_fatal("RF mode requires feature_fraction in (0, 1]")
        super().__init__(config, train_data, objective, hist_method)
        self.average_output = True
        self.shrinkage_rate = 1.0

    def _setup_train(self, train_data, hist_method):
        super()._setup_train(train_data, hist_method)
        if self._has_init_score:
            log_fatal("RF mode does not support init score")
        self._rf_boosting()

    def _rf_boosting(self) -> None:
        """One-time gradients from the constant boost-from-average score
        (rf.hpp:84-103)."""
        if self.objective is None:
            log_fatal("RF mode does not support custom objective "
                      "functions, please use built-in objectives")
        k = self.num_tree_per_iteration
        self._init_scores = [
            float(self.objective.boost_from_score(tid))
            if self.config.boost_from_average else 0.0 for tid in range(k)]
        tmp = jnp.tile(jnp.asarray(self._init_scores, jnp.float32)[None, :],
                       (self.num_data, 1))
        score = tmp if k > 1 else tmp[:, 0]
        g, h = self._grad_fn(score, *self._grad_operands)
        if k == 1:
            g, h = g[:, None], h[:, None]
        self._rf_grad, self._rf_hess = g, h

    def _multiply_scores(self, tid: int, val: float) -> None:
        self.train_score = self.train_score.at[:, tid].multiply(val)
        for i in range(len(self.valid_scores)):
            self.valid_scores[i] = \
                self.valid_scores[i].at[:, tid].multiply(val)

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        """rf.hpp:105-160: running-average score update."""
        if gradients is not None or hessians is not None:
            log_fatal("RF mode does not support custom objective gradients")
        k = self.num_tree_per_iteration
        bag = self._bagging_weight(self.iter, self._rf_grad, self._rf_hess)
        fmask = self._feature_mask()
        for tid in range(k):
            tree = None
            if self.class_need_train[tid] \
                    and self.train_data.num_features > 0:
                result = self.learner.train(
                    self._rf_grad[:, tid], self._rf_hess[:, tid],
                    bag_weight=bag, feature_mask=fmask)
                tree = self.learner.to_host_tree(result)
            if tree is not None and tree.num_leaves > 1:
                self._rf_renew(tree, result, tid)
                if abs(self._init_scores[tid]) > kEpsilon:
                    tree.add_bias(self._init_scores[tid])
                self._multiply_scores(tid, float(self.iter))
                self._update_scores(tree, result, tid)
                self._multiply_scores(tid, 1.0 / (self.iter + 1))
            else:
                output = 0.0
                if len(self.models) < k and not self.class_need_train[tid] \
                        and self.objective is not None:
                    output = float(self.objective.boost_from_score(tid))
                tree = _constant_tree(output)
                if len(self.models) < k:
                    self._multiply_scores(tid, float(self.iter))
                    self._update_scores(tree, result=None, tid=tid)
                    self._multiply_scores(tid, 1.0 / (self.iter + 1))
            self.models.append(tree)
        self.iter += 1
        return False

    def _rf_renew(self, tree: Tree, result, tid: int) -> None:
        """Leaf refit against residual (label - init_score), rf.hpp:125."""
        if self.objective is None or not getattr(
                self.objective, "is_renew_tree_output", False):
            return
        score = np.full(self.num_data, self._init_scores[tid], np.float64)
        leaf_id = jax.device_get(result.leaf_id)
        if self.bag_weight is not None:
            leaf_id = np.where(jax.device_get(self.bag_weight) > 0,
                               leaf_id, -1)
        new_vals = self.objective.renew_tree_output(
            score, leaf_id, tree.num_leaves, tree.leaf_value)
        if new_vals is not None:
            tree.leaf_value = np.asarray(new_vals,
                                         np.float64)[:tree.num_leaves]

    def _update_scores(self, tree: Tree, result, tid: int) -> None:
        if result is not None:
            super()._update_scores(tree, result, tid)
            return
        # constant tree: add to every row
        val = float(tree.leaf_value[0])
        self.train_score = self.train_score.at[:, tid].add(val)
        for i in range(len(self.valid_scores)):
            self.valid_scores[i] = self.valid_scores[i].at[:, tid].add(val)

    def rollback_one_iter(self) -> None:
        """rf.hpp:162-182."""
        if self.iter <= 0:
            return
        k = self.num_tree_per_iteration
        for tid in range(k):
            tree = self.models[-k + tid]
            tree.shrink(-1.0)
            self._multiply_scores(tid, float(self.iter))
            tadd = tree.predict_binned_device(self.train_data.binned_device)
            self.train_score = self.train_score.at[:, tid].add(tadd)
            for i, vd in enumerate(self.valid_sets):
                vadd = tree.predict_binned_device(vd.binned_device)
                self.valid_scores[i] = \
                    self.valid_scores[i].at[:, tid].add(vadd)
            if self.iter > 1:
                self._multiply_scores(tid, 1.0 / (self.iter - 1))
        del self.models[-k:]
        self.iter -= 1

    def predict_raw(self, data: np.ndarray,
                    num_iteration: int = -1) -> np.ndarray:
        raw = super().predict_raw(data, num_iteration)
        iters = self.num_iterations_trained if num_iteration < 0 \
            else min(num_iteration, self.num_iterations_trained)
        return raw / max(1, iters)


# ----------------------------------------------------------------------
_BOOSTING_CLASSES = {"gbdt": GBDT, "gbrt": GBDT, "dart": DART,
                     "goss": GOSS, "rf": RF, "random_forest": RF}


def create_boosting(config, train_data, objective=None,
                    hist_method: str = "auto") -> GBDT:
    """Boosting::CreateBoosting (src/boosting/boosting.cpp:35-68)."""
    cls = _BOOSTING_CLASSES.get(config.boosting)
    if cls is None:
        log_fatal(f"unknown boosting type {config.boosting}")
    return cls(config, train_data, objective, hist_method=hist_method)
