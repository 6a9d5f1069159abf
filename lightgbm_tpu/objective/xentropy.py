"""Cross-entropy objectives for probabilistic labels in [0, 1].

Reference analog: ``src/objective/xentropy_objective.hpp`` (275 LoC).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..utils.log import log_fatal, log_info
from .base import ObjectiveFunction

kEpsilon = 1e-15


def _check_interval(label, name):
    lbl = np.asarray(label)
    if (lbl < 0.0).any() or (lbl > 1.0).any():
        log_fatal(f"[{name}]: label must be in [0, 1] interval")


class CrossEntropy(ObjectiveFunction):
    """Straight cross-entropy (xentropy_objective.hpp:38-140)."""

    rowwise_gradients = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        _check_interval(self.label_np, self.name())
        if self.weights is not None:
            w = self.weights_np
            if w.min() <= 0.0:
                log_fatal(f"[{self.name()}]: at least one weight is "
                          "non-positive")

    def _gradients(self, score, ops):
        z = 1.0 / (1.0 + jnp.exp(-score))
        grad = z - ops["label"]
        hess = z * (1.0 - z)
        return self._weighted(grad, hess, ops)

    def boost_from_score(self, class_id: int = 0) -> float:
        lbl = np.asarray(self.label_np, np.float64)
        if self.weights is not None:
            w = np.asarray(self.weights_np, np.float64)
            pavg = float((lbl * w).sum() / w.sum())
        else:
            pavg = float(lbl.mean())
        pavg = min(max(pavg, kEpsilon), 1.0 - kEpsilon)
        init = float(np.log(pavg / (1.0 - pavg)))
        log_info(f"[{self.name()}:BoostFromScore]: pavg = {pavg:.6f} -> "
                 f"initscore = {init:.6f}")
        return init

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-score))

    def name(self):
        return "cross_entropy"


class CrossEntropyLambda(ObjectiveFunction):
    """Alternative parameterization with weight-as-trials
    (xentropy_objective.hpp:146-275)."""

    rowwise_gradients = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        _check_interval(self.label_np, self.name())
        if self.weights is not None:
            w = self.weights_np
            if w.min() <= 0.0:
                log_fatal(f"[{self.name()}]: at least one weight is "
                          "non-positive")

    def _gradients(self, score, ops):
        w, y = ops["weights"], ops["label"]
        if w is None:
            z = 1.0 / (1.0 + jnp.exp(-score))
            return z - y, z * (1.0 - z)
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-w * hhat)
        enf = jnp.exp(-score)
        grad = (1.0 - y / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - z)
        d = 1.0 + epf
        a = w * epf / (d * d)
        d2 = c - 1.0
        bb = (c / (d2 * d2)) * (1.0 + w * epf - c)
        hess = a * (1.0 + y * bb)
        return grad, hess

    def boost_from_score(self, class_id: int = 0) -> float:
        lbl = np.asarray(self.label_np, np.float64)
        if self.weights is not None:
            w = np.asarray(self.weights_np, np.float64)
            havg = float((lbl * w).sum() / w.sum())
        else:
            havg = float(lbl.mean())
        init = float(np.log(np.expm1(max(havg, kEpsilon))
                            if havg > 0 else kEpsilon))
        log_info(f"[{self.name()}:BoostFromScore]: havg = {havg:.6f} -> "
                 f"initscore = {init:.6f}")
        return init

    def convert_output(self, score):
        # output is the normalized exponential parameter lambda > 0
        return jnp.log1p(jnp.exp(score))

    def name(self):
        return "cross_entropy_lambda"
