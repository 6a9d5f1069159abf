"""Regression objective family.

Reference analog: ``src/objective/regression_objective.hpp`` (753 LoC).
Per-row OpenMP loops become vectorized jnp expressions. L1-type losses
(l1/quantile/mape) refit leaf outputs with (weighted) percentiles of
residuals (``RenewTreeOutput`` regression_objective.hpp:250-276,538-564,
637-657) — implemented as a per-leaf masked percentile in
``..ops.percentile``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..utils.log import log_fatal, log_warning
from .base import ObjectiveFunction


def _sign(x):
    return jnp.where(x > 0, 1.0, jnp.where(x < 0, -1.0, 0.0))


class RegressionL2Loss(ObjectiveFunction):
    """L2 loss (regression_objective.hpp:90-185)."""

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = bool(config.reg_sqrt)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.sqrt:
            lbl = self.label_np
            self.label = jnp.asarray(np.sign(lbl) * np.sqrt(np.abs(lbl)))
            import jax
            self.label_np = jax.device_get(self.label)
            self._operands["label"] = self.label

    @property
    def is_constant_hessian(self):
        return self.weights is None

    def _gradients(self, score, ops):
        grad = score - ops["label"]
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess, ops)

    def boost_from_score(self, class_id: int = 0) -> float:
        lbl = np.asarray(self.label_np, np.float64)
        if self.weights is not None:
            w = np.asarray(self.weights_np, np.float64)
            return float((lbl * w).sum() / w.sum())
        return float(lbl.mean())

    def convert_output(self, score):
        if self.sqrt:
            return jnp.sign(score) * score * score
        return score

    def name(self):
        return "regression"


class RegressionL1Loss(RegressionL2Loss):
    """L1 loss with median leaf refit (regression_objective.hpp:190-290)."""

    renew_alpha = 0.5
    is_renew_tree_output = True

    def _gradients(self, score, ops):
        diff = score - ops["label"]
        grad = _sign(diff)
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess, ops)

    def boost_from_score(self, class_id: int = 0) -> float:
        from ..ops.percentile import percentile_host
        return percentile_host(self.label_np,
                               self.weights_np, 0.5)

    def renew_tree_output(self, score, leaf_id, num_leaves, leaf_value):
        from ..ops.percentile import renew_leaf_outputs
        import jax
        residual = jax.device_get(self.label - score)
        return renew_leaf_outputs(residual, leaf_id, num_leaves,
                                  self.weights_np, self.renew_alpha)

    def name(self):
        return "regression_l1"


class RegressionHuberLoss(RegressionL2Loss):
    """Huber loss (regression_objective.hpp:296-400); alpha threshold."""

    def __init__(self, config: Config):
        super().__init__(config)
        self.alpha = float(config.alpha)
        if self.sqrt:
            log_warning("Cannot use sqrt transform in huber Regression, "
                        "will auto disable it")
            self.sqrt = False

    @property
    def is_constant_hessian(self):
        return self.weights is None

    def _gradients(self, score, ops):
        diff = score - ops["label"]
        grad = jnp.where(jnp.abs(diff) <= self.alpha, diff,
                         _sign(diff) * self.alpha)
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess, ops)

    def name(self):
        return "huber"


class RegressionFairLoss(RegressionL2Loss):
    """Fair loss (regression_objective.hpp:354-404)."""

    def __init__(self, config: Config):
        super().__init__(config)
        self.c = float(config.fair_c)
        self.sqrt = False

    @property
    def is_constant_hessian(self):
        return False

    def _gradients(self, score, ops):
        x = score - ops["label"]
        c = self.c
        grad = c * x / (jnp.abs(x) + c)
        hess = c * c / (jnp.abs(x) + c) ** 2
        return self._weighted(grad, hess, ops)

    def name(self):
        return "fair"


class RegressionPoissonLoss(RegressionL2Loss):
    """Poisson regression (regression_objective.hpp:407-478).

    score is log-rate; grad = exp(f) - y, hess = exp(f + max_delta_step).
    """

    def __init__(self, config: Config):
        super().__init__(config)
        self.max_delta_step = float(config.poisson_max_delta_step)
        self.sqrt = False

    def check_label(self):
        lbl = self.label_np
        if lbl.min(initial=0.0) < 0.0:
            log_fatal(f"[{self.name()}]: at least one target label is "
                      "negative")
        if lbl.sum() == 0.0:
            log_fatal(f"[{self.name()}]: sum of labels is zero")

    @property
    def is_constant_hessian(self):
        return False

    def _gradients(self, score, ops):
        grad = jnp.exp(score) - ops["label"]
        hess = jnp.exp(score + self.max_delta_step)
        return self._weighted(grad, hess, ops)

    def boost_from_score(self, class_id: int = 0) -> float:
        return float(np.log(max(RegressionL2Loss.boost_from_score(self),
                                1e-300)))

    def convert_output(self, score):
        return jnp.exp(score)

    def name(self):
        return "poisson"


class RegressionQuantileLoss(RegressionL2Loss):
    """Quantile (pinball) loss (regression_objective.hpp:483-596)."""

    is_renew_tree_output = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.alpha = float(config.alpha)
        if not 0.0 < self.alpha < 1.0:
            log_fatal("Quantile alpha should be in (0, 1)")

    @property
    def is_constant_hessian(self):
        return self.weights is None

    def _gradients(self, score, ops):
        delta = score - ops["label"]
        grad = jnp.where(delta >= 0, 1.0 - self.alpha, -self.alpha)
        hess = jnp.ones_like(score)
        return self._weighted(grad, hess, ops)

    def boost_from_score(self, class_id: int = 0) -> float:
        from ..ops.percentile import percentile_host
        return percentile_host(self.label_np,
                               self.weights_np, self.alpha)

    def renew_tree_output(self, score, leaf_id, num_leaves, leaf_value):
        from ..ops.percentile import renew_leaf_outputs
        import jax
        residual = jax.device_get(self.label - score)
        return renew_leaf_outputs(residual, leaf_id, num_leaves,
                                  self.weights_np, self.alpha)

    def name(self):
        return "quantile"


class RegressionMAPELoss(RegressionL1Loss):
    """MAPE loss (regression_objective.hpp:583-670): L1 scaled by
    1/max(1, |label|); weighted-median refits."""

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = self.label_np
        if np.abs(lbl).min(initial=1.0) <= 1.0:
            log_warning("Some label values are < 1 in absolute value. "
                        "MAPE is unstable with such values, so LightGBM "
                        "rounds them to 1.0 when computing MAPE.")
        w = np.ones_like(lbl) if self.weights is None \
            else self.weights_np
        # f32 host mirror: bit-identical to what np.asarray on the
        # device array used to fetch (jnp downcasts f64 -> f32)
        self._label_weight_np = np.asarray(
            1.0 / np.maximum(1.0, np.abs(lbl)) * w, np.float32)
        self._operands["label_weight"] = jnp.asarray(self._label_weight_np)

    @property
    def is_constant_hessian(self):
        return True

    def _gradients(self, score, ops):
        diff = score - ops["label"]
        grad = _sign(diff) * ops["label_weight"]
        w = ops["weights"]
        hess = jnp.ones_like(score) if w is None \
            else jnp.broadcast_to(w, score.shape)
        return grad, hess

    def boost_from_score(self, class_id: int = 0) -> float:
        from ..ops.percentile import percentile_host
        return percentile_host(self.label_np,
                               self._label_weight_np, 0.5)

    def renew_tree_output(self, score, leaf_id, num_leaves, leaf_value):
        from ..ops.percentile import renew_leaf_outputs
        import jax
        residual = jax.device_get(self.label - score)
        return renew_leaf_outputs(residual, leaf_id, num_leaves,
                                  self._label_weight_np, 0.5)

    def name(self):
        return "mape"


class RegressionGammaLoss(RegressionPoissonLoss):
    """Gamma regression (regression_objective.hpp:673-706)."""

    def _gradients(self, score, ops):
        y, w = ops["label"], ops["weights"]
        grad = 1.0 - y * jnp.exp(-score)
        hess = y * jnp.exp(-score)
        if w is not None:
            # reference applies the weight inside the label term only
            # (regression_objective.hpp:695-697)
            grad = 1.0 - y * jnp.exp(-score) * w
            hess = y * jnp.exp(-score) * w
        return grad, hess

    def name(self):
        return "gamma"


class RegressionTweedieLoss(RegressionPoissonLoss):
    """Tweedie regression (regression_objective.hpp:708-753)."""

    def __init__(self, config: Config):
        super().__init__(config)
        self.rho = float(config.tweedie_variance_power)

    def _gradients(self, score, ops):
        rho, y = self.rho, ops["label"]
        grad = -y * jnp.exp((1 - rho) * score) \
            + jnp.exp((2 - rho) * score)
        hess = -y * (1 - rho) * jnp.exp((1 - rho) * score) \
            + (2 - rho) * jnp.exp((2 - rho) * score)
        return self._weighted(grad, hess, ops)

    def name(self):
        return "tweedie"
