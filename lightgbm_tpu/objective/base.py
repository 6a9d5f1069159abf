"""Objective function interface + factory.

Reference analog: ``ObjectiveFunction``
(``include/LightGBM/objective_function.h:19-95``) and the factory
(``src/objective/objective_function.cpp:15-53``). Gradients/hessians are
computed as one vectorized JAX function of the score matrix — the per-row
loops of the reference collapse into array ops (jitted by the GBDT
driver).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..config import Config
from ..data.dataset import Metadata
from ..utils.log import log_fatal


class ObjectiveFunction:
    """Base objective. Subclasses override gradients() and friends."""

    #: number of models (trees) trained per boosting iteration
    num_model_per_iteration = 1
    is_constant_hessian = False
    is_renew_tree_output = False
    need_accuracte_prediction = True

    def __init__(self, config: Config):
        self.config = config
        self.num_data = 0
        self.label: Optional[jnp.ndarray] = None
        self.weights: Optional[jnp.ndarray] = None
        #: every device array ``gradients`` reads that is sized by the
        #: table, by name (``grad_operands``); a subclass that derives
        #: its own puts them here in its ``init``
        self._operands: dict = {}

    # -- ObjectiveFunction::Init (objective_function.h:29)
    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        if metadata.label is None:
            log_fatal("Label is required for training")
        self.label = jnp.asarray(metadata.label)
        self.weights = None if metadata.weights is None \
            else jnp.asarray(metadata.weights)
        # host mirrors, fetched ONCE and explicitly: the scattered
        # np.asarray(self.label) coercions the boost_from_score /
        # check_label paths used were implicit device->host transfers
        # that tripped the tier-1 transfer guard (graftlint GL105
        # class). Same bits as np.asarray on the device array.
        import jax
        self.label_np = jax.device_get(self.label)
        self.weights_np = None if self.weights is None \
            else jax.device_get(self.weights)
        self._operands = {"label": self.label, "weights": self.weights}
        self.check_label()

    def check_label(self) -> None:
        pass

    # -- GetGradients: score [N] or [N, K] -> (grad, hess) same shape
    def gradients(self, score: jnp.ndarray, ops: Optional[dict] = None):
        """``ops`` is what ``grad_operands()`` hands over; None reads
        the objective's own."""
        return self._gradients(score,
                               self._operands if ops is None else ops)

    def _gradients(self, score: jnp.ndarray, ops: dict):
        raise NotImplementedError

    def grad_operands(self) -> tuple:
        """What ``gradients`` takes after the score: every device array
        it reads that is sized by the table (labels, weights, a ranking
        objective's query layout), which a compiled program that holds
        it is handed as ARGUMENTS (the fused block, ``gbdt_grad``).
        Baked in as constants, they would make the program's text, and
        so its persistent-cache key, differ from table to table."""
        return (self._operands,)

    def setup_facts(self) -> dict:
        """Attributes of the ``lgbm.setup.objective`` span."""
        return {}

    # -- BoostFromScore(class_id) -> initial score (double)
    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    # -- ConvertOutput (raw score -> prediction space)
    def convert_output(self, score: jnp.ndarray) -> jnp.ndarray:
        return score

    # -- RenewTreeOutput: L1-family leaf refits; default no-op.
    # Returns new leaf values [num_leaves] or None.
    def renew_tree_output(self, score, leaf_id, num_leaves: int,
                          leaf_value):
        return None

    def name(self) -> str:
        raise NotImplementedError

    @staticmethod
    def _weighted(grad, hess, ops: dict):
        w = ops["weights"]
        if w is not None:
            if grad.ndim == 2:
                w = w[:, None]
            return grad * w, hess * w
        return grad, hess


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """Factory (objective_function.cpp:15-53)."""
    from . import binary, multiclass, rank, regression, xentropy
    name = config.objective
    table = {
        "regression": regression.RegressionL2Loss,
        "regression_l1": regression.RegressionL1Loss,
        "quantile": regression.RegressionQuantileLoss,
        "huber": regression.RegressionHuberLoss,
        "fair": regression.RegressionFairLoss,
        "poisson": regression.RegressionPoissonLoss,
        "mape": regression.RegressionMAPELoss,
        "gamma": regression.RegressionGammaLoss,
        "tweedie": regression.RegressionTweedieLoss,
        "binary": binary.BinaryLogloss,
        "multiclass": multiclass.MulticlassSoftmax,
        "multiclassova": multiclass.MulticlassOVA,
        "lambdarank": rank.LambdarankNDCG,
        "rank_xendcg": rank.RankXENDCG,
        "cross_entropy": xentropy.CrossEntropy,
        "cross_entropy_lambda": xentropy.CrossEntropyLambda,
    }
    if name in ("custom", "none", "null", "na"):
        return None
    if name not in table:
        log_fatal(f"Unknown objective type name: {name}")
    return table[name](config)
