"""Binary log-loss objective.

Reference analog: ``src/objective/binary_objective.hpp:21-213``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..utils.log import log_fatal, log_info, log_warning
from .base import ObjectiveFunction

kEpsilon = 1e-15


class BinaryLogloss(ObjectiveFunction):
    need_accuracte_prediction = False
    rowwise_gradients = True

    def __init__(self, config: Config, is_pos=None):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0.0:
            log_fatal(f"Sigmoid parameter {self.sigmoid} should be greater "
                      "than zero")
        self.is_unbalance = bool(config.is_unbalance)
        self.scale_pos_weight = float(config.scale_pos_weight)
        if self.is_unbalance and abs(self.scale_pos_weight - 1.0) > 1e-6:
            log_fatal("Cannot set is_unbalance and scale_pos_weight at the "
                      "same time")
        self._is_pos = is_pos if is_pos is not None \
            else (lambda label: label > 0)
        self.need_train = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = self.label_np
        pos_mask = self._is_pos(lbl)
        cnt_positive = int(pos_mask.sum())
        cnt_negative = num_data - cnt_positive
        self.num_pos_data = cnt_positive
        self.need_train = cnt_positive > 0 and cnt_negative > 0
        if not self.need_train:
            log_warning("Contains only one class")
        log_info(f"Number of positive: {cnt_positive}, number of negative: "
                 f"{cnt_negative}")
        w_pos, w_neg = 1.0, 1.0
        if self.is_unbalance and cnt_positive > 0 and cnt_negative > 0:
            if cnt_positive > cnt_negative:
                w_neg = cnt_positive / cnt_negative
            else:
                w_pos = cnt_negative / cnt_positive
        w_pos *= self.scale_pos_weight
        # per-row ±1 label value and class weight
        pos = jnp.asarray(pos_mask)
        self._operands = {"label_val": jnp.where(pos, 1.0, -1.0),
                          "label_weight": jnp.where(pos, w_pos, w_neg),
                          "weights": self.weights}

    def _gradients(self, score, ops):
        if not self.need_train:
            return jnp.zeros_like(score), jnp.zeros_like(score)
        lv = ops["label_val"]
        response = -lv * self.sigmoid \
            / (1.0 + jnp.exp(lv * self.sigmoid * score))
        abs_resp = jnp.abs(response)
        grad = response * ops["label_weight"]
        hess = abs_resp * (self.sigmoid - abs_resp) * ops["label_weight"]
        return self._weighted(grad, hess, ops)

    def boost_from_score(self, class_id: int = 0) -> float:
        lbl = self.label_np
        pos = self._is_pos(lbl).astype(np.float64)
        if self.weights is not None:
            w = np.asarray(self.weights_np, np.float64)
            pavg = float((pos * w).sum() / w.sum())
        else:
            pavg = float(pos.mean())
        pavg = min(max(pavg, kEpsilon), 1.0 - kEpsilon)
        initscore = float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)
        log_info(f"[binary:BoostFromScore]: pavg={pavg:.6f} -> "
                 f"initscore={initscore:.6f}")
        return initscore

    def class_need_train(self, class_id: int = 0) -> bool:
        return self.need_train

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * score))

    def name(self):
        return "binary"
