"""Multiclass objectives: softmax and one-vs-all.

Reference analog: ``src/objective/multiclass_objective.hpp:22-273``.
Score layout is ``[N, K]`` (the reference uses K contiguous blocks of N;
the 2-D layout is the TPU-native equivalent).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..utils.log import log_fatal
from .base import ObjectiveFunction
from .binary import BinaryLogloss

kEpsilon = 1e-15


class MulticlassSoftmax(ObjectiveFunction):
    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.num_model_per_iteration = self.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = self.label_np.astype(np.int32)
        if (lbl < 0).any() or (lbl >= self.num_class).any():
            log_fatal("Label must be in [0, num_class) for multiclass "
                      "objective")
        self._operands = {"label_int": jnp.asarray(lbl),
                          "weights": self.weights}
        w = np.ones(num_data) if self.weights is None \
            else np.asarray(self.weights_np, np.float64)
        probs = np.zeros(self.num_class)
        np.add.at(probs, lbl, w)
        self.class_init_probs = probs / w.sum()

    def _gradients(self, score, ops):
        # score [N, K]
        p = jax.nn.softmax(score, axis=-1)
        onehot = jax.nn.one_hot(ops["label_int"], self.num_class,
                                dtype=score.dtype)
        grad = p - onehot
        hess = 2.0 * p * (1.0 - p)
        return self._weighted(grad, hess, ops)

    def boost_from_score(self, class_id: int = 0) -> float:
        return float(np.log(max(kEpsilon,
                                self.class_init_probs[class_id])))

    def class_need_train(self, class_id: int) -> bool:
        p = self.class_init_probs[class_id]
        return not (abs(p) <= kEpsilon or abs(p) >= 1.0 - kEpsilon)

    def convert_output(self, score):
        return jax.nn.softmax(score, axis=-1)

    def name(self):
        return "multiclass"


class MulticlassOVA(ObjectiveFunction):
    """One-vs-all: K independent sigmoid binary objectives
    (multiclass_objective.hpp:200-273)."""

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.num_model_per_iteration = self.num_class
        self.sigmoid = float(config.sigmoid)
        self._binary = [
            BinaryLogloss(config, is_pos=_IsClass(k))
            for k in range(self.num_class)]

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        for b in self._binary:
            b.init(metadata, num_data)
        self._operands = {"classes": tuple(b._operands
                                           for b in self._binary)}

    def _gradients(self, score, ops):
        grads, hesses = [], []
        for k in range(self.num_class):
            g, h = self._binary[k].gradients(score[:, k],
                                             ops["classes"][k])
            grads.append(g)
            hesses.append(h)
        return jnp.stack(grads, axis=1), jnp.stack(hesses, axis=1)

    def boost_from_score(self, class_id: int = 0) -> float:
        return self._binary[class_id].boost_from_score(0)

    def class_need_train(self, class_id: int) -> bool:
        return self._binary[class_id].need_train

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * score))

    def name(self):
        return "multiclassova"


class _IsClass:
    def __init__(self, k: int):
        self.k = k

    def __call__(self, label):
        return np.abs(label - self.k) < 1e-6
