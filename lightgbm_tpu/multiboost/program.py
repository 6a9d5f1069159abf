"""The batched grow program: B boosters' iterations as ONE jit.

``build_grow_program`` closes over ONE serial learner (the shared
binned matrix / bin layout) and ONE objective instance per objective
class (the gradient *code*), and vmaps the per-model iteration body
along the model axis:

    per model b:  grad/hess from the model's own label/weight slices
                  -> row weights (per-model bagging draw or fold mask)
                  -> grow_tree with the model's traced hyperparameters
                  -> score update (iterations >= 1)

Byte-identity contract with the serial path (models/gbdt.py): every
array op inside the vmapped body is the SAME op the unbatched booster
runs — elementwise gradients, sequential scatter-add histograms, the
[N, 3] root reduction, the threefry bagging draw keyed on the MODEL's
seed — and vmap preserves each slice's values bitwise, so model b of a
batch equals its unbatched twin byte-for-byte (pinned by the B=1/B=3
identity tests).

Two program boundaries, mirroring the booster's sync/async split:

* ``sync0=True`` (iteration 0): returns the raw trees + leaf ids and
  does NOT fold the leaf values into the score — the host pulls the
  trees, shrinks in f64 (``Tree.shrink``) exactly like
  ``train_one_iter``, and applies :func:`mb_score_add` with the
  rounded-back f32 leaf values.
* ``sync0=False`` (iterations >= 1): the async formula — the score
  moves by ``f32(leaf) * f32(lr)`` gathered at the grow partition,
  ``where(ok, lr, 0)`` masking no-split models, identical to
  ``_train_one_iter_async``.

The objective's operands (``grad_operands``: label / weights /
binary's label_val / label_weight) are stacked along the model axis and
handed to ``gradients`` as the model's slice — ``gradients`` is
elementwise in them for every whitelisted objective.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..utils.jit_registry import register_dynamic, register_jit

class HyperBatch(NamedTuple):
    """Per-model hyperparameter axes that trace cleanly — one [B]
    array per axis. Everything else (num_leaves, max_bin, objective,
    bagging_freq, ...) is shape- or code-affecting and buckets
    (batch.py) instead of vmapping."""
    learning_rate: object            # f32 [B]
    lambda_l1: object                # f32 [B]
    lambda_l2: object                # f32 [B]
    max_delta_step: object           # f32 [B]
    min_data_in_leaf: object         # f32 [B]
    min_sum_hessian_in_leaf: object  # f32 [B]
    min_gain_to_split: object        # f32 [B]
    bagging_fraction: object         # f32 [B]
    init_score: object               # f32 [B] boost_from_average
    bag_key: object                  # u32 [B, 2] PRNGKey(model seed)


@register_jit("multiboost_score_add", donate=(0,))
@functools.partial(jax.jit, donate_argnums=(0,))
def mb_score_add(score, leaf_vals, leaf_id):
    """Batched analog of ``_score_add_leaf`` for the sync iteration:
    per-model gather of the HOST-shrunk (f64 -> f32) leaf values at
    the grow partition, added to the donated [B, N] score. A no-split
    model's row is filled with its constant output, so the gather adds
    the constant to every row regardless of leaf ids."""
    return score + jnp.take_along_axis(leaf_vals, leaf_id, axis=1)


def build_grow_program(learner, objective, *, use_bagging: bool,
                       bagging_freq: int, has_mask: bool,
                       traced_fields: tuple = ()):
    """One jitted iteration over B models; see module docstring.

    ``learner`` is the bucket's SerialTreeLearner on the SHARED
    dataset; ``objective`` the template instance whose ``gradients``
    is traced with the model's slice of the stacked operands
    (``attrs``, the pytree of ``grad_operands()`` with a model axis).

    ``traced_fields`` names the SplitParams numerics that VARY across
    the bucket and therefore enter the grow graph as traced per-model
    scalars. Fields uniform across the bucket stay static python
    floats — XLA constant-folds them exactly like the unbatched twin,
    which keeps even the recorded ``split_gain`` ulps byte-identical.
    (Traced numerics shift FMA/folding decisions; varying them trades
    last-ulp gain determinism, never split choices' correctness.)

    Returns the registered jit with signature
    ``fn(score, it, attrs, masks, hyp, *, sync0)`` ->
    ``(score, trees, leaf_id, ok)`` when ``sync0`` else
    ``(score, trees, ok)``.
    """
    from ..learner.serial import grow_tree
    from ..learner.split_step import split_fusion_default
    from ..models.gbdt import _bag_mask_core

    binned = learner.binned
    n = int(binned.shape[0])
    base_params = learner.params
    statics = dict(
        meta=learner.meta, num_leaves=learner.num_leaves,
        max_depth=learner.max_depth, num_bins_max=learner.num_bins_max,
        hist_method=learner.hist_method, bundled=learner.bundled,
        cache_hists=learner.cache_hists, mv_slots=learner.mv_slots,
        mv_groups=learner.mv_groups, has_monotone=learner.has_monotone,
        split_fusion=split_fusion_default())
    ones_rows = learner._ones_rows
    all_features = learner._all_features
    freq = int(max(bagging_freq, 1))

    def _per_model(score_b, attrs_b, mask_b, hyp_b, it):
        grad, hess = objective.gradients(score_b, attrs_b)
        if use_bagging:
            bag = _bag_mask_core(hyp_b.bag_key, it, None, freq=freq,
                                 n=n, frac=hyp_b.bagging_fraction,
                                 pos_frac=1.0, neg_frac=1.0)
        elif has_mask:
            bag = mask_b
        else:
            bag = ones_rows
        params_b = base_params._replace(
            **{f: getattr(hyp_b, f) for f in traced_fields}) \
            if traced_fields else base_params
        res = grow_tree(binned, grad, hess, bag, all_features,
                        params=params_b, rand_key=None, **statics)
        ok = res.tree.num_leaves > 1
        return res.tree, res.leaf_id, ok

    def _batched(score, it, attrs, masks, hyp, *, sync0: bool):
        if sync0:
            score = score + hyp.init_score[:, None]
        mask_ax = 0 if has_mask else None
        trees, leaf_id, ok = jax.vmap(
            _per_model, in_axes=(0, 0, mask_ax, 0, None))(
                score, attrs, masks, hyp, it)
        if sync0:
            # host pulls the trees, f64-shrinks, then mb_score_add
            return score, trees, leaf_id, ok
        scale = jnp.where(ok, hyp.learning_rate.astype(jnp.float32),
                          jnp.float32(0.0))
        adds = trees.leaf_value * scale[:, None]
        score = score + jnp.take_along_axis(adds, leaf_id, axis=1)
        return score, trees, ok

    return register_dynamic(
        "multiboost_grow",
        jax.jit(_batched, static_argnames=("sync0",),
                donate_argnums=(0,)),
        donate=(0,))


__all__ = ["HyperBatch", "build_grow_program", "mb_score_add"]
