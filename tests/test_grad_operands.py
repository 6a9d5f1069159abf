"""Every array a training program reads from its table is an argument.

Two tables of one shape lower ``gbdt_grad`` and ``gbdt_fused_block``
to the same text, so the persistent compile cache that holds the first
table's programs serves the second's: the labels, weights and query
layout reach the programs as ``ObjectiveFunction.grad_operands()``,
the per-feature metadata as the learner's ``grow_operands()``, the
valid sets and balanced bagging's labels as arguments of the fused
block and of ``gbdt_grad_bag``.
Lowered here for the TPU, under the plan the chip takes for each
table (megakernel, categorical, bundled, wide), with the compiled
kernels' Mosaic calls in place of their interpret twins.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu.learner.partitioned as partitioned
import lightgbm_tpu.learner.split_step as split_step
from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.data.dataset import Metadata
from lightgbm_tpu.models.variants import create_boosting
from lightgbm_tpu.objective.base import create_objective

ROWS = 512

# a dense literal: ``dense<[...]>`` or ``dense<"0x...">``, not a splat
_LITERAL = re.compile(
    r'stablehlo\.constant dense<([\["][^>]*)> : tensor<([0-9x]+)x[a-z0-9]+>')


def _labels(objective, rng, n):
    if objective == "multiclass":
        return rng.randint(0, 3, n).astype(np.float64)
    if objective == "cross_entropy":
        return rng.uniform(0.0, 1.0, n)
    if objective == "lambdarank":
        return rng.randint(0, 5, n).astype(np.float64)
    if objective == "regression":
        return rng.randn(n)
    return (rng.randn(n) > 0).astype(np.float64)


def _table(plan, seed):
    """``(x, params)`` of one table of ``plan``'s shape; the values,
    bins, defaults and most frequent bins come from ``seed``."""
    rng = np.random.RandomState(seed)
    if plan == "categorical":
        x = rng.randn(ROWS, 16).astype(np.float32)
        x[:, :4] = rng.randint(0, 9, (ROWS, 4))
        return x, {"categorical_feature": [0, 1, 2, 3]}
    if plan == "bundled":
        import scipy.sparse as sp
        cols = [rng.randn(ROWS, 2)]
        for card in (6, 9):
            hot = np.zeros((ROWS, card))
            hot[np.arange(ROWS), rng.randint(0, card, ROWS)] = 1.0
            cols.append(hot)
        return sp.csr_matrix(np.hstack(cols)), {
            "min_data_in_bin": 1, "feature_pre_filter": False}
    width = 200 if plan == "wide" else 8
    return rng.randn(ROWS, width).astype(np.float32), {}


#: what a case adds to the booster: a valid set, balanced bagging (its
#: labels an operand of the sampling), GOSS (it reads the gradients)
VARIANTS = {
    "": {},
    "valid": {},
    "balanced_bagging": {"bagging_freq": 1, "pos_bagging_fraction": 0.5,
                         "neg_bagging_fraction": 0.8},
    "goss": {"boosting": "goss"},
}


def _booster(objective, plan, seed, variant=""):
    rng = np.random.RandomState(1000 + seed)
    x, extra = _table(plan, seed)
    cats = extra.pop("categorical_feature", None)
    params = {"objective": objective, "num_leaves": 7, "verbosity": -1,
              "tree_learner": "partitioned", "min_data_in_leaf": 5,
              **extra, **VARIANTS[variant]}
    if objective == "multiclass":
        params["num_class"] = 3
    cfg = Config.from_params(params)
    kw = {"label": _labels(objective, rng, ROWS),
          "weight": rng.uniform(0.5, 2.0, ROWS)}
    if objective == "lambdarank":
        kw["group"] = [ROWS // 8] * 8
    if cats:
        kw["categorical_features"] = cats
    ds = Dataset.from_scipy(x, cfg, **kw) if plan == "bundled" \
        else Dataset.from_numpy(x, cfg, **kw)
    g = create_boosting(cfg, ds)
    if variant == "valid":
        xv, _ = _table(plan, seed + 100)
        g.add_valid(ds.create_valid(
            xv, label=_labels(objective, rng, ROWS)), "valid_0")
    return g


def _lowered(g):
    """The texts of ``gbdt_grad``, ``gbdt_fused_block`` and, where the
    sampling runs beside the gradients, ``gbdt_grad_bag``, as the
    booster hands them their arguments, lowered for the TPU."""
    k = g.num_tree_per_iteration
    score = g.train_score if k > 1 else g.train_score[:, 0]
    texts = {"gbdt_grad": g._grad_fn.trace(score, *g._grad_operands),
             "gbdt_fused_block": g._fused_block().trace(
                 *g._fused_block_args(), m=2)}
    if g._grad_hess_bag(score, 0)[2] is not None:
        texts["gbdt_grad_bag"] = g._grad_bag_jit.trace(
            score, jnp.int32(0), g._grad_operands, g._bag_operands())
    return {name: traced.lower(lowering_platforms=("tpu",)).as_text()
            for name, traced in texts.items()}


@pytest.fixture
def chip_plan(monkeypatch):
    """The chip's plan and compiled kernels, for lowering alone."""
    monkeypatch.setattr(split_step, "on_tpu", lambda: True)
    monkeypatch.setattr(partitioned, "on_tpu", lambda: True)


CASES = [("binary", plan, "") for plan in
         ("megakernel", "categorical", "bundled", "wide")] + \
    [(obj, "megakernel", "") for obj in
     ("regression", "multiclass", "cross_entropy", "lambdarank")] + \
    [("binary", "megakernel", variant)
     for variant in ("valid", "balanced_bagging", "goss")]


@pytest.mark.parametrize("objective,plan,variant", CASES,
                         ids=["-".join(filter(None, c)) for c in CASES])
def test_two_tables_of_one_shape_lower_to_one_program(chip_plan,
                                                      objective, plan,
                                                      variant):
    first, second = (_booster(objective, plan, seed, variant)
                     for seed in (1, 2))
    got_plan = first.learner.split_plan()
    assert got_plan == second.learner.split_plan()
    assert got_plan.body == ("megakernel" if plan == "megakernel"
                             else "per_phase")
    assert got_plan.wide == (plan == "wide")
    assert got_plan.cat_scan == (plan == "categorical")
    assert first.learner.bundled == (plan == "bundled")
    # the tables differ where the programs would read them
    assert not np.array_equal(first.objective.label_np,
                              second.objective.label_np)
    a, b = _lowered(first), _lowered(second)
    assert ("gbdt_grad_bag" in a) == (variant == "balanced_bagging")
    assert a.keys() == b.keys()
    for name, text_a in a.items():
        assert text_a == b[name], name
        for value, dims in _LITERAL.findall(text_a):
            size = int(np.prod([int(d) for d in dims.split("x")]))
            assert size < ROWS, (name, dims, value[:80])


def _metadata(name, rng, n, weighted):
    md = Metadata(n)
    if name in ("multiclass", "multiclassova"):
        label = rng.randint(0, 3, n)
    elif name in ("cross_entropy", "cross_entropy_lambda"):
        label = rng.uniform(0.0, 1.0, n)
    elif name == "lambdarank":
        label = rng.randint(0, 5, n)
    elif name in ("poisson", "gamma", "tweedie"):
        label = rng.gamma(2.0, 1.0, n)
    elif name == "binary":
        label = rng.randint(0, 2, n)
    else:
        label = rng.randn(n) * 3.0
    md.set_label(np.asarray(label, np.float32))
    if weighted:
        md.set_weights(rng.uniform(0.5, 2.0, n).astype(np.float32))
    if name == "lambdarank":
        md.set_query([16, 9, 30, 7, 2])
    return md


JITTABLE = [
    ("regression", {}), ("regression", {"reg_sqrt": True}),
    ("regression_l1", {}), ("quantile", {}), ("huber", {}),
    ("fair", {}), ("poisson", {}), ("mape", {}), ("gamma", {}),
    ("tweedie", {}), ("binary", {}), ("binary", {"is_unbalance": True}),
    ("multiclass", {"num_class": 3}), ("multiclassova", {"num_class": 3}),
    ("cross_entropy", {}), ("cross_entropy_lambda", {}),
    ("lambdarank", {})]


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize(
    "name,extra", JITTABLE,
    ids=[n + "".join(f"-{k}" for k in e) for n, e in JITTABLE])
def test_gradients_from_operands_equal_the_objectives_own(name, extra,
                                                          weighted):
    """``gradients(score)`` reads the objective's own operands,
    ``gradients(score, *grad_operands())`` the ones it is handed: the
    same arrays, so the two agree bit for bit. Compiled, the first
    bakes them in as constants, as every program did before they were
    arguments; without weights the two programs agree bit for bit too.
    With weights XLA may fold the product of two constant arrays (a
    weight and gamma's label, or ``is_unbalance``'s class weight) ahead
    of the multiply by the score, which rounds once in another place:
    within an ulp or two."""
    n = 64
    rng = np.random.RandomState(7)
    obj = create_objective(Config.from_params(
        {"objective": name, "verbosity": -1, **extra}))
    assert getattr(obj, "jittable", True)
    obj.init(_metadata(name, rng, n, weighted), n)
    k = obj.num_model_per_iteration
    score = jnp.asarray(rng.randn(n, k) if k > 1 else rng.randn(n),
                        jnp.float32)
    ops = obj.grad_operands()
    assert ops and max(a.shape[0] for a in jax.tree.leaves(ops)) == n
    for a, b in zip(obj.gradients(score), obj.gradients(score, *ops)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    own = jax.jit(obj.gradients)(score)
    handed = jax.jit(obj.gradients)(score, *ops)
    for a, b in zip(own, handed):
        if weighted:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
