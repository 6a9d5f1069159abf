import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.learner.serial import SerialTreeLearner
from lightgbm_tpu.models.gbdt import GBDT


def _binary_problem(n=3000, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    logit = 2 * X[:, 0] - 1.5 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (logit + rng.randn(n) * 0.3 > 0).astype(np.float32)
    return X, y


def _regression_problem(n=3000, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (3 * X[:, 0] + np.sin(2 * X[:, 1]) + 0.5 * X[:, 2]
         + rng.randn(n) * 0.1).astype(np.float32)
    return X, y


def test_single_tree_partition_consistency():
    """leaf_id produced by training == predict_leaf_index_binned."""
    X, y = _binary_problem()
    cfg = Config.from_params({"objective": "binary", "num_leaves": 15})
    ds = Dataset.from_numpy(X, cfg, label=y)
    learner = SerialTreeLearner(ds, cfg)
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full(len(y), 0.25)
    result = learner.train(grad, hess)
    tree = learner.to_host_tree(result)
    assert tree.num_leaves > 1
    leaf_from_training = np.asarray(result.leaf_id)
    leaf_from_predict = tree.predict_leaf_index_binned(ds.binned)
    np.testing.assert_array_equal(leaf_from_training, leaf_from_predict)
    # raw-feature prediction agrees with bin-space prediction
    np.testing.assert_array_equal(tree.predict_leaf_index(X),
                                  leaf_from_predict)


def test_tree_respects_num_leaves_and_depth():
    X, y = _binary_problem()
    cfg = Config.from_params({"objective": "binary", "num_leaves": 8,
                              "max_depth": 3})
    ds = Dataset.from_numpy(X, cfg, label=y)
    learner = SerialTreeLearner(ds, cfg)
    result = learner.train(jnp.asarray(y - 0.5),
                           jnp.full(len(y), 0.25))
    tree = learner.to_host_tree(result)
    assert tree.num_leaves <= 8
    assert tree.leaf_depth.max() <= 3


def test_leaf_counts_sum_to_n():
    X, y = _binary_problem()
    cfg = Config.from_params({"objective": "binary", "num_leaves": 31})
    ds = Dataset.from_numpy(X, cfg, label=y)
    learner = SerialTreeLearner(ds, cfg)
    result = learner.train(jnp.asarray(y - 0.5), jnp.full(len(y), 0.25))
    tree = learner.to_host_tree(result)
    assert tree.leaf_count.sum() == len(y)
    counts = np.bincount(np.asarray(result.leaf_id),
                         minlength=tree.num_leaves)
    np.testing.assert_array_equal(counts[:tree.num_leaves],
                                  tree.leaf_count)
    assert (tree.leaf_count >= cfg.min_data_in_leaf).all()


def test_binary_end_to_end_auc():
    X, y = _binary_problem()
    Xv, yv = _binary_problem(seed=1)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 31, "learning_rate": 0.1,
        "metric": "auc", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    dv = ds.create_valid(Xv, label=yv)
    booster = GBDT(cfg, ds)
    booster.add_valid(dv, "valid_0")
    booster.train(30)
    auc = booster.evals_result["valid_0"]["auc"][-1]
    assert auc > 0.97
    # predictions are probabilities
    pred = booster.predict(Xv)
    assert (pred >= 0).all() and (pred <= 1).all()


def test_regression_end_to_end():
    X, y = _regression_problem()
    Xv, yv = _regression_problem(seed=1)
    cfg = Config.from_params({
        "objective": "regression", "num_leaves": 31, "metric": "l2",
        "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    dv = ds.create_valid(Xv, label=yv)
    booster = GBDT(cfg, ds)
    booster.add_valid(dv, "valid_0")
    booster.train(50)
    l2 = booster.evals_result["valid_0"]["l2"]
    assert l2[-1] < l2[0] * 0.2
    assert l2[-1] < 0.5


def test_multiclass_end_to_end():
    rng = np.random.RandomState(0)
    n = 2000
    X = rng.randn(n, 6)
    y = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int)
    cfg = Config.from_params({
        "objective": "multiclass", "num_class": 3, "num_leaves": 15,
        "metric": "multi_logloss", "verbosity": -1,
        "is_provide_training_metric": True})
    ds = Dataset.from_numpy(X, cfg, label=y.astype(np.float32))
    booster = GBDT(cfg, ds)
    booster.train(20)
    ll = booster.evals_result["training"]["multi_logloss"]
    assert ll[-1] < ll[0] * 0.5
    pred = booster.predict(X)
    assert pred.shape == (n, 3)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, atol=1e-5)
    acc = (pred.argmax(axis=1) == y).mean()
    assert acc > 0.9


def test_l1_objective_with_renewal():
    X, y = _regression_problem()
    cfg = Config.from_params({
        "objective": "regression_l1", "num_leaves": 15, "metric": "l1",
        "verbosity": -1, "is_provide_training_metric": True})
    ds = Dataset.from_numpy(X, cfg, label=y)
    booster = GBDT(cfg, ds)
    booster.train(30)
    l1 = booster.evals_result["training"]["l1"]
    assert l1[-1] < l1[0] * 0.3


def test_early_stopping():
    X, y = _binary_problem(n=800)
    Xv, yv = _binary_problem(n=400, seed=3)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 63, "learning_rate": 0.3,
        "metric": "binary_logloss", "early_stopping_round": 3,
        "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    dv = ds.create_valid(Xv, label=yv)
    booster = GBDT(cfg, ds)
    booster.add_valid(dv, "valid_0")
    booster.train(200)
    assert booster.num_iterations_trained < 200


def test_weights_affect_training():
    X, y = _binary_problem()
    cfg = Config.from_params({"objective": "binary", "num_leaves": 15,
                              "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    w = np.where(y > 0, 10.0, 1.0).astype(np.float32)
    dsw = Dataset.from_numpy(X, cfg, label=y, weight=w)
    b1 = GBDT(cfg, ds)
    b1.train(5)
    b2 = GBDT(cfg, dsw)
    b2.train(5)
    p1 = b1.predict(X).mean()
    p2 = b2.predict(X).mean()
    assert p2 > p1  # up-weighted positives push predictions up


def test_bagging_runs():
    X, y = _binary_problem()
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 15, "bagging_fraction": 0.5,
        "bagging_freq": 1, "verbosity": -1, "metric": "auc",
        "is_provide_training_metric": True})
    ds = Dataset.from_numpy(X, cfg, label=y)
    booster = GBDT(cfg, ds)
    booster.train(10)
    assert booster.evals_result["training"]["auc"][-1] > 0.9


def test_feature_fraction_runs():
    X, y = _binary_problem()
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 15, "feature_fraction": 0.5,
        "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    booster = GBDT(cfg, ds)
    booster.train(10)
    assert booster.num_iterations_trained == 10


def test_nan_features_train_and_predict():
    rng = np.random.RandomState(0)
    X, y = _binary_problem()
    X = X.copy()
    X[rng.rand(*X.shape) < 0.2] = np.nan
    cfg = Config.from_params({"objective": "binary", "num_leaves": 15,
                              "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    booster = GBDT(cfg, ds)
    booster.train(10)
    pred = booster.predict(X)
    assert np.isfinite(pred).all()
    # bin-space and raw-space prediction agree under NaN
    tree = booster.models[-1]
    np.testing.assert_array_equal(
        tree.predict_leaf_index(X),
        tree.predict_leaf_index_binned(ds.binned))


def test_custom_fobj():
    X, y = _regression_problem()
    cfg = Config.from_params({"objective": "custom", "num_leaves": 15,
                              "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    booster = GBDT(cfg, ds)
    for _ in range(10):
        score = np.asarray(booster.train_score[:, 0])
        grad = (score - y).astype(np.float32)
        hess = np.ones_like(grad)
        booster.train_one_iter(grad, hess)
    pred = booster.predict_raw(X)
    assert np.mean((pred - y) ** 2) < np.var(y) * 0.5


def test_monotone_constraints_enforced():
    """Predictions must be monotone in the constrained feature."""
    rng = np.random.RandomState(0)
    n = 3000
    X = rng.rand(n, 3)
    # non-monotone true relationship in feature 0
    y = (np.sin(4 * X[:, 0]) + X[:, 1] + rng.randn(n) * 0.05).astype(
        np.float32)
    cfg = Config.from_params({
        "objective": "regression", "num_leaves": 31,
        "monotone_constraints": "1,0,0", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    booster = GBDT(cfg, ds)
    booster.train(20)
    # sweep feature 0 holding others fixed
    sweep = np.tile(np.array([[0.0, 0.5, 0.5]]), (100, 1))
    sweep[:, 0] = np.linspace(0, 1, 100)
    pred = booster.predict(sweep)
    diffs = np.diff(pred)
    assert (diffs >= -1e-6).all(), f"violations: {diffs.min()}"
    # without the constraint the same sweep must be non-monotone
    cfg2 = Config.from_params({
        "objective": "regression", "num_leaves": 31, "verbosity": -1})
    ds2 = Dataset.from_numpy(X, cfg2, label=y)
    b2 = GBDT(cfg2, ds2)
    b2.train(20)
    assert (np.diff(b2.predict(sweep)) < -1e-6).any()


def test_custom_grad_reference_layout():
    """Flat [K*N] custom gradients (reference layout) are accepted."""
    rng = np.random.RandomState(0)
    n = 500
    X = rng.randn(n, 4)
    y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)
    cfg = Config.from_params({"objective": "custom", "num_class": 3,
                              "num_leaves": 7, "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y.astype(np.float32))
    booster = GBDT(cfg, ds)
    onehot = np.eye(3)[y]
    for _ in range(5):
        score = np.asarray(booster.train_score)  # [N, 3]
        e = np.exp(score - score.max(axis=1, keepdims=True))
        p = e / e.sum(axis=1, keepdims=True)
        grad = (p - onehot).T.ravel()  # [K*N] reference layout
        hess = (2 * p * (1 - p)).T.ravel()
        booster.train_one_iter(grad.astype(np.float32),
                               hess.astype(np.float32))
    pred = booster.predict_raw(X)
    assert pred.shape == (n, 3)
    acc = (pred.argmax(axis=1) == y).mean()
    assert acc > 0.8


def test_histogram_pool_bounded_matches_cached():
    """histogram_pool_size small enough to evict the cache switches the
    grow loops to rebuild-both-children mode; trees must match the
    cached mode (float association aside)."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner

    rng = np.random.RandomState(9)
    n = 1200
    X = rng.randn(n, 8)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.3 * rng.randn(n) > 0).astype(
        np.float32)
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((n,), 0.25, jnp.float32)

    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    cfg = Config.from_params(base)
    cfg_pool = Config.from_params(dict(base, histogram_pool_size=0.001))
    ds = Dataset.from_numpy(X, cfg, label=y)

    ref = SerialTreeLearner(ds, cfg)
    assert ref.cache_hists
    bounded = SerialTreeLearner(ds, cfg_pool)
    assert not bounded.cache_hists
    t_ref = ref.to_host_tree(ref.train(grad, hess))
    t_b = bounded.to_host_tree(bounded.train(grad, hess))
    assert t_b.num_leaves == t_ref.num_leaves
    np.testing.assert_array_equal(t_b.split_feature_inner,
                                  t_ref.split_feature_inner)
    np.testing.assert_allclose(t_b.leaf_value, t_ref.leaf_value,
                               rtol=2e-4, atol=2e-6)

    pb = PartitionedTreeLearner(ds, cfg_pool, interpret=True)
    assert not pb.cache_hists
    t_p = pb.to_host_tree(pb.train(grad, hess))
    assert t_p.num_leaves == t_ref.num_leaves
    np.testing.assert_array_equal(t_p.split_feature_inner,
                                  t_ref.split_feature_inner)


def test_profile_capture(tmp_path, monkeypatch):
    """LGBM_TPU_PROFILE_DIR arms the ONE-SHOT span-aligned capture
    window (observability/tracing.py ProfileWindow): the xprof trace
    covers a few steady-state iteration boundaries, and the host-side
    spans of the same run are in ``Telemetry.spans``."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.observability import tracing
    from lightgbm_tpu.observability.telemetry import get_telemetry
    # fresh window: the singleton is one-shot per process and another
    # test may have consumed it
    monkeypatch.setattr(tracing, "_PROFILE", tracing.ProfileWindow())
    monkeypatch.setenv("LGBM_TPU_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("LGBM_TPU_PROFILE_SKIP", "0")
    rng = np.random.RandomState(1)
    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(np.float32)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 5,
                              "num_iterations": 6, "verbosity": -1})
    tel = get_telemetry()
    tel.reset()
    tel.ensure_ring()
    try:
        booster = GBDT(cfg, Dataset.from_numpy(X, cfg, label=y))
        booster.train()
        spans = dict(tel.spans)
    finally:
        tel.reset()
    assert tracing.profile_window().state == "done"
    # a trace was written and the boosting span accumulated over the
    # same run
    import os
    found = [f for _, _, fs in os.walk(tmp_path) for f in fs]
    assert any(f.endswith((".pb", ".json.gz", ".xplane.pb"))
               for f in found), found
    assert spans["train/boosting"][1] > 0
    assert spans["train/boosting"][0] > 0


def test_histogram_pool_lru_matches_cached():
    """A bounded LRU pool (2 <= slots < num_leaves) with parent-slot
    reuse must reproduce the fully-cached trees (HistogramPool,
    serial_tree_learner.cpp:313-353): cached parents use the
    subtraction trick, evicted leaves rebuild both children."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner

    rng = np.random.RandomState(9)
    n = 1500
    X = rng.randn(n, 8)
    y = (X[:, 0] - 0.5 * X[:, 1] + X[:, 2] * X[:, 3]
         + 0.3 * rng.randn(n) > 0).astype(np.float32)
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((n,), 0.25, jnp.float32)

    base = {"objective": "binary", "num_leaves": 31,
            "min_data_in_leaf": 5, "verbosity": -1}
    cfg = Config.from_params(base)
    ds = Dataset.from_numpy(X, cfg, label=y)
    ref = SerialTreeLearner(ds, cfg)
    t_ref = ref.to_host_tree(ref.train(grad, hess))

    # slot = f*b*3*4 bytes; 0.1 MB -> a handful of slots, << 31 leaves
    cfg_pool = Config.from_params(dict(base, histogram_pool_size=0.1))
    pl = PartitionedTreeLearner(ds, cfg_pool, interpret=True)
    assert 2 <= pl.hist_slots < 31, pl.hist_slots
    t_p = pl.to_host_tree(pl.train(grad, hess))
    assert t_p.num_leaves == t_ref.num_leaves
    np.testing.assert_array_equal(t_p.split_feature_inner,
                                  t_ref.split_feature_inner)
    np.testing.assert_array_equal(t_p.threshold_bin, t_ref.threshold_bin)
    np.testing.assert_allclose(t_p.leaf_value, t_ref.leaf_value,
                               rtol=2e-4, atol=2e-6)
    # second tree reuses the donated matrices + pool state
    t_p2 = pl.to_host_tree(pl.train(grad, hess))
    np.testing.assert_array_equal(t_p2.split_feature_inner,
                                  t_ref.split_feature_inner)
