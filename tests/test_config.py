import os

import pytest

from lightgbm_tpu.config import Config


def test_defaults():
    cfg = Config()
    assert cfg.num_leaves == 31
    assert cfg.learning_rate == 0.1
    assert cfg.max_bin == 255
    assert cfg.objective == "regression"
    assert cfg.boosting == "gbdt"


def test_aliases():
    cfg = Config.from_params({
        "n_estimators": 50, "eta": "0.3", "num_leaf": 15,
        "min_child_samples": 5, "colsample_bytree": 0.8,
        "reg_alpha": 1.5, "reg_lambda": 2.0, "subsample": 0.9,
        "random_state": 42, "application": "binary",
    })
    assert cfg.num_iterations == 50
    assert cfg.learning_rate == 0.3
    assert cfg.num_leaves == 15
    assert cfg.min_data_in_leaf == 5
    assert cfg.feature_fraction == 0.8
    assert cfg.lambda_l1 == 1.5
    assert cfg.lambda_l2 == 2.0
    assert cfg.bagging_fraction == 0.9
    assert cfg.seed == 42
    assert cfg.objective == "binary"


def test_objective_aliases():
    assert Config.from_params({"objective": "mse"}).objective == "regression"
    assert Config.from_params({"objective": "mae"}).objective \
        == "regression_l1"
    assert Config.from_params(
        {"objective": "xentropy"}).objective == "cross_entropy"


def test_bool_and_list_parse():
    cfg = Config.from_params({
        "is_unbalance": "true", "metric": "auc,binary_logloss",
        "eval_at": "1,3,5", "monotone_constraints": "1,-1,0",
    })
    assert cfg.is_unbalance is True
    assert cfg.metric == ["auc", "binary_logloss"]
    assert cfg.eval_at == [1, 3, 5]
    assert cfg.monotone_constraints == [1, -1, 0]


def test_max_depth_caps_leaves():
    cfg = Config.from_params({"max_depth": 3})
    assert cfg.num_leaves == 8
    cfg = Config.from_params({"max_depth": 3, "num_leaves": 6})
    assert cfg.num_leaves == 6


def test_rf_requires_bagging():
    with pytest.raises(ValueError):
        Config.from_params({"boosting": "rf"})
    cfg = Config.from_params(
        {"boosting": "rf", "bagging_freq": 1, "bagging_fraction": 0.8})
    assert cfg.boosting == "rf"


def test_metric_resolution():
    assert Config.from_params({"objective": "binary"}).resolved_metrics() \
        == ["binary_logloss"]
    cfg = Config.from_params({"objective": "binary", "metric": "auc"})
    assert cfg.resolved_metrics() == ["auc"]
    cfg = Config.from_params({"metric": ["l2", "mse", "rmse"]})
    assert cfg.resolved_metrics() == ["l2", "rmse"]


def test_num_class_validation():
    with pytest.raises(ValueError):
        Config.from_params({"objective": "multiclass"})
    cfg = Config.from_params({"objective": "multiclass", "num_class": 3})
    assert cfg.num_tree_per_iteration() == 3


def test_params_doc_in_sync():
    """docs/Parameters.md is generated from the Config dataclass; the
    committed file must match (the reference CI's parameter-docs
    consistency check, .ci/test.sh:34-39)."""
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "gen_params_doc.py"),
         "--check"],
        capture_output=True, text=True, env=env)
    assert res.returncode == 0, res.stderr


def test_master_seed_derives_sub_seeds():
    """`seed` (alias random_state) derives every sub-seed not set
    explicitly (Config::Set, src/io/config.cpp:187-196)."""
    a = Config.from_params({"seed": 42})
    b = Config.from_params({"random_state": 42})
    c = Config.from_params({"seed": 43})
    d = Config.from_params({})
    subs = ("data_random_seed", "bagging_seed", "drop_seed",
            "feature_fraction_seed", "objective_seed", "extra_seed")
    for s in subs:
        assert getattr(a, s) == getattr(b, s)      # alias equivalent
    assert any(getattr(a, s) != getattr(c, s) for s in subs)
    assert any(getattr(a, s) != getattr(d, s) for s in subs)
    # explicit sub-seed wins over derivation
    e = Config.from_params({"seed": 42, "bagging_seed": 777})
    assert e.bagging_seed == 777
    assert e.data_random_seed == a.data_random_seed
    # EXACT values the reference CLI derives for seed=42 (read from a
    # reference model dump's parameters section)
    ref = {"data_random_seed": 175, "bagging_seed": 400,
           "drop_seed": 17869, "feature_fraction_seed": 30056,
           "objective_seed": 16083, "extra_seed": 12879}
    for s, want in ref.items():
        assert getattr(a, s) == want, (s, getattr(a, s), want)


def test_master_seed_changes_bagged_training():
    """Different random_state values produce different bagged models —
    the sklearn-style determinism contract."""
    import numpy as np

    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(800, 5)
    y = (X[:, 0] > 0).astype(float)
    def train(seed):
        return lgb.train({"objective": "binary", "bagging_fraction": 0.5,
                          "bagging_freq": 1, "num_leaves": 15,
                          "random_state": seed, "verbosity": -1},
                         lgb.Dataset(X, label=y),
                         num_boost_round=5).predict(X)
    p1, p1b, p2 = train(1), train(1), train(2)
    np.testing.assert_array_equal(p1, p1b)         # reproducible
    assert not np.array_equal(p1, p2)              # seed matters


def test_is_parallel_find_bin_derivation():
    """config.cpp:283-295: data/voting learners derive
    is_parallel_find_bin=true; the data learner also drops an enabled
    histogram LRU pool to avoid per-shard refetch communication."""
    from lightgbm_tpu.config import Config
    base = {"objective": "binary", "verbosity": -1, "num_machines": 2,
            "machines": "127.0.0.1:121,127.0.0.1:122"}
    assert Config.from_params(
        {**base, "tree_learner": "data"}).is_parallel_find_bin
    assert Config.from_params(
        {**base, "tree_learner": "voting"}).is_parallel_find_bin
    assert not Config.from_params(
        {**base, "tree_learner": "feature"}).is_parallel_find_bin
    assert not Config.from_params(
        {"objective": "binary", "verbosity": -1}).is_parallel_find_bin
    cfg = Config.from_params({**base, "tree_learner": "data",
                              "histogram_pool_size": 512.0})
    assert cfg.histogram_pool_size == -1
    cfg = Config.from_params({**base, "tree_learner": "voting",
                              "histogram_pool_size": 512.0})
    assert cfg.histogram_pool_size == 512.0
