"""Multi-val (row-wise CSR) device path for extreme-sparse features
(VERDICT r3 #5): features whose combined conflicts overflow the
shared-column budget ride a padded slot matrix instead of dense
columns (multi_val_sparse_bin.hpp:26, dataset.cpp:186-231,1170-1273)."""

import numpy as np
import scipy.sparse as sp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.learner.serial import SerialTreeLearner


def _bosch_like(n=2500, f=300, density=0.04, seed=3):
    """>=95% sparse, conflicting nonzeros -> no exclusive bundles.
    300 columns: sparse groups that fit the training matrix's 128-byte
    row (up to 112 columns) stay physical byte columns, which cost the
    chip nothing extra; multi-val is for a set that would widen the
    row, as here."""
    rng = np.random.RandomState(seed)
    X = np.where(rng.rand(n, f) < density,
                 rng.randint(1, 9, size=(n, f)) * 0.5, 0.0)
    logit = (3.0 * X[:, 0] - 2.0 * X[:, 1] + X[:, 2] - X[:, 3]
             + 0.5 * X[:, 4])
    y = (logit + 0.3 * rng.randn(n) > 0.2).astype(np.float32)
    return X, y


def test_bosch_shape_goes_multival():
    X, y = _bosch_like()
    cfg = Config.from_params({"objective": "binary", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    assert ds.has_multival
    # the dense matrix collapses to (almost) nothing
    assert ds.binned.shape[1] < X.shape[1] // 4
    assert ds.mv_slots.shape[0] == len(y)
    # slot count ~ max nonzeros per row, far below F
    assert ds.mv_slots.shape[1] < X.shape[1] // 4
    assert ds.num_groups > ds.num_dense_groups


def test_multival_matches_dense_training():
    """Same data, multi-val vs dense (enable_bundle=false) must grow
    the same trees — the histograms are mathematically identical."""
    import jax.numpy as jnp
    X, y = _bosch_like()
    cfg_mv = Config.from_params({"objective": "binary", "num_leaves": 31,
                                 "min_data_in_leaf": 5, "verbosity": -1})
    cfg_dense = Config.from_params({
        "objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
        "enable_bundle": False, "verbosity": -1})
    ds_mv = Dataset.from_numpy(X, cfg_mv, label=y)
    ds_dense = Dataset.from_numpy(X, cfg_dense, label=y)
    assert ds_mv.has_multival and not ds_dense.has_multival

    g = jnp.asarray(y - 0.5)
    h = jnp.full(len(y), 0.25)
    t_mv = SerialTreeLearner(ds_mv, cfg_mv)
    t_d = SerialTreeLearner(ds_dense, cfg_dense)
    tree_mv = t_mv.to_host_tree(t_mv.train(g, h))
    tree_d = t_d.to_host_tree(t_d.train(g, h))
    assert tree_mv.num_leaves == tree_d.num_leaves
    np.testing.assert_array_equal(tree_mv.split_feature_inner,
                                  tree_d.split_feature_inner)
    np.testing.assert_array_equal(tree_mv.threshold_bin,
                                  tree_d.threshold_bin)
    np.testing.assert_allclose(tree_mv.leaf_value, tree_d.leaf_value,
                               rtol=2e-4, atol=2e-6)


def test_multival_full_training_with_valid():
    """End-to-end lgb.train on multi-val input incl. a valid set
    (exercises the mv binned-prediction traversal) and sparse input."""
    X, y = _bosch_like(n=3000)
    Xs = sp.csr_matrix(X)
    params = {"objective": "binary", "num_leaves": 31,
              "min_data_in_leaf": 5, "metric": "auc", "verbosity": -1}
    evals = {}
    dtrain = lgb.Dataset(Xs[:2400], label=y[:2400])
    dvalid = dtrain.create_valid(Xs[2400:], label=y[2400:])
    booster = lgb.train(params, dtrain, num_boost_round=20,
                        valid_sets=[dvalid], valid_names=["valid"],
                        callbacks=[lgb.record_evaluation(evals)])
    assert dtrain.construct()._inner.has_multival
    auc = evals["valid"]["auc"][-1]
    # dense reference on the SAME split: mv must match it (and the
    # valid-set score path must agree with raw-value prediction)
    evals_d = {}
    dt2 = lgb.Dataset(X[:2400], label=y[:2400],
                      params={"enable_bundle": False})
    dv2 = dt2.create_valid(X[2400:], label=y[2400:])
    lgb.train(params, dt2, num_boost_round=20, valid_sets=[dv2],
              valid_names=["valid"],
              callbacks=[lgb.record_evaluation(evals_d)])
    assert abs(auc - evals_d["valid"]["auc"][-1]) < 1e-6
    pred = booster.predict(X[2400:])
    from sklearn.metrics import roc_auc_score
    assert abs(roc_auc_score(y[2400:], pred) - auc) < 1e-6


def test_multival_dense_parity_auc():
    """AUC parity vs the dense path at matched params (VERDICT done
    criterion)."""
    X, y = _bosch_like(n=3000, f=200)
    from sklearn.metrics import roc_auc_score
    aucs = {}
    for name, extra in (("mv", {}), ("dense", {"enable_bundle": False})):
        params = {"objective": "binary", "num_leaves": 31,
                  "min_data_in_leaf": 5, "verbosity": -1, **extra}
        b = lgb.train(params, lgb.Dataset(X, label=y),
                      num_boost_round=15)
        aucs[name] = roc_auc_score(y, b.predict(X))
    assert abs(aucs["mv"] - aucs["dense"]) < 1e-6, aucs


def test_multival_binary_cache_roundtrip(tmp_path):
    X, y = _bosch_like()
    cfg = Config.from_params({"objective": "binary", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    assert ds.has_multival
    path = str(tmp_path / "cache.npz")
    ds.save_binary(path)
    ds2 = Dataset.load_binary(path)
    assert ds2.has_multival
    np.testing.assert_array_equal(ds.mv_slots, ds2.mv_slots)
    assert ds2.mv_group_start == ds.mv_group_start
    np.testing.assert_array_equal(ds.binned, ds2.binned)


def test_multival_subset_and_bagging():
    X, y = _bosch_like(n=2000)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "bagging_freq": 1,
              "bagging_fraction": 0.7, "verbosity": -1}
    b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, b.predict(X)) > 0.75


def test_multival_async_valid_scoring():
    """The ASYNC training path's valid scoring (traverse_tree_arrays)
    must decode multi-val pseudo-group splits from the slot matrix —
    regression for the silent clipped-column read. metric=\"\" keeps
    per-iteration eval off so the async path engages."""
    X, y = _bosch_like(n=2000)
    params = {"objective": "binary", "num_leaves": 31,
              "min_data_in_leaf": 5, "metric": "", "verbosity": -1}
    dtrain = lgb.Dataset(X[:1600], label=y[:1600])
    dvalid = dtrain.create_valid(X[1600:], label=y[1600:])
    booster = lgb.train(params, dtrain, num_boost_round=10,
                        valid_sets=[dvalid])
    src = booster._src()
    assert dtrain.construct()._inner.has_multival
    # the accumulated valid scores must equal a fresh raw prediction
    import numpy as np
    want = booster.predict(X[1600:], raw_score=True)
    got = np.asarray(src.valid_scores[0]).sum(axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_midsparsity_stays_dense():
    """~20%-density conflicting features would pad a slot matrix
    LARGER than their dense columns (4 * max-nnz-per-row >= F), so the
    planner must keep them as dense singletons, not multi-val."""
    rng = np.random.RandomState(5)
    n, f = 2000, 30
    X = np.where(rng.rand(n, f) < 0.2,
                 rng.randint(1, 9, size=(n, f)) * 0.5, 0.0)
    y = (X[:, 0] - X[:, 1] + 0.2 * rng.randn(n) > 0).astype(np.float32)
    cfg = Config.from_params({"objective": "binary", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    assert not ds.has_multival
    assert ds.binned.shape[1] == f  # dense singletons
