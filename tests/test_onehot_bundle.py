"""A sparse one-hot table through the chip's fast path (ISSUE 33):
``lgb.Dataset(scipy CSR)`` bundles it losslessly (EFB) into a few
physical byte columns of one 128-byte row, ``PartitionedTreeLearner``
grows on the bundled per-phase body, and the trees are those of the
same table trained dense and unbundled, and the plain reference's."""

import numpy as np
import pytest
import scipy.sparse as sp

import lightgbm_tpu as lgb
from benchmarks.generators import allstate_like
from benchmarks.kinds import train_sparse
from benchmarks.kinds.train_cat import _first_tree_gains
from benchmarks.reference import gbdt_sparse_numpy
from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.data.bundling import (decode_feature_bin,
                                        plan_bundles_from_nonzeros)
from lightgbm_tpu.observability.telemetry import get_telemetry
from lightgbm_tpu.ops.hist_pallas import matrix_cols

# make / model / sub-model nest; no factor has two values (its two
# indicator columns would mirror each other and tie exactly)
CARDS = [10, 80, 240, 3, 3, 3, 4, 4, 5, 5, 6, 7, 8, 9, 10, 8, 15]
FEATURES = allstate_like.NUMERIC + sum(CARDS)
ROWS = 20000
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "learning_rate": 0.1, "verbosity": -1, "metric": "",
          "tree_learner": "partitioned"}


@pytest.fixture(scope="module")
def table():
    return allstate_like.make(33, ROWS, FEATURES, cards=CARDS,
                              positive=0.2, signal=0.6)


@pytest.fixture(scope="module")
def bundled(table):
    x, y = table
    return Dataset.from_scipy(x, Config.from_params(PARAMS), label=y)


@pytest.fixture
def tel():
    t = get_telemetry()
    was_on = t.enabled
    t.ensure_ring()
    yield t
    if not was_on:
        t.reset()


def _column_bins_dense(inner, x):
    """[rows, used features] bins from the raw values and the bin
    boundaries alone."""
    dense = x.toarray()
    return np.stack([
        inner.feature_mapper(i).values_to_bins(
            dense[:, inner.real_feature_idx[i]].astype(np.float64))
        for i in range(inner.num_features)], axis=1)


def test_the_table_has_equal_columns(table):
    x, _ = table
    csc = x.tocsc()
    model0 = allstate_like.NUMERIC + CARDS[0]
    sub0 = model0 + CARDS[1]
    rows_of = [tuple(csc.indices[csc.indptr[j]:csc.indptr[j + 1]])
               for j in range(model0, sub0 + CARDS[2])]
    models, subs = set(rows_of[:CARDS[1]]), set(rows_of[CARDS[1]:])
    assert len(models & subs) >= 10     # a model with one sub-model


@pytest.mark.parametrize("what", ["no_multival", "no_conflict",
                                  "one_row", "few_columns",
                                  "bin_budget"])
def test_plan_is_lossless_physical_and_one_row(bundled, what):
    ds = bundled
    assert ds.feature_group is not None
    if what == "no_multival":
        assert not ds.has_multival
        assert ds.num_dense_groups == ds.num_groups == ds.binned.shape[1]
    elif what == "no_conflict":
        assert ds.bundle_conflict_rows == 0
    elif what == "one_row":
        assert matrix_cols(ds.num_groups) == 128
    elif what == "few_columns":
        # 17 categoricals and 16 numerics: the make, model and
        # sub-model each need a column a 255 values
        assert 33 <= ds.num_groups <= 40
        assert ds.num_features > 300
    else:
        assert int(ds.group_num_bins.max()) <= 256


def test_every_value_decodes_from_its_bundle(table, bundled):
    """Lossless: every column's bin in every row is recovered from its
    group column."""
    x, _ = table
    want = _column_bins_dense(bundled, x)
    for i in range(bundled.num_features):
        col = bundled.binned[:, bundled.feature_group[i]].astype(np.int64)
        got = decode_feature_bin(col, int(bundled.feature_offset[i]),
                                 int(bundled.num_bin(i)))
        np.testing.assert_array_equal(got, want[:, i], err_msg=str(i))


def test_the_plan_sees_every_row_not_a_sample(table):
    """Bins from a 2,000-row sample, the plan from all 20,000 rows: a
    rare column that a sampled plan would put beside a column it meets
    outside the sample is kept apart."""
    x, y = table
    cfg = Config.from_params(dict(PARAMS, bin_construct_sample_cnt=2000))
    ds = Dataset.from_scipy(x, cfg, label=y)
    assert ds.feature_group is not None and not ds.has_multival
    assert ds.bundle_conflict_rows == 0


@pytest.fixture(scope="module")
def models(table):
    """The CSR through EFB, and the same table dense and unbundled."""
    x, y = table
    sparse_ds = lgb.Dataset(x, label=y, params=PARAMS)
    b1 = lgb.train(PARAMS, sparse_ds, num_boost_round=3)
    p2 = dict(PARAMS, enable_bundle=False)
    dense_ds = lgb.Dataset(x.toarray(), label=y, params=p2)
    b2 = lgb.train(p2, dense_ds, num_boost_round=3)
    return sparse_ds, b1, dense_ds, b2


def test_csr_reaches_the_partitioned_learner_bundled(models, tel):
    sparse_ds, b1, dense_ds, b2 = models
    ln = b1._gbdt.learner
    assert type(ln).__name__ == "PartitionedTreeLearner"
    assert ln.bundled and ln.num_groups == sparse_ds._inner.num_groups
    plan = ln.split_plan()
    assert plan.body == "per_phase" and plan.lut_partition
    assert not plan.cat_scan and not plan.wide
    assert dense_ds._inner.feature_group is None
    assert not b2._gbdt.learner.bundled


@pytest.mark.parametrize("tree", [0, 1, 2])
def test_bundled_model_equals_the_unbundled_tree_for_tree(models, tree):
    _, b1, _, b2 = models
    t1, t2 = b1._gbdt.models[tree], b2._gbdt.models[tree]
    assert t1.num_leaves == t2.num_leaves == 15
    n = t1.num_leaves - 1
    for field in ("split_feature", "threshold_bin", "left_child",
                  "right_child", "internal_count", "decision_type"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t1, field))[:n],
            np.asarray(getattr(t2, field))[:n], err_msg=field)
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t2.leaf_value), atol=2e-6)
    np.testing.assert_allclose(np.asarray(t1.split_gain)[:n],
                               np.asarray(t2.split_gain)[:n], rtol=1e-4)


def test_bundled_model_agrees_with_the_plain_reference(table):
    """The reference sees per-column bins made from the raw values and
    the bin boundaries, and nothing of the bundles."""
    x, y = table
    init = (np.random.default_rng(5).standard_normal(ROWS) * 0.5
            ).astype(np.float32)
    ds = lgb.Dataset(x, label=y, params=PARAMS, init_score=init)
    bst = lgb.train(PARAMS, ds, num_boost_round=2)
    forest = []
    want = gbdt_sparse_numpy.train(
        *train_sparse._column_bins(ds._inner, x), y, PARAMS, 2,
        forest=forest, init_score=init)
    got = np.asarray(bst._gbdt.train_score[:, 0], np.float64)
    np.testing.assert_allclose(got, want, atol=2e-5)
    gains = _first_tree_gains(bst._gbdt.models[0], forest[0]["splits"])
    assert gains["first_tree_compared_splits"] \
        == gains["first_tree_splits"] == 14
    assert gains["gain_err_median"] < 4e-6


@pytest.mark.parametrize("counter,of", [
    ("data.bundle_features", lambda ds: ds.num_features),
    ("data.bundle_columns", lambda ds: ds.num_dense_groups),
    ("data.bundle_conflict_rows", lambda ds: 0),
    ("data.multival_features", lambda ds: 0),
])
def test_bundle_counters_read_what_the_table_says(table, tel, counter, of):
    x, y = table
    ds = Dataset.from_scipy(x, Config.from_params(PARAMS), label=y)
    assert tel.counters[counter] == of(ds)


def test_bundled_traces_counts_the_bundled_body(table, tel):
    x, y = table
    before = tel.counters.get("learner.bundled_traces", 0)
    small = dict(PARAMS, num_leaves=4)
    lgb.train(small, lgb.Dataset(x[:3000], label=y[:3000], params=small),
              num_boost_round=1)
    assert tel.counters["learner.bundled_traces"] > before
    unbundled = tel.counters["learner.bundled_traces"]
    dense = np.random.default_rng(0).standard_normal((500, 5))
    lgb.train(small, lgb.Dataset(dense, label=dense[:, 0] > 0,
                                 params=small), num_boost_round=1)
    assert tel.counters["learner.bundled_traces"] == unbundled


def test_one_injected_conflict_row_is_counted(table, bundled, tel):
    """A table laid out by a plan that did not see it: one row holds
    two members of one bundle, the second overwrites the first and the
    row is counted."""
    x, y = table
    group = np.asarray(bundled.feature_group)
    shared = next(g for g in range(bundled.num_groups)
                  if (group == g).sum() >= 2)
    a, b = [bundled.real_feature_idx[i]
            for i in np.flatnonzero(group == shared)[:2]]
    head = x[:200].tolil()
    head[7, a] = 1.0
    head[7, b] = 1.0
    clean = Dataset.from_scipy(x[:200], Config.from_params(PARAMS),
                               label=y[:200], reference=bundled)
    assert clean.bundle_conflict_rows == 0
    assert tel.counters["data.bundle_conflict_rows"] == 0
    hurt = Dataset.from_scipy(head.tocsr(), Config.from_params(PARAMS),
                              label=y[:200], reference=bundled)
    assert hurt.bundle_conflict_rows == 1
    assert tel.counters["data.bundle_conflict_rows"] == 1


@pytest.mark.parametrize("shared,columns", [(0, 1), (1, 2), (10, 2)])
def test_one_shared_row_keeps_two_columns_apart(shared, columns):
    """The conflict budget is 0, v2.3.2's default, for every table: two
    columns that share a row of those the plan saw share no physical
    column, however few the rows; two that share none share one."""
    rows = 1000
    a = np.arange(0, 600, dtype=np.int32)           # 60 % of the rows
    b = np.arange(600 - shared, 1000, dtype=np.int32)
    plan = plan_bundles_from_nonzeros([a, b], np.asarray([2, 2]), rows)
    assert plan.num_groups == columns


@pytest.mark.parametrize("columns,numeric,indicators",
                         [(9, 3, 300), (47, 16, 4212)])
def test_debundle_equals_an_index_a_bin(columns, numeric, indicators):
    """``debundle_hist``'s row gather and rolls give, bit for bit, what
    an index a bin gives: the comparison the chip holds at the cell's
    width (``tools/check_kernels_on_chip.py debundle``)."""
    from tools.check_kernels_on_chip import stage_debundle
    assert stage_debundle(columns=columns, numeric=numeric,
                          indicators=indicators) == 0


def test_equal_columns_take_the_lower_index(tel):
    """Two indicator columns that are equal in every row tie exactly;
    the program takes the lower feature index, as the reference does,
    whichever bundle either sits in."""
    rng = np.random.default_rng(3)
    rows = 4000
    cat_a = rng.integers(0, 6, rows)            # columns 1..6
    cat_b = rng.integers(0, 5, rows)            # columns 8..12
    dense = np.zeros((rows, 13), np.float32)
    dense[:, 0] = rng.standard_normal(rows)
    dense[np.arange(rows), 1 + cat_a] = 1.0
    dense[np.arange(rows), 8 + cat_b] = 1.0
    dense[:, 7] = dense[:, 3]                   # column 7 == column 3
    y = ((dense[:, 3] > 0) ^ (rng.random(rows) < 0.05)).astype(np.float32)
    params = dict(PARAMS, num_leaves=4)
    ds = lgb.Dataset(sp.csr_matrix(dense), label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=1)
    inner = ds._inner
    assert inner.feature_group is not None
    assert inner.feature_group[3] != inner.feature_group[7]
    assert int(bst._gbdt.models[0].split_feature[0]) == 3


def test_save_binary_round_trips_a_bundled_table(bundled, tmp_path, tel):
    path = str(tmp_path / "onehot.bin")
    bundled.save_binary(path)
    tel.counters.pop("data.bundle_columns", None)
    back = Dataset.load_binary(path)
    np.testing.assert_array_equal(back.binned, bundled.binned)
    np.testing.assert_array_equal(back.feature_group,
                                  bundled.feature_group)
    np.testing.assert_array_equal(back.feature_offset,
                                  bundled.feature_offset)
    np.testing.assert_array_equal(back.group_num_bins,
                                  bundled.group_num_bins)
    assert back.bin_layout_fingerprint() \
        == bundled.bin_layout_fingerprint()
    assert back.bundle_conflict_rows == 0 and not back.has_multival
    # a loaded table states its bundling as a constructed one does
    assert tel.counters["data.bundle_columns"] == bundled.num_dense_groups
