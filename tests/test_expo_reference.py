"""The learners against the benchmark's plain categorical reference
(``benchmarks/reference/gbdt_cat_numpy.py``: NumPy, float64, nothing of
``lightgbm_tpu``) on a small table from the benchmark's generator
(``benchmarks/generators/expo_like.py``): a one-hot column, sorted
category columns, one with more categories than a byte holds (cut to
``max_bin`` bins by the binning) and numeric ones (ISSUE 27). Same split
columns, thresholds and category sets where gains do not tie; leaf
values and scores to the tolerance float32 histograms leave."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import lightgbm_tpu as lgb  # noqa: E402
from benchmarks.generators import expo_like  # noqa: E402
from benchmarks.reference import gbdt_cat_numpy  # noqa: E402

COLUMNS = [
    {"name": "UniqueCarrier", "cardinality": 3, "exponent": 1.0},
    {"name": "Origin", "cardinality": 700, "exponent": 1.0},
    {"name": "Dest", "cardinality": 40, "exponent": 1.0},
    {"name": "Month", "cardinality": 12},
]
ROWS, FEATURES, TREES = 8000, 9, 3
TABLE_SEED = 4          # a table on which every column kind is split on
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "learning_rate": 0.1, "categorical_feature": "0,1,2,3",
          "metric": "", "verbosity": -1}
# float32 histogram sums against float64 ones, three trees deep
SCORE_TOL = 1e-4
GAIN_TIE = 1e-4         # relative: closer gains may fall either way
_CACHE = {}


def _table(seed):
    if seed not in _CACHE:
        x, y = expo_like.make(seed, ROWS, FEATURES, COLUMNS,
                              table_seed=TABLE_SEED)
        inner = lgb.Dataset(x, label=y,
                            params=PARAMS).construct()._inner
        mappers = [inner.feature_mapper(i) for i in range(FEATURES)]
        categorical = np.array([m.bin_type == "categorical"
                                for m in mappers])
        category_bins = np.array(
            [m.num_bin - (m.missing_type != "None") for m in mappers])
        forest = []
        scores = gbdt_cat_numpy.train(
            inner.binned, inner.num_bins_array(), y, PARAMS, TREES,
            categorical=categorical, category_bins=category_bins,
            forest=forest)
        _CACHE[seed] = (x, y, inner, forest, scores)
    return _CACHE[seed]


def _left_bins(bitset):
    return [w * 32 + b for w, word in enumerate(bitset)
            for b in range(32) if int(word) >> b & 1]


@pytest.mark.parametrize("seed", [3, 2147483777])
@pytest.mark.parametrize("learner", ["serial", "partitioned"])
def test_learner_grows_the_reference_trees(learner, seed):
    x, y, inner, forest, want = _table(seed)
    params = dict(PARAMS, tree_learner=learner)
    gbdt = lgb.Booster(params, lgb.Dataset(x, label=y,
                                           params=params))._gbdt
    gbdt.train(TREES)
    assert type(gbdt.learner).__name__ == {
        "serial": "SerialTreeLearner",
        "partitioned": "PartitionedTreeLearner"}[learner]
    tied = False
    # the program folds the starting score into its first tree
    start = np.log(y.mean() / (1.0 - y.mean()))
    for tree, ref in zip(gbdt.models, forest):
        splits = ref["splits"]
        assert tree.num_leaves == len(splits) + 1
        for i, s in enumerate(splits):
            got = (int(tree.split_feature[i]),
                   bool(int(tree.decision_type[i]) & 1))
            same = got == (s["feature"], "left_bins" in s)
            if same and "left_bins" in s:
                same = _left_bins(tree.cat_bitsets[i]) == s["left_bins"]
            elif same:
                same = int(tree.threshold_bin[i]) == s["threshold"]
            if not same:
                # only a tie may fall the other way; the trees differ
                # from here on
                assert abs(float(tree.split_gain[i]) - s["gain"]) \
                    <= GAIN_TIE * s["gain"], (i, got, s)
                tied = True
                break
        if tied:
            break
        np.testing.assert_allclose(
            np.asarray(tree.leaf_value[:tree.num_leaves], np.float64)
            - (start if tree is gbdt.models[0] else 0.0),
            ref["leaf_values"], rtol=1e-3, atol=1e-5)
    got = np.asarray(gbdt.train_score[:, 0], np.float64)
    assert np.isfinite(got).all()
    if not tied:
        assert np.abs(got - want).max() <= SCORE_TOL
    # the host trees send every row where the learner sent it
    raw = sum(np.asarray(t.predict(x.astype(np.float64)))
              for t in gbdt.models)
    assert np.abs(raw - got).max() <= SCORE_TOL


RULES = {
    "one_hot": lambda s, inner: "left_bins" in s and s["feature"] == 0
    and len(s["left_bins"]) == 1,
    "many_vs_many": lambda s, inner: "left_bins" in s
    and len(s["left_bins"]) >= 2,
    "wide_column_cut": lambda s, inner: s["feature"] == 1
    and "left_bins" in s,
    "numeric": lambda s, inner: "threshold" in s,
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_the_reference_forest_uses_every_rule(rule):
    x, y, inner, forest, _ = _table(2147483777)
    assert any(RULES[rule](s, inner) for t in forest for s in t["splits"])
    if rule == "one_hot":
        assert inner.feature_mapper(0).num_bin \
            <= gbdt_cat_numpy.DEFAULTS["max_cat_to_onehot"]
    if rule == "wide_column_cut":
        # more categories than a byte holds, and more than max_bin of
        # them with min_data_in_bin rows: cut to max_bin bins; the last
        # bin is then no category
        wide = inner.feature_mapper(1)
        counts = np.unique(x[:, 1], return_counts=True)[1]
        assert (counts >= 3).sum() > 256
        assert wide.num_bin == PARAMS["max_bin"]
        assert wide.missing_type == "NaN"
        assert not any(wide.num_bin - 1 in s.get("left_bins", [])
                       for t in forest for s in t["splits"]
                       if s["feature"] == 1)


def test_the_reference_imports_nothing_of_the_program():
    with open(gbdt_cat_numpy.__file__) as fh:
        text = fh.read()
    assert "lightgbm_tpu" not in text.split('"""', 2)[2]
    assert "import jax" not in text


def test_the_reference_in_lower_precision_moves_the_scores():
    """The reading the configuration's tolerances are set against."""
    import ml_dtypes
    x, y, inner, forest, want = _table(3)
    mappers = [inner.feature_mapper(i) for i in range(FEATURES)]
    low = gbdt_cat_numpy.train(
        inner.binned, inner.num_bins_array(), y, PARAMS, TREES,
        categorical=[m.bin_type == "categorical" for m in mappers],
        category_bins=[m.num_bin - (m.missing_type != "None")
                       for m in mappers],
        quantize=lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float64))
    assert np.abs(low - want).max() > SCORE_TOL
