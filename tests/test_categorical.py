"""Categorical split search tests.

Covers the one-hot and many-vs-many regimes of
``ops/split_categorical.py`` (reference semantics:
``FindBestThresholdCategoricalInner`` feature_histogram.hpp:149-310)
plus end-to-end training with categorical features.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.ops.split import FeatureMeta, SplitParams, kEpsilon
from lightgbm_tpu.ops.split_categorical import (_pack_bitset,
                                                per_feature_categorical)


def _meta(num_bins, missing=0, is_cat=True):
    f = len(num_bins)
    return FeatureMeta(
        num_bins=jnp.asarray(num_bins, jnp.int32),
        missing=jnp.full((f,), missing, jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        most_freq_bin=jnp.zeros((f,), jnp.int32),
        monotone=jnp.zeros((f,), jnp.int32),
        penalty=jnp.ones((f,), jnp.float32),
        is_categorical=jnp.full((f,), is_cat, bool))


def _params(**kw):
    base = dict(lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
                min_data_in_leaf=1.0, min_sum_hessian_in_leaf=1e-3,
                min_gain_to_split=0.0, has_categorical=True)
    base.update(kw)
    return SplitParams(**base)


def _bitset_members(bitset):
    out = []
    for w, word in enumerate(np.asarray(bitset, np.uint64)):
        for b in range(32):
            if (int(word) >> b) & 1:
                out.append(w * 32 + b)
    return out


def test_pack_bitset_roundtrip():
    bits = np.zeros((2, 64), bool)
    bits[0, [0, 5, 33]] = True
    bits[1, [63]] = True
    packed = np.asarray(_pack_bitset(jnp.asarray(bits)))
    assert _bitset_members(packed[0]) == [0, 5, 33]
    assert _bitset_members(packed[1]) == [63]


def test_onehot_picks_best_single_category():
    # 4 categories; category 2 has strongly negative gradient
    hist = np.zeros((1, 4, 3), np.float32)
    g = np.array([1.0, 0.5, -8.0, 1.5])
    h = np.array([4.0, 4.0, 4.0, 4.0])
    c = np.array([10, 10, 10, 10], np.float32)
    hist[0, :, 0] = g
    hist[0, :, 1] = h
    hist[0, :, 2] = c
    p = _params(max_cat_to_onehot=4)
    cat = per_feature_categorical(
        jnp.asarray(hist), jnp.float32(g.sum()), jnp.float32(h.sum()),
        jnp.float32(c.sum()), _meta([4]), p,
        jnp.float32(-np.inf), jnp.float32(np.inf))
    assert np.isfinite(float(cat["score"][0]))
    assert _bitset_members(np.asarray(cat["bitset"])[0]) == [2]
    # left stats are the category's own
    assert float(cat["left_g"][0]) == pytest.approx(-8.0)
    assert float(cat["left_c"][0]) == pytest.approx(10.0)


def _brute_force_many(g, h, c, parent_g, parent_h, parent_c, p):
    """Literal transcription of the reference's many-vs-many scan."""
    used = [i for i in range(len(g)) if c[i] >= p.cat_smooth]
    l2 = p.lambda_l2 + p.cat_l2
    ctr = lambda i: g[i] / (h[i] + p.cat_smooth)
    used.sort(key=ctr)
    nb = len(used)
    max_num_cat = min(p.max_cat_threshold, (nb + 1) // 2)
    gain_shift = parent_g ** 2 / (parent_h + 2 * kEpsilon + p.lambda_l2)
    best = (-np.inf, None, None)
    for dir_, start in ((1, 0), (-1, nb - 1)):
        lg, lh, lc, grp = 0.0, kEpsilon, 0.0, 0.0
        pos = start
        for i in range(min(nb, max_num_cat)):
            t = used[pos]
            pos += dir_
            lg += g[t]
            lh += h[t]
            lc += c[t]
            grp += c[t]
            if lc < p.min_data_in_leaf or lh < p.min_sum_hessian_in_leaf:
                continue
            rc = parent_c - lc
            if rc < p.min_data_in_leaf or rc < p.min_data_per_group:
                break
            rh = parent_h + 2 * kEpsilon - lh
            if rh < p.min_sum_hessian_in_leaf:
                break
            if grp < p.min_data_per_group:
                continue
            grp = 0.0
            rg = parent_g - lg
            gain = lg ** 2 / (lh + l2) + rg ** 2 / (rh + l2)
            if gain <= gain_shift + p.min_gain_to_split:
                continue
            if gain > best[0]:
                if dir_ == 1:
                    members = used[:i + 1]
                else:
                    members = used[nb - 1 - i:]
                best = (gain - gain_shift, sorted(members), lg)
    return best


def test_many_vs_many_matches_bruteforce():
    rng = np.random.RandomState(7)
    nbins = 20
    g = rng.randn(nbins).astype(np.float64) * 5
    h = np.abs(rng.randn(nbins)).astype(np.float64) * 3 + 1
    c = rng.randint(5, 50, nbins).astype(np.float64)
    hist = np.stack([g, h, c], axis=1)[None].astype(np.float32)
    p = _params(max_cat_to_onehot=4, min_data_per_group=10.0,
                cat_smooth=10.0, cat_l2=10.0, max_cat_threshold=32)
    cat = per_feature_categorical(
        jnp.asarray(hist), jnp.float32(g.sum()), jnp.float32(h.sum()),
        jnp.float32(c.sum()), _meta([nbins]), p,
        jnp.float32(-np.inf), jnp.float32(np.inf))
    ref_gain, ref_members, ref_lg = _brute_force_many(
        g, h, c, g.sum(), h.sum(), c.sum(), p)
    got = float(cat["score"][0])
    if ref_members is None:
        assert not np.isfinite(got)
    else:
        assert got == pytest.approx(ref_gain, rel=1e-4)
        assert _bitset_members(np.asarray(cat["bitset"])[0]) == ref_members
        assert float(cat["left_g"][0]) == pytest.approx(ref_lg, rel=1e-4)


def test_best_split_prefers_informative_categorical():
    # numerical feature = noise; categorical feature separates perfectly
    n = 4000
    rng = np.random.RandomState(0)
    cats = rng.randint(0, 8, n)
    y = (np.isin(cats, [1, 3, 6])).astype(np.float32)
    X = np.stack([rng.randn(n), cats.astype(np.float64)], axis=1)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 4,
                              "min_data_in_leaf": 20, "verbosity": -1,
                              "min_data_per_group": 10})
    ds = Dataset.from_numpy(X, cfg, label=y, categorical_features=[1])
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    lr = SerialTreeLearner(ds, cfg)
    assert lr.params.has_categorical
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((n,), 0.25, jnp.float32)
    res = lr.train(grad, hess)
    tree = lr.to_host_tree(res)
    # root split must be the categorical feature
    assert int(tree.split_feature_inner[0]) == 1
    assert int(tree.decision_type[0]) & 1  # categorical flag


def test_categorical_end_to_end_beats_numerical_treatment():
    n = 6000
    rng = np.random.RandomState(3)
    cats = rng.randint(0, 40, n)
    effect = np.where(np.isin(cats, [2, 5, 11, 17, 23, 31]), 2.5, -1.0)
    noise = rng.randn(n, 3)
    logit = effect + 0.3 * noise[:, 0]
    y = (logit + rng.randn(n) * 0.5 > 0).astype(np.float32)
    X = np.concatenate([cats[:, None].astype(np.float64), noise], axis=1)

    def run(cat_feats):
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": 15, "verbosity": -1,
            "num_iterations": 20, "learning_rate": 0.2,
            "min_data_per_group": 20})
        ds = Dataset.from_numpy(X, cfg, label=y,
                                categorical_features=cat_feats)
        b = GBDT(cfg, ds)
        b.train()
        from sklearn.metrics import roc_auc_score
        return float(roc_auc_score(y, np.asarray(b.predict_raw(X)).ravel()))

    auc_cat = run([0])
    assert auc_cat > 0.9
    # numerical treatment of an unordered 40-way category needs many more
    # splits to carve out the high-effect ids; categorical must win
    auc_num = run([])
    assert auc_cat >= auc_num - 0.01


def test_categorical_prediction_consistency():
    # device bin-space traversal and host value-space prediction agree
    n = 2000
    rng = np.random.RandomState(5)
    cats = rng.randint(0, 12, n)
    y = (np.isin(cats, [0, 4, 7])).astype(np.float32)
    X = cats[:, None].astype(np.float64)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 6,
                              "verbosity": -1, "num_iterations": 5,
                              "min_data_per_group": 5})
    ds = Dataset.from_numpy(X, cfg, label=y, categorical_features=[0])
    b = GBDT(cfg, ds)
    b.train()
    raw = np.asarray(b.predict_raw(X)).ravel()
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, raw) > 0.95


@pytest.mark.parametrize("params", [
    {"categorical_feature": "0,2"}, {"categorical_feature": [0, 2]},
    {"cat_feature": "0,2"}, {"categorical_column": [2, 0]},
    {"cat_column": "0,2"}, {"categorical_feature": "name:a,c"}],
    ids=lambda p: next(iter(p)) + ":" + type(next(iter(p.values()))).__name__)
def test_dataset_reads_categorical_feature_from_params(params):
    """``lgb.Dataset(x, params=...)`` honours ``categorical_feature``
    and its aliases when the keyword is left at "auto", as upstream's
    ``Dataset._lazy_init`` does (a config file states them this way):
    one bin layout with the keyword route."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    x = np.stack([rng.randint(0, 7, 600), rng.randn(600),
                  rng.randint(0, 300, 600)], axis=1).astype(np.float64)
    y = (x[:, 0] % 2 == 0).astype(np.float64)
    names = ["a", "b", "c"]
    keyword = lgb.Dataset(x, label=y, feature_name=names,
                          categorical_feature=[0, 2]).construct()._inner
    through = lgb.Dataset(x, label=y, feature_name=names,
                          params=dict(params, verbosity=-1)
                          ).construct()._inner
    assert [through.feature_mapper(i).bin_type for i in range(3)] \
        == ["categorical", "numerical", "categorical"]
    assert through.bin_layout_fingerprint() \
        == keyword.bin_layout_fingerprint()
    plain = lgb.Dataset(x, label=y, feature_name=names).construct()._inner
    assert plain.bin_layout_fingerprint() \
        != keyword.bin_layout_fingerprint()


def test_categorical_keyword_wins_over_params():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(1)
    x = rng.randint(0, 5, (400, 2)).astype(np.float64)
    inner = lgb.Dataset(x, label=x[:, 0] % 2, categorical_feature=[1],
                        params={"categorical_feature": "0",
                                "verbosity": -1}).construct()._inner
    assert [inner.feature_mapper(i).bin_type for i in range(2)] \
        == ["numerical", "categorical"]


def _zipf_column(rng, categories, n):
    p = 1.0 / np.arange(1, categories + 1)
    ids = rng.permutation(categories)       # the ids' order says nothing
    return ids[rng.choice(categories, n, p=p / p.sum())]


@pytest.mark.parametrize("max_bin,bins", [(255, 255), (63, 63),
                                          (1000, 256)])
def test_a_wide_categorical_column_is_cut_to_max_bin(max_bin, bins):
    """A categorical column keeps at most min(max_bin, 256) bins
    (``binning.MAX_CATEGORICAL_BINS``: a split's left set is a 256-bit
    set on every learner). Read off the raw values, without the
    mapper's own table: the most frequent categories keep a bin each,
    all the others share the last bin, which is no category."""
    from lightgbm_tpu.data.binning import MAX_CATEGORICAL_BINS
    from lightgbm_tpu.ops.split import MAX_CAT_WORDS
    assert MAX_CATEGORICAL_BINS == 32 * MAX_CAT_WORDS
    rng = np.random.RandomState(7)
    n = 60000
    col = _zipf_column(rng, 1500, n)
    x = np.stack([col, rng.randn(n)], axis=1).astype(np.float64)
    cfg = Config.from_params({"objective": "binary", "max_bin": max_bin,
                              "verbosity": -1})
    ds = Dataset.from_numpy(x, cfg, label=(col % 2).astype(np.float64),
                            categorical_features=[0])
    m = ds.feature_mapper(0)
    assert m.num_bin == bins and m.missing_type == "NaN"
    got = np.asarray(ds.binned[:, 0]).astype(np.int64)
    ids, counts = np.unique(col, return_counts=True)
    by_count = ids[np.argsort(-counts, kind="stable")]
    alone, shared = by_count[:bins - 1], by_count[bins - 1:]
    own = np.array([np.unique(got[col == c]) for c in alone])
    assert own.shape == (bins - 1, 1)                 # one bin a category
    assert len(np.unique(own)) == bins - 1            # and no two alike
    assert bins - 1 not in own
    assert (got[np.isin(col, shared)] == bins - 1).all()
    # a category the table never saw, and a missing value, go there too
    assert list(m.values_to_bins(np.array([99999.0, np.nan, -1.0]))) \
        == [bins - 1] * 3


@pytest.mark.parametrize("learner", ["serial", "partitioned"])
def test_a_wide_categorical_column_is_split_on(learner):
    """Cut to 255 bins, a 1500-category column is split on by both
    learners (uncut it had about 1,000 bins: the scan invalidated it and
    the partitioned learner refused the table); the shared last bin
    never goes left, so the host trees, which send an unlisted category
    right, agree with the training scores on every row."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(11)
    n = 20000
    col = _zipf_column(rng, 1500, n)
    effect = rng.randn(1500)
    y = (effect[col] + 0.5 * rng.randn(n) > 0).astype(np.float64)
    x = np.stack([col, rng.randn(n)], axis=1).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "categorical_feature": "0", "tree_learner": learner,
              "metric": ""}
    gbdt = lgb.Booster(params, lgb.Dataset(x, label=y,
                                           params=params))._gbdt
    gbdt.train(3)
    last = gbdt.train_data.feature_mapper(0).num_bin - 1
    assert last == 254
    sets = [_bitset_members(t.cat_bitsets[i]) for t in gbdt.models
            for i in range(t.num_leaves - 1)
            if int(t.decision_type[i]) & 1]
    assert sets and all(last not in s for s in sets)
    got = np.asarray(gbdt.train_score[:, 0], np.float64)
    raw = sum(np.asarray(t.predict(x)) for t in gbdt.models)
    assert np.abs(raw - got).max() <= 1e-5
