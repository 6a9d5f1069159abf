"""Ranking stack tests: lambdarank/xendcg gradients vs a NumPy oracle
transcribed from the reference loops, NDCG/MAP metric values, and
end-to-end LTR training lift."""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.metric.rank_metrics import MapMetric, NDCGMetric
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objective.rank import (LambdarankNDCG, RankXENDCG,
                                         default_label_gain)


def _synthetic_ltr(nq=60, min_docs=3, max_docs=25, f=8, seed=0):
    rng = np.random.RandomState(seed)
    counts = rng.randint(min_docs, max_docs + 1, nq)
    n = counts.sum()
    X = rng.randn(n, f)
    rel = 2.2 * X[:, 0] - 1.4 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3] \
        + rng.randn(n) * 0.5
    # grade into 0..4 per global quantiles
    qs = np.quantile(rel, [0.5, 0.75, 0.9, 0.97])
    y = np.digitize(rel, qs).astype(np.float32)
    return X, y, counts


def _oracle_lambdarank(score, label, qb, sigmoid=1.0, norm=True,
                       truncation=20, label_gain=None):
    """Direct transcription of GetGradientsForOneQuery
    (rank_objective.hpp:139-230) with an exact sigmoid."""
    gain = default_label_gain() if label_gain is None else label_gain
    n = len(score)
    lam = np.zeros(n)
    hess = np.zeros(n)
    discount = 1.0 / np.log2(2.0 + np.arange(n))
    for qi in range(len(qb) - 1):
        s, e = qb[qi], qb[qi + 1]
        cnt = e - s
        sc = score[s:e]
        lb = label[s:e].astype(int)
        top = np.sort(lb)[::-1][:truncation]
        maxdcg = (gain[top] * discount[:len(top)]).sum()
        inv = 1.0 / maxdcg if maxdcg > 0 else 0.0
        order = np.argsort(-sc, kind="stable")
        best, worst = sc[order[0]], sc[order[cnt - 1]]
        lam_q = np.zeros(cnt)
        hess_q = np.zeros(cnt)
        sum_lambdas = 0.0
        for i in range(cnt):
            hi = order[i]
            for j in range(cnt):
                if i == j:
                    continue
                lo = order[j]
                if lb[hi] <= lb[lo]:
                    continue
                ds = sc[hi] - sc[lo]
                gap = gain[lb[hi]] - gain[lb[lo]]
                pd = abs(discount[i] - discount[j])
                delta = gap * pd * inv
                if norm and best != worst:
                    delta /= (0.01 + abs(ds))
                sig = 1.0 / (1.0 + np.exp(sigmoid * ds))
                pl = -sigmoid * delta * sig
                ph = sigmoid * sigmoid * delta * sig * (1 - sig)
                lam_q[hi] += pl
                lam_q[lo] -= pl
                hess_q[hi] += ph
                hess_q[lo] += ph
                sum_lambdas -= 2 * pl
        if norm and sum_lambdas > 0:
            nf = np.log2(1 + sum_lambdas) / sum_lambdas
            lam_q *= nf
            hess_q *= nf
        lam[s:e] = lam_q
        hess[s:e] = hess_q
    return lam, hess


def test_lambdarank_matches_oracle():
    import jax.numpy as jnp
    X, y, counts = _synthetic_ltr(nq=25, max_docs=15, seed=3)
    cfg = Config.from_params({"objective": "lambdarank", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y, group=counts)
    obj = LambdarankNDCG(cfg)
    obj.init(ds.metadata, ds.num_data)
    rng = np.random.RandomState(0)
    score = rng.randn(ds.num_data).astype(np.float32)
    g, h = obj.gradients(jnp.asarray(score))
    qb = np.asarray(ds.metadata.query_boundaries)
    og, oh = _oracle_lambdarank(score.astype(np.float64), y, qb)
    np.testing.assert_allclose(np.asarray(g), og, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h), oh, rtol=2e-4, atol=2e-6)


def test_lambdarank_zero_at_equal_labels():
    """Queries with all-equal labels produce zero lambdas."""
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    X = rng.randn(30, 4)
    y = np.ones(30, np.float32)
    counts = np.asarray([10, 20])
    cfg = Config.from_params({"objective": "lambdarank", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y, group=counts)
    obj = LambdarankNDCG(cfg)
    obj.init(ds.metadata, ds.num_data)
    g, h = obj.gradients(jnp.asarray(rng.randn(30).astype(np.float32)))
    np.testing.assert_allclose(np.asarray(g), 0.0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(h), 0.0, atol=1e-7)


def _oracle_xendcg(score, label, qb, u):
    """Transcription of RankXENDCG::GetGradientsForOneQuery
    (rank_objective.hpp:306-349) with supplied uniforms."""
    n = len(score)
    lam = np.zeros(n)
    hess = np.zeros(n)
    for qi in range(len(qb) - 1):
        s, e = qb[qi], qb[qi + 1]
        cnt = e - s
        sc = score[s:e].astype(np.float64)
        rho = np.exp(sc - sc.max())
        rho /= rho.sum()
        l1 = np.exp2(label[s:e].astype(int)) - u[s:e]
        sum_labels = max(1e-15, l1.sum())
        l1 = -l1 / sum_labels + rho
        if cnt <= 1:
            lam[s:e] = l1
        else:
            sum_l1 = l1.sum()
            l2 = (sum_l1 - l1) / (1 - rho)
            sum_l2 = l2.sum()
            l3 = (sum_l2 - l2) / (1 - rho)
            lam[s:e] = l1 + rho * l2 + rho * rho * l3
        hess[s:e] = rho * (1 - rho)
    return lam, hess


def test_xendcg_matches_oracle():
    import jax.numpy as jnp
    X, y, counts = _synthetic_ltr(nq=20, seed=4)
    cfg = Config.from_params({"objective": "rank_xendcg", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y, group=counts)
    obj = RankXENDCG(cfg)
    obj.init(ds.metadata, ds.num_data)
    score = np.random.RandomState(0).randn(ds.num_data).astype(np.float32)
    obj._rng = np.random.RandomState(123)
    u = np.random.RandomState(123).rand(ds.num_data).astype(np.float32)
    g, h = obj.gradients(jnp.asarray(score))
    qb = np.asarray(ds.metadata.query_boundaries)
    og, oh = _oracle_xendcg(score, y, qb, u)
    np.testing.assert_allclose(np.asarray(g), og, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h), oh, rtol=2e-4, atol=2e-6)


def _oracle_ndcg_at(score, label, qb, ks, gain=None):
    gain = default_label_gain() if gain is None else gain
    res = np.zeros(len(ks))
    nq = len(qb) - 1
    for qi in range(nq):
        s, e = qb[qi], qb[qi + 1]
        lb = label[s:e].astype(int)
        sc = score[s:e]
        disc = 1.0 / np.log2(2.0 + np.arange(e - s))
        order = np.argsort(-sc, kind="stable")
        for j, k in enumerate(ks):
            kk = min(k, e - s)
            ideal = (np.sort(gain[lb])[::-1][:kk] * disc[:kk]).sum()
            if ideal <= 0:
                res[j] += 1.0
            else:
                dcg = (gain[lb[order[:kk]]] * disc[:kk]).sum()
                res[j] += dcg / ideal
    return res / nq


def test_ndcg_metric_matches_oracle():
    X, y, counts = _synthetic_ltr(nq=30, seed=5)
    cfg = Config.from_params({"objective": "lambdarank",
                              "eval_at": [1, 3, 5, 10], "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y, group=counts)
    m = NDCGMetric(cfg)
    m.init(ds.metadata, ds.num_data)
    assert m.names == ["ndcg@1", "ndcg@3", "ndcg@5", "ndcg@10"]
    score = np.random.RandomState(1).randn(ds.num_data)
    vals = m.eval(score, None)
    qb = np.asarray(ds.metadata.query_boundaries)
    oracle = _oracle_ndcg_at(score, y, qb, [1, 3, 5, 10])
    np.testing.assert_allclose(vals, oracle, rtol=1e-10)
    # perfect ranking scores NDCG 1
    vals_perfect = m.eval(y.astype(np.float64), None)
    # ties in y make stable order == ideal order; all should be 1
    np.testing.assert_allclose(vals_perfect, 1.0, atol=1e-12)


def test_map_metric_basic():
    # one query, known AP
    y = np.asarray([1, 0, 1, 0, 0], np.float32)
    score = np.asarray([5.0, 4.0, 3.0, 2.0, 1.0])
    cfg = Config.from_params({"objective": "lambdarank",
                              "eval_at": [3, 5], "verbosity": -1})
    X = np.random.RandomState(0).randn(5, 2)
    ds = Dataset.from_numpy(X, cfg, label=y, group=[5])
    m = MapMetric(cfg)
    m.init(ds.metadata, ds.num_data)
    vals = m.eval(score, None)
    # hits at ranks 1 and 3: precisions 1/1, 2/3
    ap3 = (1.0 + 2.0 / 3.0) / 2
    ap5 = (1.0 + 2.0 / 3.0) / 2
    np.testing.assert_allclose(vals, [ap3, ap5], rtol=1e-12)


def test_lambdarank_end_to_end_ndcg_lift():
    X, y, counts = _synthetic_ltr(nq=80, max_docs=20, seed=6)
    cfg = Config.from_params({
        "objective": "lambdarank", "num_leaves": 15, "learning_rate": 0.1,
        "metric": "ndcg", "eval_at": [10], "min_data_in_leaf": 5,
        "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y, group=counts)
    booster = GBDT(cfg, ds)
    m = NDCGMetric(cfg)
    m.init(ds.metadata, ds.num_data)
    before = m.eval(np.zeros(ds.num_data), None)[0]
    booster.train(30)
    score = np.asarray(booster.train_score[:, 0], np.float64)
    after = m.eval(score, None)[0]
    assert after > before + 0.05, (before, after)


def test_xendcg_end_to_end_ndcg_lift():
    X, y, counts = _synthetic_ltr(nq=80, max_docs=20, seed=7)
    cfg = Config.from_params({
        "objective": "rank_xendcg", "num_leaves": 15,
        "learning_rate": 0.1, "metric": "ndcg", "eval_at": [10],
        "min_data_in_leaf": 5, "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y, group=counts)
    booster = GBDT(cfg, ds)
    m = NDCGMetric(cfg)
    m.init(ds.metadata, ds.num_data)
    before = m.eval(np.zeros(ds.num_data), None)[0]
    booster.train(30)
    score = np.asarray(booster.train_score[:, 0], np.float64)
    after = m.eval(score, None)[0]
    assert after > before + 0.05, (before, after)


def test_ndcg_early_stopping_on_valid():
    X, y, counts = _synthetic_ltr(nq=60, seed=8)
    Xv, yv, cv = _synthetic_ltr(nq=30, seed=9)
    cfg = Config.from_params({
        "objective": "lambdarank", "num_leaves": 15,
        "learning_rate": 0.3, "metric": "ndcg", "eval_at": [5],
        "early_stopping_round": 3, "min_data_in_leaf": 5,
        "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y, group=counts)
    dv = Dataset.from_numpy(Xv, cfg, label=yv, group=cv, reference=ds)
    booster = GBDT(cfg, ds)
    booster.add_valid(dv, "valid_0")
    booster.train(100)
    assert booster.num_iterations_trained < 100
    assert "ndcg@5" in booster.evals_result["valid_0"]


# -- the query layout (ISSUE 37) -----------------------------------------
def _padded_lambdarank(score, label, qb, weights=None, sigmoid=1.0,
                       norm=True, truncation=20):
    """The layout this file's objective had until PR 37, kept here as
    the oracle of the new one: every query padded to the longest, an
    index a slot in and a scatter-add out, ``argsort`` +
    ``take_along_axis`` for the order, one ``[nq, Q, Q]`` pair block."""
    import jax.numpy as jnp
    n, counts = len(score), np.diff(qb)
    nq, q = len(counts), int(counts.max())
    gain = default_label_gain()
    idx = np.full((nq, q), n, np.int32)
    inv = np.zeros(nq, np.float32)
    disc = 1.0 / np.log2(2.0 + np.arange(q))
    for i in range(nq):
        idx[i, :counts[i]] = np.arange(qb[i], qb[i + 1])
        top = np.sort(label[qb[i]:qb[i + 1]].astype(int))[::-1][:truncation]
        m = (gain[top] * disc[:len(top)]).sum()
        inv[i] = 1.0 / m if m > 0 else 0.0
    msk = jnp.asarray(idx < n)
    ext = jnp.concatenate([jnp.asarray(score, jnp.float32), jnp.zeros(1)])
    sc = jnp.where(msk, ext[idx], -jnp.inf)
    lab = jnp.asarray(np.concatenate([label, [0]]).astype(np.int32))[idx]
    order = jnp.argsort(-sc, axis=1, stable=True)
    sc_s = jnp.take_along_axis(sc, order, axis=1)
    lab_s = jnp.take_along_axis(lab, order, axis=1)
    ok_s = jnp.take_along_axis(msk, order, axis=1)
    worst = jnp.take_along_axis(
        sc_s, jnp.asarray(counts - 1)[:, None], axis=1)[:, 0]
    ds = sc_s[:, :, None] - sc_s[:, None, :]
    g = jnp.asarray(gain, jnp.float32)
    d = jnp.asarray(disc, jnp.float32)
    delta = (g[lab_s][:, :, None] - g[lab_s][:, None, :]) \
        * jnp.abs(d[None, :, None] - d[None, None, :]) \
        * jnp.asarray(inv)[:, None, None]
    if norm:
        delta = jnp.where((sc_s[:, 0] != worst)[:, None, None],
                          delta / (0.01 + jnp.abs(ds)), delta)
    pair = (lab_s[:, :, None] > lab_s[:, None, :]) \
        & ok_s[:, :, None] & ok_s[:, None, :]
    sig = 1.0 / (1.0 + jnp.exp(sigmoid * ds))
    pl = jnp.where(pair, -sigmoid * delta * sig, 0.0)
    ph = jnp.where(pair, sigmoid * sigmoid * delta * sig * (1 - sig), 0.0)
    lam_s = pl.sum(axis=2) - pl.sum(axis=1)
    hess_s = ph.sum(axis=2) + ph.sum(axis=1)
    if norm:
        s = -2.0 * pl.sum(axis=(1, 2))
        nf = jnp.where(s > 0, jnp.log2(1 + s) / jnp.maximum(s, 1e-15), 1.0)
        lam_s, hess_s = lam_s * nf[:, None], hess_s * nf[:, None]
    back = jnp.argsort(order, axis=1, stable=True)
    flat = idx.reshape(-1)
    out = [jnp.zeros(n + 1).at[flat].add(
        jnp.take_along_axis(a, back, axis=1).reshape(-1))[:n]
        for a in (lam_s, hess_s)]
    w = 1.0 if weights is None else jnp.asarray(weights)
    return np.asarray(out[0] * w), np.asarray(out[1] * w)


def _ragged_queries(seed=0):
    """Seeded ragged queries of 1 to 300 documents: one of a single
    document, one whose labels are all equal, one longer than the
    largest class but one."""
    rng = np.random.RandomState(seed)
    counts = np.concatenate([[1, 17, 300], rng.randint(1, 120, 60)])
    rng.shuffle(counts)
    n = int(counts.sum())
    y = rng.choice(5, n, p=[0.5, 0.3, 0.14, 0.04, 0.02]).astype(np.float32)
    qb = np.concatenate([[0], np.cumsum(counts)])
    q17 = int(np.flatnonzero(counts == 17)[0])
    y[qb[q17]:qb[q17 + 1]] = 2.0
    score = rng.randn(n).astype(np.float32)
    # ties within a query keep row order
    score[qb[3]:qb[3] + 4] = 0.25
    return counts, qb, y, score, rng.uniform(0.5, 2.0, n).astype(np.float32)


def _rank_objective(cls, counts, y, weights=None, **params):
    cfg = Config.from_params(dict(
        {"objective": "lambdarank", "verbosity": -1}, **params))
    ds = Dataset.from_numpy(np.zeros((len(y), 2)), cfg, label=y,
                            group=counts, weight=weights)
    obj = cls(cfg)
    obj.init(ds.metadata, ds.num_data)
    return obj


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("weighted", [False, True])
def test_layout_gradients_equal_the_padded_layouts(norm, weighted):
    import jax.numpy as jnp
    counts, qb, y, score, w = _ragged_queries()
    w = w if weighted else None
    obj = _rank_objective(LambdarankNDCG, counts, y, w,
                          lambdarank_norm=norm)
    lengths = obj.layout.lengths
    assert len(lengths) > 2 and lengths[-2] < 300 <= lengths[-1]
    g, h = obj.gradients(jnp.asarray(score))
    og, oh = _padded_lambdarank(score, y, qb, w, norm=norm)
    # float32 sums in another order
    np.testing.assert_allclose(np.asarray(g), og, rtol=1e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h), oh, rtol=1e-4, atol=2e-6)
    # the jitted program with the layout as an argument, as the fused
    # block calls it
    import jax
    gj, hj = jax.jit(obj.gradients)(jnp.asarray(score),
                                    *obj.grad_operands())
    np.testing.assert_allclose(np.asarray(gj), np.asarray(g), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(hj), np.asarray(h), rtol=1e-5,
                               atol=1e-7)
    one = int(qb[np.flatnonzero(counts == 1)[0]])
    assert float(g[one]) == 0.0 and float(h[one]) == 0.0
    q17 = int(np.flatnonzero(counts == 17)[0])
    assert not np.asarray(g[qb[q17]:qb[q17 + 1]]).any()


@pytest.mark.parametrize("norm", [True, False])
def test_layout_gradients_equal_the_plain_references(norm):
    import jax.numpy as jnp
    from benchmarks.reference import gbdt_rank_numpy
    counts, _, y, score, _ = _ragged_queries(seed=1)
    obj = _rank_objective(LambdarankNDCG, counts, y, lambdarank_norm=norm)
    g, h = obj.gradients(jnp.asarray(score))
    og, oh = gbdt_rank_numpy.lambdarank_gradients(
        score, y, counts, {"lambdarank_norm": norm})
    np.testing.assert_allclose(np.asarray(g), og, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h), oh, rtol=2e-4, atol=2e-6)


def test_layout_follows_the_documents_not_the_longest_query():
    """One 1,251-document query among 500 short ones: no ``nq x
    max_query`` array, the counters say what the layout came to."""
    from lightgbm_tpu.observability.telemetry import get_telemetry
    rng = np.random.RandomState(0)
    counts = np.concatenate([rng.randint(1, 60, 250), [1251],
                             rng.randint(1, 60, 250)])
    y = rng.randint(0, 5, counts.sum()).astype(np.float32)
    tel = get_telemetry()
    tel.ensure_ring()
    obj = _rank_objective(LambdarankNDCG, counts, y)
    c = {k.split("rank_")[1]: int(v) for k, v in tel.counters.items()
         if k.startswith("objective.rank_")}
    assert c["queries"] == 501 and c["docs"] == counts.sum()
    assert c["doc_pairs"] == (counts.astype(np.int64) ** 2).sum()
    assert c["slots"] <= 1.6 * c["docs"]
    assert c["pair_slots"] <= 4 * c["doc_pairs"]
    assert 2 <= c["classes"] <= 8
    assert c["slots"] < 501 * 1251 // 10
    lay = obj.layout
    assert c["slots"] == sum(s * l for s, l in zip(lay.sizes, lay.lengths))
    import jax
    held = [a for a in jax.tree.leaves(obj.grad_operands())]
    assert max(a.size for a in held) == counts.sum()
    assert obj.setup_facts() == {"queries": 501, "classes": c["classes"]}


def test_xendcg_on_the_layout_matches_oracle_on_ragged_queries():
    import jax.numpy as jnp
    counts, qb, y, score, _ = _ragged_queries(seed=2)
    obj = _rank_objective(RankXENDCG, counts, y, objective="rank_xendcg",
                          objective_seed=5)
    u = np.random.RandomState(5).rand(len(y)).astype(np.float32)
    g, h = obj.gradients(jnp.asarray(score))
    og, oh = _oracle_xendcg(score.astype(np.float64), y, qb, u)
    np.testing.assert_allclose(np.asarray(g), og, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h), oh, rtol=2e-4, atol=2e-6)


def test_fused_lambdarank_matches_per_iteration_and_names_its_parts(
        monkeypatch):
    """(Here and not in tests/test_fused_scan.py, which tier-1 leaves
    out as slow.) Lambdarank on ragged query groups rides the fused block (its
    query layout is an argument of the program, ISSUE 37): tree for
    tree the per-iteration route's model, and the block's gradient
    program carries the three ranking scopes."""
    import jax
    from lightgbm_tpu.models.tree import DeferredStackTree
    from lightgbm_tpu.models.variants import create_boosting
    from lightgbm_tpu.observability import scopes
    from lightgbm_tpu.observability.telemetry import get_telemetry
    rng = np.random.RandomState(5)
    group = np.concatenate([[1, 150], rng.randint(2, 40, 40)])
    n = int(group.sum())
    X = rng.randn(n, 6).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 0.5 * rng.randn(n) + 1), 0, 4) \
        .astype(np.float32)
    get_telemetry().ensure_ring()
    scopes.forget()
    models = []
    for fused in (False, True):
        monkeypatch.setenv("LGBM_TPU_FUSE_ITERS", "1" if fused else "0")
        cfg = Config.from_params({
            "objective": "lambdarank", "num_leaves": 7,
            "learning_rate": 0.1, "tree_learner": "partitioned",
            "verbosity": -1, "metric": ""})
        b = create_boosting(cfg, Dataset.from_numpy(X, cfg, label=y,
                                                    group=group))
        assert b._fused_scan_supported() is fused
        b.train(5)
        b.finalize_trees()
        models.append(b)
    assert any(isinstance(m, DeferredStackTree) for m in models[1].models)
    assert len(models[0].models) == len(models[1].models) == 5
    for t0, t1 in zip(models[0].models, models[1].models):
        assert int(t0.num_leaves) == int(t1.num_leaves)
        np.testing.assert_array_equal(t0.split_feature, t1.split_feature)
        np.testing.assert_array_equal(t0.threshold_bin,
                                      t1.threshold_bin)
    # the same splits; the pair sums are float32 in the order XLA
    # fuses them, inside the scan and outside it
    np.testing.assert_allclose(np.asarray(models[0].predict_raw(X)),
                               np.asarray(models[1].predict_raw(X)),
                               rtol=1e-4, atol=1e-6)
    table = scopes.program_scopes("gbdt_fused_block")
    assert set(scopes.RANK_SCOPES) <= set(table.values())
    # the layout is an argument of the program, not a constant in it
    fused = models[1]
    assert fused._grad_operands and jax.tree.leaves(fused._grad_operands)
