"""Fused Pallas split-scan kernel vs the XLA reference scan.

The kernel (ops/split_scan_pallas.py) recomputes the cumulative sums
with a different (but mathematically identical) reduction order, so
per-feature gains may differ at f32-rounding level and near-exact ties
can pick an adjacent threshold; assertions are therefore tolerant on
scores and validate structure via score-consistency rather than
demanding bit-equality (the reference's GPU learner has the same
relationship to its CPU learner, gpu_tree_learner.cpp:299).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.split import (FeatureMeta, SplitParams,
                                    per_feature_numerical)
from lightgbm_tpu.ops.split_scan_pallas import per_feature_numerical_pallas

F, B = 11, 64


def _mk_meta(rng, with_missing):
    return FeatureMeta(
        num_bins=jnp.asarray(rng.randint(3, B, F), jnp.int32),
        missing=jnp.asarray(
            rng.randint(0, 3 if with_missing else 1, F), jnp.int32),
        default_bin=jnp.asarray(rng.randint(0, 5, F), jnp.int32),
        most_freq_bin=jnp.zeros(F, jnp.int32),
        monotone=jnp.asarray(rng.randint(-1, 2, F), jnp.int32),
        penalty=jnp.asarray(1.0 + 0.1 * rng.rand(F), jnp.float32),
        is_categorical=jnp.zeros(F, bool),
        global_id=jnp.arange(F, dtype=jnp.int32))


def _mk_hist(rng, meta):
    hist = np.zeros((F, B, 3), np.float32)
    for f in range(F):
        nb = int(meta.num_bins[f])
        hist[f, :nb, 2] = rng.randint(0, 50, nb)
        hist[f, :nb, 0] = rng.randn(nb) * hist[f, :nb, 2]
        hist[f, :nb, 1] = np.abs(rng.randn(nb)) * hist[f, :nb, 2]
    return hist


@pytest.mark.parametrize("with_missing", [False, True])
@pytest.mark.parametrize("l1,mds", [(0.0, 0.0), (0.3, 0.5)])
def test_kernel_matches_xla_scan(with_missing, l1, mds):
    rng = np.random.RandomState(7 + int(with_missing) + int(l1 * 10))
    meta = _mk_meta(rng, with_missing)
    params = SplitParams(
        lambda_l1=l1, lambda_l2=0.5, max_delta_step=mds,
        min_data_in_leaf=5.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0, any_missing=with_missing,
        use_scan_kernel=True)
    hist = _mk_hist(rng, meta)
    # parent sums must equal each feature's own totals for a
    # self-consistent histogram; use feature 0's (others' mismatch is
    # harmless for scan math, which only uses parent minus prefix)
    pg, ph, pc = (float(hist[0, :, j].sum()) for j in range(3))
    mask = jnp.asarray(rng.rand(F) > 0.2)
    args = (jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph),
            jnp.float32(pc), meta, params, jnp.float32(-np.inf),
            jnp.float32(np.inf), mask)
    ref = per_feature_numerical(*args)
    got = per_feature_numerical_pallas(*args)

    ref_sc, got_sc = np.asarray(ref.score), np.asarray(got.score)
    # validity pattern must agree exactly
    assert np.array_equal(np.isfinite(ref_sc), np.isfinite(got_sc))
    fin = np.isfinite(ref_sc)
    np.testing.assert_allclose(got_sc[fin], ref_sc[fin],
                               rtol=5e-5, atol=1e-4)
    # thresholds: identical except where adjacent-threshold gains tie
    # at rounding level; re-check those by symmetry of the score
    thr_same = np.asarray(ref.threshold) == np.asarray(got.threshold)
    assert thr_same[fin].mean() > 0.7
    for name in ("left_output", "right_output"):
        x = np.asarray(getattr(ref, name))[fin & thr_same]
        y = np.asarray(getattr(got, name))[fin & thr_same]
        np.testing.assert_allclose(y, x, rtol=5e-5, atol=1e-4)
    x = np.asarray(ref.left_c)[fin & thr_same]
    y = np.asarray(got.left_c)[fin & thr_same]
    np.testing.assert_allclose(y, x, rtol=1e-6, atol=1e-3)
    assert np.array_equal(np.asarray(ref.default_left)[fin & thr_same],
                          np.asarray(got.default_left)[fin & thr_same])


def test_kernel_under_vmap_matches_unbatched():
    """The production path (scan_children) always calls the kernel
    under jax.vmap over both children; make sure the pallas batching
    rule gives the same answers as two unbatched calls."""
    import jax
    rng = np.random.RandomState(11)
    meta = _mk_meta(rng, True)
    params = SplitParams(
        lambda_l1=0.0, lambda_l2=0.5, max_delta_step=0.0,
        min_data_in_leaf=5.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0, any_missing=True, use_scan_kernel=True)
    h1 = _mk_hist(rng, meta)
    h2 = _mk_hist(rng, meta)
    pg, ph, pc = (float(h1[0, :, j].sum()) for j in range(3))
    mask = jnp.ones(F, bool)

    def one(hh):
        return per_feature_numerical_pallas(
            hh, jnp.float32(pg), jnp.float32(ph), jnp.float32(pc),
            meta, params, jnp.float32(-np.inf), jnp.float32(np.inf),
            mask)

    batched = jax.vmap(one)(jnp.stack([jnp.asarray(h1),
                                       jnp.asarray(h2)]))
    singles = [one(jnp.asarray(h)) for h in (h1, h2)]
    # batched execution may fuse in a different order -> ulp-level
    # drift; assert equivalence, not bit-identity
    for k in range(2):
        bs = np.asarray(batched.score)[k]
        ss = np.asarray(singles[k].score)
        assert np.array_equal(np.isfinite(bs), np.isfinite(ss))
        fin = np.isfinite(ss)
        np.testing.assert_allclose(bs[fin], ss[fin], rtol=1e-5,
                                   err_msg=f"child {k} score")
        thr_same = (np.asarray(batched.threshold)[k]
                    == np.asarray(singles[k].threshold))
        assert thr_same[fin].mean() > 0.9
        np.testing.assert_allclose(
            np.asarray(batched.left_output)[k][fin & thr_same],
            np.asarray(singles[k].left_output)[fin & thr_same],
            rtol=1e-5, err_msg=f"child {k} left_output")


def test_kernel_respects_feature_mask_and_monotone():
    rng = np.random.RandomState(3)
    meta = _mk_meta(rng, False)._replace(
        monotone=jnp.asarray([1, -1] * 5 + [0], jnp.int32))
    params = SplitParams(
        lambda_l1=0.0, lambda_l2=1.0, max_delta_step=0.0,
        min_data_in_leaf=1.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0, any_missing=False, use_scan_kernel=True)
    hist = _mk_hist(rng, meta)
    pg, ph, pc = (float(hist[0, :, j].sum()) for j in range(3))
    mask = jnp.asarray([True, False] * 5 + [True])
    got = per_feature_numerical_pallas(
        jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph),
        jnp.float32(pc), meta, params, jnp.float32(-0.5),
        jnp.float32(0.5), mask)
    sc = np.asarray(got.score)
    assert not np.isfinite(sc[1::2][:5]).any()  # masked-off features
    # constrained outputs honor the [cmin, cmax] clip
    fin = np.isfinite(sc)
    assert (np.asarray(got.left_output)[fin] >= -0.5 - 1e-6).all()
    assert (np.asarray(got.left_output)[fin] <= 0.5 + 1e-6).all()


# ---- PR 31: a wide table scans a block of features a grid step -------

def _wide_case(f, b, seed, with_missing):
    rng = np.random.RandomState(seed)
    meta = FeatureMeta(
        num_bins=jnp.asarray(rng.randint(3, b + 1, f), jnp.int32),
        missing=jnp.asarray(
            rng.randint(0, 3 if with_missing else 1, f), jnp.int32),
        default_bin=jnp.asarray(rng.randint(0, 5, f), jnp.int32),
        most_freq_bin=jnp.zeros(f, jnp.int32),
        monotone=jnp.zeros(f, jnp.int32),
        penalty=jnp.asarray(1.0 + 0.1 * rng.rand(f), jnp.float32),
        is_categorical=jnp.zeros(f, bool),
        global_id=jnp.arange(f, dtype=jnp.int32))
    # one leaf's rows binned by every feature: each feature's totals
    # are the parent's, as in a grow loop
    n = 1500
    g = rng.randn(n)
    h = 0.05 + 0.2 * rng.rand(n)
    bins = (rng.rand(f, n) * np.asarray(meta.num_bins)[:, None]
            ).astype(np.int64)
    idx = (bins + b * np.arange(f)[:, None]).ravel()
    hist = np.stack(
        [np.bincount(idx, np.tile(w, f), f * b).reshape(f, b)
         for w in (g, h, np.ones(n))], axis=-1).astype(np.float32)
    return meta, hist, jnp.asarray(rng.rand(f) > 0.1)


# one block, a block and one feature, the Epsilon table (15 blocks and
# 80 features: the table is padded to whole blocks), whole blocks
@pytest.mark.parametrize("f,b", [(128, 64), (129, 64), (2000, 256),
                                 (256, 255)])
@pytest.mark.parametrize("with_missing", [False, True])
def test_feature_blocked_scan_matches_xla_scan(f, b, with_missing):
    """``_scan_call`` past ``SCAN_BLOCK_F`` features is a grid over
    blocks of features; every feature's row scans on its own, so the
    result is the one-block kernel's, against
    ``per_feature_numerical``."""
    from lightgbm_tpu.ops.split_scan_pallas import SCAN_BLOCK_F
    assert f >= SCAN_BLOCK_F
    meta, hist, mask = _wide_case(f, b, 31 + f + with_missing,
                                  with_missing)
    params = SplitParams(
        lambda_l1=0.0, lambda_l2=0.5, max_delta_step=0.0,
        min_data_in_leaf=5.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0, any_missing=with_missing,
        use_scan_kernel=True)
    pg, ph, pc = (float(hist[0, :, j].sum()) for j in range(3))
    args = (jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph),
            jnp.float32(pc), meta, params, jnp.float32(-np.inf),
            jnp.float32(np.inf), mask)
    ref = per_feature_numerical(*args)
    got = per_feature_numerical_pallas(*args)
    ref_sc, got_sc = np.asarray(ref.score), np.asarray(got.score)
    assert got_sc.shape == (f,)
    assert np.array_equal(np.isfinite(ref_sc), np.isfinite(got_sc))
    fin = np.isfinite(ref_sc)
    assert fin.sum() > f // 2
    np.testing.assert_allclose(got_sc[fin], ref_sc[fin],
                               rtol=5e-5, atol=1e-4)
    thr_same = np.asarray(ref.threshold) == np.asarray(got.threshold)
    assert thr_same[fin].mean() > 0.7
    for name in ("left_output", "right_output", "left_g"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, name))[fin & thr_same],
            np.asarray(getattr(ref, name))[fin & thr_same],
            rtol=5e-5, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(got.left_c)[fin & thr_same],
        np.asarray(ref.left_c)[fin & thr_same])


def test_feature_blocked_scan_under_vmap():
    """Both children in one call, as the grow loop scans them."""
    import jax
    f, b = 300, 64
    meta, h1, mask = _wide_case(f, b, 5, True)
    _, h2, _ = _wide_case(f, b, 6, True)
    params = SplitParams(
        lambda_l1=0.0, lambda_l2=0.5, max_delta_step=0.0,
        min_data_in_leaf=5.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0, any_missing=True, use_scan_kernel=True)
    pg, ph, pc = (float(h1[0, :, j].sum()) for j in range(3))

    def one(hh):
        return per_feature_numerical_pallas(
            hh, jnp.float32(pg), jnp.float32(ph), jnp.float32(pc),
            meta, params, jnp.float32(-np.inf), jnp.float32(np.inf),
            mask)

    batched = jax.vmap(one)(jnp.stack([jnp.asarray(h1),
                                       jnp.asarray(h2)]))
    for k, h in enumerate((h1, h2)):
        single = one(jnp.asarray(h))
        bs, ss = np.asarray(batched.score)[k], np.asarray(single.score)
        assert np.array_equal(np.isfinite(bs), np.isfinite(ss))
        fin = np.isfinite(ss)
        np.testing.assert_allclose(bs[fin], ss[fin], rtol=1e-5)
        assert (np.asarray(batched.threshold)[k]
                == np.asarray(single.threshold))[fin].mean() > 0.9
