"""Pallas kernel tests (interpret mode on CPU).

Mirrors the reference's GPU_DEBUG_COMPARE cross-check
(gpu_tree_learner.cpp:993-1031): the device kernels are validated
against the plain-XLA scatter histogram and a literal numpy partition.
"""

import functools

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import histogram_scatter, make_ghc
from lightgbm_tpu.ops.hist_pallas import (build_matrix, extract_row_ids,
                                          histogram_segment, pack_gh)
from lightgbm_tpu.ops.partition_pallas import (bitset_to_lut,
                                               partition_segment)


@functools.lru_cache(maxsize=None)
def _packed(f=12, b=64):
    rng = np.random.RandomState(0)
    n = 3000
    binned = rng.randint(0, b, (n, f)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = np.abs(rng.randn(n)).astype(np.float32) + 0.1
    bag = (rng.rand(n) < 0.8).astype(np.float32)
    ghc = make_ghc(jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(bag))
    mat = pack_gh(build_matrix(jnp.asarray(binned)), f,
                  ghc[:, 0], ghc[:, 1], ghc[:, 2])
    return binned, ghc, mat, n, f, b


@pytest.fixture(scope="module")
def packed():
    return _packed()


_last_programs = [None]


@pytest.fixture(autouse=True)
def _drop_compiled_programs_between_widths(request):
    """An interpret-mode histogram program is large (its body unrolls
    over the features) and this file compiles one a width: drop the
    compiled ones when the test or its width changes, as conftest.py
    does between modules and for the same reason (this sandbox's
    XLA:CPU segfaults in a later compile once a process holds too
    many)."""
    import jax
    spec = getattr(request.node, "callspec", None)
    key = (request.function.__name__, spec and spec.params.get("f"))
    if key != _last_programs[0]:
        jax.clear_caches()
    _last_programs[0] = key


# ---- one histogram form at every width ------------------------------
# ``histogram_segment`` is the one-hot stream whoever asks: whole rows
# up to ``MAX_FUSED_F`` (192) columns, a ``SLICE_F``-column (128) slice
# at a time past it. The widths: the small fixture; the cells' 28
# (Higgs), 40 (Expo, 256 bins) and 67 (Criteo); 68 | 69, where the
# deleted nibble kernel stopped compiling; 115 | 116, where the
# payload's 13 columns leave the first lane tile; 128 | 129, one tile
# of bins and one column more; 192 | 193, the two sides of the bound
# (193: two slices, the second nearly empty, the payload in it); 300
# (no multiple of the slice, the payload past a tile boundary) and
# the Epsilon table's 2,000 (15 slices and 80 columns, the payload in
# the last slice's own tile).
SEGMENT_WIDTHS = [(12, 64), (28, 255), (40, 256), (67, 255), (68, 255),
                  (69, 255), (115, 255), (116, 255), (128, 255),
                  (129, 255), (192, 255), (193, 255), (300, 255),
                  (2000, 255)]


@functools.lru_cache(maxsize=None)
def _packed_wide(f, b=255, n=1300):
    rng = np.random.RandomState(f)
    binned = rng.randint(0, b, (n, f)).astype(np.uint8)
    ghc = make_ghc(
        jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.asarray(np.abs(rng.randn(n)).astype(np.float32) + 0.1),
        jnp.asarray((rng.rand(n) < 0.8).astype(np.float32)))
    mat = pack_gh(build_matrix(jnp.asarray(binned)), f,
                  ghc[:, 0], ghc[:, 1], ghc[:, 2])
    return binned, ghc, mat


def _scatter(binned, ghc, begin, count, b):
    if not count:
        return np.zeros((binned.shape[1], b, 3), np.float32)
    return np.asarray(histogram_scatter(
        jnp.asarray(binned[begin:begin + count]),
        ghc[begin:begin + count], b))


@pytest.mark.parametrize("begin,count", [(0, 1300), (517, 700),
                                         (1299, 1), (100, 0)],
                         ids=["whole", "unaligned-ragged", "one-row",
                              "no-row"])
@pytest.mark.parametrize("f,b", SEGMENT_WIDTHS)
def test_histogram_segment_matches_scatter(f, b, begin, count):
    """``histogram_segment`` against ``ops/histogram.py`` at every
    width: exactly the table's columns land in the result (a slice's
    columns in their own rows, those past the width cut off), rows
    before ``begin`` in its granule and past the count are masked,
    neighbours' rows stay out; and the call is counted as one slice up
    to the bound and ``ceil(F / SLICE_F)`` past it."""
    from lightgbm_tpu.observability.telemetry import get_telemetry
    from lightgbm_tpu.ops.hist_pallas import MAX_FUSED_F, SLICE_F
    binned, ghc, mat = _packed_wide(f, b)
    tel = get_telemetry()
    tel.ensure_ring()
    slices0 = tel.counters.get("kernels.hist_feature_slices", 0)
    seg = np.asarray(histogram_segment(mat, begin, count, b, f,
                                       interpret=True))
    assert tel.counters["kernels.hist_feature_slices"] - slices0 \
        == (1 if f <= MAX_FUSED_F else -(-f // SLICE_F))
    assert seg.shape == (f, b, 3)
    ref = _scatter(binned, ghc, begin, count, b)
    assert np.abs(ref - seg).max() < 2e-3
    # counts are sums of 0/1: exact, so no neighbour's row and no
    # other slice's column leaked in
    np.testing.assert_array_equal(seg[..., 2], ref[..., 2])


# ---- the whole-row stream's program, as the megakernel holds it ------

def _child_stream(f, b, blk):
    """The registered program a width and block size; the segment stays
    dynamic, as it is inside the megakernel."""
    from lightgbm_tpu.ops.hist_pallas import histogram_child_stream
    return functools.partial(
        histogram_child_stream, num_bins=b, num_features=f, blk=blk,
        interpret=True)


# a parent [40, 2040) split at NL = 700: its left child starts where
# the parent does, its right child ends where the parent does
_PARENT = (40, 2000, 700)
CHILD_SEGMENTS = [
    pytest.param(0, 3000, id="whole"),
    pytest.param(517, 1234, id="unaligned-ragged"),
    pytest.param(8, 1500, id="aligned-ragged"),
    pytest.param(13, 300, id="one-block"),
    pytest.param(5, 1024, id="empty-tail"),
    pytest.param(2999, 1, id="one-row"),
    pytest.param(100, 0, id="no-row"),
    pytest.param(_PARENT[0], _PARENT[2], id="left-child"),
    pytest.param(_PARENT[0] + _PARENT[2], _PARENT[1] - _PARENT[2],
                 id="right-child"),
]


@pytest.mark.parametrize("begin,count", CHILD_SEGMENTS)
@pytest.mark.parametrize("f,b,blk", [(28, 255, 512), (67, 255, 512),
                                     (28, 256, 256)])
def test_hist_child_stream_matches_scatter(f, b, blk, begin, count):
    """``hist_pallas.histogram_child_stream`` against
    ``ops/histogram.py`` over segments that span several blocks: rows
    before ``begin`` in its granule and rows past the count are masked
    through the payload, the tail block is short, neighbours' rows stay
    out."""
    binned, ghc, mat, n, f, b = _packed(f, b)
    seg = np.asarray(_child_stream(f, b, blk)(mat, begin, count))
    assert seg.shape == (f, b, 3)
    ref = _scatter(binned, ghc, begin, count, b)
    assert np.abs(ref - seg).max() < 2e-3
    # counts are sums of 0/1: exact, so no neighbour's row leaked in
    np.testing.assert_array_equal(seg[..., 2], ref[..., 2])


@pytest.mark.parametrize("blk,stream_blk", [(256, 256), (512, 512),
                                            (2048, 512)])
@pytest.mark.parametrize("f,program", [
    (12, "histogram_child_stream"), (193, "_histogram_segment_slices")])
def test_the_streams_row_block_follows_the_matrix(monkeypatch, f, program,
                                                  blk, stream_blk):
    """``blk`` is the row block the matrix was padded for: the stream,
    whole rows or slices, takes ``SLICE_BLK`` rows a block where that
    fits and ``blk`` where the matrix has less slack, so no window
    leaves the matrix; the histogram is the same."""
    import lightgbm_tpu.ops.hist_pallas as hp
    b, n = 255, 700
    rng = np.random.RandomState(blk)
    binned = rng.randint(0, b, (n, f)).astype(np.int32)
    ghc = jnp.asarray(np.stack(
        [rng.randn(n), rng.rand(n) + 0.1, np.ones(n)], 1).astype(np.float32))
    mat = pack_gh(build_matrix(jnp.asarray(binned), blk), f,
                  ghc[:, 0], ghc[:, 1], ghc[:, 2])
    seen = []
    plain = getattr(hp, program)
    monkeypatch.setattr(
        hp, program,
        lambda *a, **kw: seen.append(kw["blk"]) or plain(*a, **kw))
    seg = np.asarray(hp.histogram_segment(mat, 3, 600, b, f, blk=blk,
                                          interpret=True))
    assert seen == [stream_blk]
    ref = _scatter(binned, ghc, 3, 600, b)
    assert np.abs(ref - seg).max() < 2e-3
    np.testing.assert_array_equal(seg[..., 2], ref[..., 2])


def test_partition_stable_and_payload(packed):
    binned, ghc, mat, n, f, b = packed
    ws = jnp.zeros_like(mat)
    zlut = jnp.zeros((1, 256), jnp.float32)
    begin, count, feat, thr = 100, 2500, 3, 20
    mat2, ws2, nl = partition_segment(
        mat, ws, begin, count, feat, thr, 0, 0, 0, b, 0, zlut,
        interpret=True)
    nl = int(nl[0])
    ids = np.arange(begin, begin + count)
    left = binned[ids, feat] <= thr
    ref_ids = np.concatenate([ids[left], ids[~left]])
    got = np.asarray(extract_row_ids(mat2, f, n))
    assert nl == int(left.sum())
    assert (got[begin:begin + count] == ref_ids).all()
    assert (got[:begin] == np.arange(begin)).all()
    assert (got[begin + count:] == np.arange(begin + count, n)).all()
    # gh payload moved with its rows: grad bytes decode to grad[row id]
    mat_np = np.asarray(mat2)
    gb = mat_np[:n, f:f + 4].astype(np.uint32)
    g_rec = (gb[:, 0] | (gb[:, 1] << 8) | (gb[:, 2] << 16)
             | (gb[:, 3] << 24)).view(np.float32)
    assert np.array_equal(g_rec, np.asarray(ghc[:, 0])[got])


def test_partition_no_lut_path_matches(packed):
    # the static use_lut_path=False compile (cat-free unbundled
    # datasets) must partition identically on numerical splits
    binned, ghc, mat, n, f, b = packed
    ws = jnp.zeros_like(mat)
    zlut = jnp.zeros((1, 256), jnp.float32)
    begin, count, feat, thr = 100, 2500, 3, 20
    m1, _, nl1 = partition_segment(
        mat, ws, begin, count, feat, thr, 0, 0, 0, b, 0, zlut,
        interpret=True)
    m2, _, nl2 = partition_segment(
        mat, ws, begin, count, feat, thr, 0, 0, 0, b, 0, zlut,
        interpret=True, use_lut_path=False)
    assert int(nl1[0]) == int(nl2[0])
    assert np.array_equal(np.asarray(m1), np.asarray(m2))


def test_partition_categorical_bitset(packed):
    binned, ghc, mat, n, f, b = packed
    ws = jnp.zeros_like(mat)
    cats = [1, 7, 13, 40]
    bits = np.zeros(8, np.uint32)
    for c in cats:
        bits[c // 32] |= np.uint32(1 << (c % 32))
    lut = bitset_to_lut(jnp.asarray(bits))
    mat2, _, nl = partition_segment(
        mat, ws, 0, n, 5, 0, 0, 0, 0, b, 1, lut, interpret=True)
    left = np.isin(binned[:, 5], cats)
    assert int(nl[0]) == int(left.sum())
    got = np.asarray(extract_row_ids(mat2, f, n))
    ref = np.concatenate([np.arange(n)[left], np.arange(n)[~left]])
    assert (got[:n] == ref).all()


def _grow_both(X, y, params, cat=()):  # -> (serial tree, partitioned tree)
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    cfg = Config.from_params(dict(params, objective="binary",
                                  verbosity=-1))
    ds = Dataset.from_numpy(X, cfg, label=y, categorical_features=cat)
    n = len(y)
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((n,), 0.25, jnp.float32)
    s = SerialTreeLearner(ds, cfg)
    p = PartitionedTreeLearner(ds, cfg, interpret=True)
    rs, rp = s.train(grad, hess), p.train(grad, hess)
    return (s.to_host_tree(rs), p.to_host_tree(rp),
            np.asarray(rs.leaf_id), np.asarray(rp.leaf_id))


def test_partitioned_learner_matches_serial():
    rng = np.random.RandomState(1)
    n = 600
    X = rng.randn(n, 6)
    X[rng.rand(n) < 0.1, 2] = np.nan  # exercise NaN-missing partition
    y = (1.5 * X[:, 0] - X[:, 1] + 0.3 * rng.randn(n) > 0).astype(
        np.float32)
    ts, tp, ls, lp = _grow_both(X, y, {"num_leaves": 7})
    assert ts.num_leaves == tp.num_leaves
    assert np.array_equal(ts.split_feature_inner, tp.split_feature_inner)
    assert np.array_equal(ts.threshold_bin, tp.threshold_bin)
    assert np.allclose(ts.leaf_value, tp.leaf_value, atol=1e-4)
    assert np.array_equal(ls, lp)


def test_partitioned_learner_matches_serial_categorical():
    rng = np.random.RandomState(2)
    n = 800
    cats = rng.randint(0, 10, n)
    y = np.isin(cats, [1, 4, 7]).astype(np.float32)
    X = np.stack([cats.astype(float), rng.randn(n)], axis=1)
    ts, tp, ls, lp = _grow_both(
        X, y, {"num_leaves": 5, "min_data_per_group": 5}, cat=[0])
    assert ts.num_leaves == tp.num_leaves
    assert np.array_equal(ts.split_feature_inner, tp.split_feature_inner)
    assert np.allclose(ts.leaf_value, tp.leaf_value, atol=1e-4)
    assert np.array_equal(ls, lp)


def test_gbdt_with_partitioned_learner():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    rng = np.random.RandomState(3)
    n = 800
    X = rng.randn(n, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 7, "num_iterations": 5,
        "tree_learner": "partitioned", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    booster = GBDT(cfg, ds)
    booster.train()
    from sklearn.metrics import roc_auc_score
    auc = roc_auc_score(y, np.asarray(booster.predict_raw(X)).ravel())
    assert auc > 0.9
