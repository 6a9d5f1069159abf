"""The data-parallel learner on four of the virtual CPU devices: what a
four-chip host runs (``MeshPartitionedTreeLearner``, its kernels in
interpret mode), with the table built a row shard a worker and the
metadata handed to the mesh programs as an argument.

* ``engine.train`` with ``tree_learner=data`` over four shards grows the
  trees the plain float64 reference grows;
* two tables of one shape lower the mesh fused block to one text (a
  program that holds its table's metadata does not);
* the table binned a shard a worker is the one-worker table, bit for
  bit, and the learner's matrices are its rows and their ids as a plain
  loop lays them out; a one-shard table keeps one worker;
* the collectives run under their device scopes and count the bytes a
  chip sends of them.
"""

import re
import threading

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.parallel.learners as learners
from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.data.binning import BinMapper
from lightgbm_tpu.observability import scopes
from lightgbm_tpu.observability.telemetry import get_telemetry
from lightgbm_tpu.parallel import ingest

SHARDS = 4
DP = {"objective": "binary", "tree_learner": "data",
      "num_machines": SHARDS, "verbosity": -1}


@pytest.fixture
def on_chip_route(monkeypatch):
    """The learner factory routes data-parallel onto the mesh
    segment-kernel learner as on a TPU; the kernels stay in interpret
    mode."""
    monkeypatch.setattr(learners, "on_tpu", lambda: True)


def _table(rows, features, seed, shift=0.0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, features)).astype(np.float32) + shift
    logit = 2.0 * (x[:, 0] - shift) - 1.5 * (x[:, 1] - shift) \
        + (x[:, 2] - shift) * (x[:, 3] - shift)
    y = (logit + rng.standard_normal(rows) > 0).astype(np.float32)
    return x, y


def _split_list(tree):
    """``(leaf, feature, threshold, rows)`` of each split in the order
    they were made: a split keeps the leaf it split on its left, so the
    leaf of split ``i`` is the one found by going left from it."""
    out = []
    for i in range(int(tree.num_leaves) - 1):
        c = i
        while c >= 0:
            c = int(tree.left_child[c])
        out.append((~c, int(tree.split_feature_inner[i]),
                    int(tree.threshold_bin[i]),
                    int(tree.internal_count[i])))
    return out


def test_data_parallel_booster_grows_the_reference_trees(on_chip_route):
    """20,000 x 67, 31 leaves, three trees through ``engine.train`` over
    four shards: every split's leaf, feature, threshold and rows are the
    plain float64 reference's, and every gain is within 1e-4 of the
    split's two score terms (the program sums histograms in float32
    from gradients split into two bfloat16 halves)."""
    from benchmarks.reference import gbdt_cat_numpy
    x, y = _table(20_000, 67, seed=3)
    params = dict(DP, num_leaves=31, learning_rate=0.1)
    ds = lgb.Dataset(x, label=y, params=dict(params))
    bst = lgb.train(dict(params), ds, num_boost_round=3)
    learner = bst._gbdt.learner
    assert type(learner).__name__ == "MeshPartitionedTreeLearner"
    assert learner.num_shards == SHARDS
    inner = ds._inner
    forest = []
    gbdt_cat_numpy.train(inner.binned, inner.num_bins_array(),
                         np.asarray(inner.metadata.label), params, 3,
                         forest=forest)
    models = bst._gbdt.models
    assert len(models) == len(forest) == 3
    for tree, ref in zip(models, forest):
        mine = _split_list(tree)
        theirs = [(s["leaf"], s["feature"], s["threshold"], s["rows"])
                  for s in ref["splits"]]
        assert mine == theirs
        # each leaf holds the rows its path sends there
        leaf = np.zeros(len(x), np.int64)
        for i, (lf, f, t, _) in enumerate(mine):
            leaf[(leaf == lf) & (inner.binned[:, f] > t)] = i + 1
        np.testing.assert_array_equal(
            np.bincount(leaf, minlength=int(tree.num_leaves)),
            np.asarray(tree.leaf_count))
        for gain, s in zip(np.asarray(tree.split_gain), ref["splits"]):
            assert abs(float(gain) - s["gain"]) <= 1e-4 * s["terms"]


def _fused_text(x, y, params):
    bst = lgb.Booster(dict(params), lgb.Dataset(x, label=y,
                                                params=dict(params)))
    g = bst._gbdt
    assert type(g.learner).__name__ == "MeshPartitionedTreeLearner"
    return g._fused_block().trace(*g._fused_block_args(),
                                  m=1).lower().as_text()


def test_two_tables_lower_the_mesh_fused_block_to_one_text(
        on_chip_route, monkeypatch):
    """The second table's bins differ in every column (shifted by one
    standard deviation: its default and most frequent bins move), and
    the program is the same text; with the metadata closed over as the
    learner held it before, the texts part."""
    params = dict(DP, num_leaves=7, min_data_in_leaf=5)
    texts = [_fused_text(*_table(1024, 9, seed, shift), params)
             for seed, shift in ((0, 0.0), (1, 1.0))]
    assert texts[0] == texts[1]
    plain = learners.MeshPartitionedTreeLearner.traceable_grow
    monkeypatch.setattr(learners.MeshPartitionedTreeLearner,
                        "grow_operands", lambda self: None)
    monkeypatch.setattr(
        learners.MeshPartitionedTreeLearner, "traceable_grow",
        lambda self, *a, meta, **k: plain(self, *a, meta=self.meta, **k))
    closed = [_fused_text(*_table(1024, 9, seed, shift), params)
              for seed, shift in ((0, 0.0), (1, 1.0))]
    assert closed[0] != closed[1]


def test_collectives_run_under_their_scopes_and_count_sent_bytes(
        on_chip_route):
    """The per-split reduce-scatter and winner gather sit under
    ``lgbm.grow.splits.collective``, the root's psum under
    ``lgbm.grow.root.collective``; a chip's ring share of each is
    counted beside its payload."""
    tel = get_telemetry()
    tel.reset()
    tel.ensure_ring()
    params = dict(DP, num_leaves=7, min_data_in_leaf=5)
    x, y = _table(1024, 9, seed=0)
    bst = lgb.Booster(dict(params), lgb.Dataset(x, label=y,
                                                params=dict(params)))
    g = bst._gbdt
    text = g._fused_block().lower(*g._fused_block_args(), m=1) \
        .compile().as_text()
    table = scopes.parse_hlo_scopes(text)
    kinds = set()
    for line in text.splitlines():
        op = re.search(r"\s(all-reduce|reduce-scatter|all-gather)"
                       r"(?:-start)?\(", line)
        name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=", line)
        if op and name:
            kinds.add((op.group(1), table.get(name.group(1))))
    assert ("reduce-scatter", scopes.SPLITS_COLLECTIVE) in kinds
    assert ("all-gather", scopes.SPLITS_COLLECTIVE) in kinds
    assert ("all-reduce", scopes.ROOT_COLLECTIVE) in kinds
    c = tel.counters
    d = SHARDS
    assert c["comm.psum_scatter_sent_bytes"] \
        == c["comm.psum_scatter_bytes"] * (d - 1) / d
    assert c["comm.psum_sent_bytes"] == c["comm.psum_bytes"] * 2 * (d - 1) / d
    assert c["comm.all_gather_sent_bytes"] \
        == c["comm.all_gather_bytes"] * (d - 1)
    tel.reset()


def _bins_by_thread(monkeypatch):
    """The threads that bin, recorded as ``values_to_bins`` runs."""
    threads = []
    plain = BinMapper.values_to_bins

    def recording(self, values):
        threads.append(threading.current_thread())
        return plain(self, values)
    monkeypatch.setattr(BinMapper, "values_to_bins", recording)
    return threads


@pytest.mark.parametrize("rows", [10_000, 10_003, 3])
def test_the_sharded_table_is_the_one_worker_table(monkeypatch, rows):
    """Four shards, four workers, each binning its rows into the
    dataset's matrix: the bins are the one worker's bit for bit, and
    each worker leaves a ``lgbm.data.bin_rows`` span with its shard
    under ``lgbm.data.construct``. Row counts that four divide, that it
    does not, and fewer rows than shards."""
    x, y = _table(rows, 11, seed=5)
    one = Dataset.from_numpy(x, Config.from_params(
        {"objective": "binary", "verbosity": -1}), label=y)
    tel = get_telemetry()
    tel.reset()
    tel.ensure_ring()
    threads = _bins_by_thread(monkeypatch)
    sharded = Dataset.from_numpy(x, Config.from_params(DP), label=y)
    assert ingest.row_shards(Config.from_params(DP)) == SHARDS
    np.testing.assert_array_equal(sharded.binned, one.binned)
    spans = [r for r in tel.records if r.get("kind") == "span"
             and r["name"] == scopes.DATA_BIN_ROWS]
    assert sorted(r["shard"] for r in spans) == list(range(SHARDS))
    assert {r["parent"] for r in spans} == {scopes.DATA_CONSTRUCT}
    assert sum(r["rows"] for r in spans) == rows
    if rows >= 100:
        # the caller's own calls are the bin search's
        workers = [t for t in threads if t is not threading.current_thread()]
        assert len(set(workers)) == SHARDS
        assert len(workers) == SHARDS * x.shape[1]
    tel.reset()


def _plain_blocks(binned, d):
    """Each shard's training matrix built row range by row range: its
    rows' bins first, every row's global id in four bytes after them."""
    from lightgbm_tpu.learner.partitioned import HIST_BLK
    from lightgbm_tpu.ops.hist_pallas import RID_OFF, matrix_cols, matrix_rows
    n, g = binned.shape
    n_local = -(-n // d)
    mats = np.zeros((d, matrix_rows(n_local, HIST_BLK), matrix_cols(g)),
                    np.uint8)
    for s in range(d):
        lo, hi = s * n_local, min((s + 1) * n_local, n)
        if hi > lo:
            mats[s, :hi - lo, :g] = binned[lo:hi]
        rid = (s * n_local + np.arange(n_local)).astype(np.uint32)
        for k in range(4):
            mats[s, :n_local, g + RID_OFF + k] = (rid >> (8 * k)) & 0xFF
    return mats


def test_the_mesh_learner_trains_on_the_blocks_it_was_binned_into(
        on_chip_route):
    """The learner's device matrix, filled a shard a worker, is each
    shard's rows and their global ids as a plain loop lays them out."""
    x, y = _table(5003, 11, seed=6)
    ds = lgb.Dataset(x, label=y, params=dict(DP)).construct()
    bst = lgb.Booster(dict(DP), ds)
    got = np.asarray(jax.device_get(bst._gbdt.learner.mat))
    want = _plain_blocks(ds._inner.binned, SHARDS)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        ingest.row_blocks_of(ds._inner.binned, SHARDS), want)


def test_a_one_shard_table_keeps_one_worker(monkeypatch):
    """One shard (the serial learner, or data-parallel over one
    machine): the caller's thread bins every column, one
    ``lgbm.data.bin_rows`` span with no shard."""
    x, y = _table(4000, 11, seed=7)
    for params in ({"objective": "binary", "verbosity": -1},
                   dict(DP, num_machines=1, n_devices=1)):
        cfg = Config.from_params(params)
        assert ingest.row_shards(cfg) == 1
        tel = get_telemetry()
        tel.reset()
        tel.ensure_ring()
        threads = _bins_by_thread(monkeypatch)
        Dataset.from_numpy(x, cfg, label=y)
        assert set(threads) == {threading.current_thread()}
        spans = [r for r in tel.records if r.get("kind") == "span"
                 and r["name"] == scopes.DATA_BIN_ROWS]
        assert len(spans) == 1 and "shard" not in spans[0]
        monkeypatch.undo()
        tel.reset()
