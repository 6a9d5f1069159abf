"""chip_smoke.py off the chip: the entry point refuses a CPU, and its
stage functions run end to end at a tiny size (about 2k rows, 15
leaves, kernels in interpret mode) on the CPU mesh.

The chip run itself is the builder's and the driver's; this file keeps
the script's plumbing — path report, serving checks, sharding checks —
from rotting between chip runs.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

TINY = dict(cs.PARAMS, num_leaves=15)


@pytest.fixture
def fuse_iters(monkeypatch):
    # the fused-scan driver is the TPU default; this existing switch
    # turns it on for the CPU backend
    monkeypatch.setenv("LGBM_TPU_FUSE_ITERS", "1")


def test_entry_point_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stderr
    # no report, no result object: nothing on stdout at all
    assert proc.stdout == ""


def test_result_line_is_exactly_ok_and_device():
    """What the driver parses off the last line of stdout: these keys
    and no others (the observations ride on the ``report:`` line)."""
    got = json.loads(cs.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "versions": {"jax": "0.9.0"}}))
    assert got == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_train_and_serve_stages_tiny(fuse_iters):
    x, y = cs.higgs_like(2000)
    # auto is TPU-only; forcing the interpret twin on shows the report
    # reads the megakernel from the trace, not from the gate
    bst, report = cs.stage_train(
        x, y, dict(TINY, tree_learner="partitioned",
                   fused_split_kernel="on"), cs.ROUNDS,
        learner="PartitionedTreeLearner", interpret=True,
        megakernel=True)
    assert report["fused_block_hits"] == 1     # 1 sync + one block of 16
    # the megakernel's interpret twin partitions by a prefix-sum
    # permutation: only a compiled phase 0 traces the pipelined stream
    assert report["partition_pipelined"] == 0
    assert report["partition_one_compaction"] == 0
    # the twin's histograms are ``histogram_segment``'s, the one-hot
    # stream in interpret mode: counted per kernel trace, like the
    # categorical stage's ``partition_pipelined`` below
    assert isinstance(report["hist_child_stream"], int)
    serve = cs.stage_serve(bst, x, sizes=(1, 512))
    assert [r["route"] for r in serve["requests"]] == ["device"] * 2
    assert serve["fallbacks"] == 0
    assert serve["bucket_misses_serving"] == 0


def test_categorical_stage_tiny(fuse_iters):
    """The stage of the table the compiled megakernel refuses: the
    per-phase route with the bitset partition and the categorical scan,
    in fused blocks, its host trees against the device's scores."""
    x, y, params = cs.expo_like(3000)
    assert params["categorical_feature"] == "0,1,2,3,4,5,6,7,8,9,10,11"
    params = dict(params, num_leaves=15, tree_learner="partitioned")
    _, report = cs.stage_train(x, y, params, cs.CAT_ROUNDS,
                               learner="PartitionedTreeLearner",
                               interpret=True, megakernel=False,
                               categorical=True)
    assert report["fused_block_hits"] == 1      # 1 sync + one block of 8
    assert report["lut_partition"] == "on" and report["cat_scan"] == "on"
    # partition_segment's kernel is the pipelined stream on every
    # platform: counted per kernel trace, so 0 only where an earlier
    # test of this process already traced the same shapes
    assert isinstance(report["partition_pipelined"], int)
    # PR 34: one compaction a stream traced (counted where the
    # compaction enters the trace, not where the stream does)
    assert report["partition_one_compaction"] \
        == report["partition_pipelined"]
    assert len(report["model_sha256"]) == 16
    assert report["cat_splits"] > 0
    foil = cs.stage_foil(x, y, params, cs.CAT_ROUNDS)
    assert abs(report["auc"] - foil["auc"]) <= cs.CAT_FOIL_AUC_TOL


def test_wide_stage_tiny(fuse_iters):
    """The stage of the table too wide for the megakernel (2,000
    columns): the per-phase route with every histogram call cut into
    column slices, in fused blocks, against the scatter foil. Off a
    TPU the plan never refuses for width (``auto`` picks no megakernel
    there), so ``wide_table`` reads off."""
    x, y = cs.epsilon_like(1200)
    assert x.shape == (1200, 2000)
    params = dict(TINY, tree_learner="partitioned")
    _, report = cs.stage_train(x, y, params, cs.WIDE_ROUNDS,
                               learner="PartitionedTreeLearner",
                               interpret=True, megakernel=False,
                               wide=True)
    assert report["fused_block_hits"] == 1      # 1 sync + one block of 4
    assert report["wide_table"] == "off"
    assert report["hist_feature_slices"] % 16 == 0
    foil = cs.stage_foil(x, y, params, cs.WIDE_ROUNDS,
                         hist_method="scatter")
    assert abs(report["auc"] - foil["auc"]) <= cs.WIDE_FOIL_AUC_TOL


def test_bundled_stage_tiny(fuse_iters):
    """The stage of the sparse one-hot table (ISSUE 33): a scipy CSR
    of the benchmark's 4,228 columns, bundled by the dataset into a few
    byte columns, through the bundled per-phase body in fused blocks,
    against the XLA foil on the same bundled table."""
    x, y = cs.allstate_like(4000)
    assert x.shape == (4000, 4228) and x.nnz == 4000 * 33
    params = dict(TINY, tree_learner="partitioned")
    _, report = cs.stage_train(x, y, params, cs.ONEHOT_ROUNDS,
                               learner="PartitionedTreeLearner",
                               interpret=True, megakernel=False,
                               bundled=True, min_auc=cs.ONEHOT_MIN_AUC)
    assert report["fused_block_hits"] == 1      # 1 sync + one block of 4
    assert report["bundled"] == "on" and report["lut_partition"] == "on"
    assert report["bundle_conflict_rows"] == 0
    foil = cs.stage_foil(x, y, params, cs.ONEHOT_ROUNDS)
    assert abs(report["auc"] - foil["auc"]) <= cs.ONEHOT_FOIL_AUC_TOL


def test_rank_stage_tiny(fuse_iters):
    """The stage of the learning-to-rank table (ISSUE 37): ragged query
    groups, lambdarank on the query layout, the megakernel's interpret
    twin at the benchmark's 137 columns, in fused blocks."""
    x, y, sizes, params = cs.msltr_like(3000)
    assert x.shape == (3000, 137) and sizes.sum() == 3000
    assert sizes.max() == 1251 and params["objective"] == "lambdarank"
    params = dict(params, num_leaves=7, tree_learner="partitioned",
                  fused_split_kernel="on")
    _, report = cs.stage_train(x, y, params, cs.RANK_ROUNDS,
                               learner="PartitionedTreeLearner",
                               interpret=True, megakernel=True,
                               min_auc=cs.RANK_MIN_AUC, group=sizes)
    assert report["fused_block_hits"] == 1      # 1 sync + one block of 4
    assert report["rank_docs"] == 3000 and report["rank_queries"] == 25
    assert report["rank_slots"] <= 1.6 * 3000
    assert report["ndcg10"] >= cs.RANK_MIN_NDCG
    assert len(report["model_sha256"]) == 16


@pytest.mark.slow
def test_kernel_and_foil_stages_tiny(fuse_iters):
    kernels = cs.stage_kernels(
        interpret=True,
        hist=dict(shapes=((2100, 28, 256),)),
        partition_v1=dict(shapes=((2100, 28, 256),)),
        split_scan=dict(shapes=((28, 256, False),)),
        fused_split=dict(rows=1500, features=28, leaves=7),
        debundle=dict(columns=9, numeric=3, indicators=300))
    assert set(kernels) == {"hist", "partition_v1", "split_scan",
                            "fused_split", "debundle"}
    x, y = cs.higgs_like(2000)
    params = dict(TINY, tree_learner="partitioned")
    _, report = cs.stage_train(x, y, params, cs.ROUNDS,
                               learner="PartitionedTreeLearner",
                               interpret=True, megakernel=False)
    foil = cs.stage_foil(x, y, params, cs.ROUNDS)
    assert abs(report["auc"] - foil["auc"]) <= cs.FOIL_AUC_TOL


@pytest.mark.slow
def test_four_shard_stage_tiny(fuse_iters, monkeypatch):
    """``--devices 4`` on the virtual CPU mesh: make the learner
    factory route data-parallel onto the mesh segment-kernel learner
    as it does on a TPU (the kernels stay in interpret mode)."""
    import lightgbm_tpu.parallel.learners as learners
    monkeypatch.setattr(learners, "on_tpu", lambda: True)
    x, y = cs.higgs_like(4000)
    bst, report = cs.stage_train(
        x, y, dict(TINY, tree_learner="data", num_machines=4),
        cs.ROUNDS, learner="MeshPartitionedTreeLearner",
        interpret=True, megakernel=False, shards=4)
    shards = cs.stage_shards(bst, 4000, 4)
    assert shards["shard_devices"] == [0, 1, 2, 3]


def test_data_parallel_stage_tiny(fuse_iters, monkeypatch):
    """The four-chip stage at the Criteo width on the virtual CPU mesh
    (the factory routed as on a TPU, kernels in interpret mode): the
    table binned a shard a worker, trained over four shards, and the
    three collectives counted with the bytes a chip sends; skipped on
    a host of fewer chips."""
    import lightgbm_tpu.parallel.learners as learners
    assert "skipped" in cs.stage_data_parallel(1)
    monkeypatch.setattr(learners, "on_tpu", lambda: True)
    report = cs.stage_data_parallel(4, rows=3000, params=TINY,
                                    interpret=True)
    assert report["learner"] == "MeshPartitionedTreeLearner"
    assert report["num_shards"] == 4 and report["trees"] == cs.DP_ROUNDS
    assert report["shards"]["shard_devices"] == [0, 1, 2, 3]
    assert report["comm"]["comm.psum_scatter_calls"] >= 1
    assert len(report["model_sha256"]) == 16
