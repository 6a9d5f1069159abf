"""The four-chip data-parallel cell (``criteo-dp4-26m-train``:
``kinds/train.py``'s run through ``kinds/train_dp.py``) end to end on
four virtual CPU devices at a tiny size, its probe against a program
whose mesh block holds its table, check (a)'s float64 tree check
against a moved threshold, a lost shard and bfloat16 sums, and the
sizing the cell's rows rest on."""

import json

import ml_dtypes
import numpy as np
import pytest

from benchmarks import run, spec
from benchmarks.kinds import train_dp
from benchmarks.reference import tree_check_numpy

CELL = "criteo-dp4-26m-train"
TINY = {"config": {"params": {"num_leaves": 15},
                   "check": {"rows": 2000, "trees": 3, "auc_rows": 2000}},
        "traffic": {"rows": 6000, "probe_rows": 1024, "measure_steps": 2}}
COLLECTIVE_METRICS = {"collective_ms_per_split", "collective_exposed_share"}


@pytest.fixture
def on_chip_route(monkeypatch):
    """The learner factory routes data-parallel onto the mesh
    segment-kernel learner as it does on a TPU (kernels stay in
    interpret mode), as tests/test_cells_tiny.py does."""
    import lightgbm_tpu.parallel.learners as learners
    monkeypatch.setattr(learners, "on_tpu", lambda: True)


def _run(capsys, trace, scratch):
    rc = run.main(["--workload", CELL, "--seed", "2147483999",
                   "--seconds", "2", "--trace", str(trace)],
                  tiny=dict(TINY, allow_cpu=True, scratch=str(scratch)))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    info = {ln.split(" ", 2)[1]: json.loads(ln.split(" ", 2)[2])
            for ln in out if ln.startswith("info:")}
    return json.loads(out[-1]), info


def test_the_cell_is_declared_as_the_deployment():
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, CELL)
    assert cell.chips == 4 and cell.config_name == "criteo-dp4"
    mix, cfg = cell.traffic, cell.config
    assert mix["kind"] == "train_dp"
    assert (mix["rows"], mix["block"]) == (26_000_000, 2)
    assert mix["params"] == {"tree_learner": "data", "num_machines": 4}
    assert mix["expect"] == {"learner": "MeshPartitionedTreeLearner",
                             "num_shards": 4, "megakernel": False}
    assert cfg["machines"] == 4 and cfg["features"] == 67
    assert sorted(cfg["reduced"]) == ["rows", "trees"]
    declared = {m["name"] for m in spec.metrics_for(bench, "per_layer",
                                                    CELL)}
    assert COLLECTIVE_METRICS | {"collective_ici_roofline"} <= declared
    # the per-phase body's three
    assert {"partition_ms_per_split", "seg_hist_ms_per_split",
            "scan_ms_per_split"} <= declared


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_on_four_virtual_devices(capsys, tmp_path, trace,
                                               on_chip_route):
    result, info = _run(capsys, trace, tmp_path)
    assert result["correct"] is True, info
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert info["probe"]["rows"] == 1024
    path = info["check_path"]
    assert path["learner"] == "MeshPartitionedTreeLearner"
    assert path["num_shards"] == 4 and path["megakernel"] is False
    assert path["compiles_in_window"] == 0
    assert path["fused_block_hits"] == result["attempted"]
    tree = info["check_tree"]
    assert tree["ok"] is True and tree["count_mismatches"] == 0
    assert tree["splits"] == 14 and tree["searched_nodes"] >= 7
    assert tree["search_mismatches"] == 0
    assert tree["bfloat16_gain_err_median"] \
        > spec.load_cell(spec.load_benchmark(), CELL).config["check"][
            "split_gain_median_rtol"]
    assert info["check_reference"]["ok"] is True
    bench = spec.load_benchmark()
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in spec.metrics_for(bench, section, CELL)}
    got = set(result["metrics"])
    if not trace:
        assert got == declared == {"train_mrow_iters_per_s", "setup_s"}
        return
    # a CPU run reads no kernel time and no device peak: the shares of
    # a roofline or of the kernels are left out
    assert COLLECTIVE_METRICS | {
        "grow_ms_per_split", "partition_ms_per_split",
        "seg_hist_ms_per_split", "scan_ms_per_split",
        "grow_pack_ms_per_tree", "score_update_ms_per_tree",
        "scope_unattributed_share"} <= got <= declared
    assert 0 < result["metrics"]["collective_exposed_share"]["value"] <= 100


def test_the_probe_refuses_a_mesh_block_that_holds_its_table(
        on_chip_route, monkeypatch):
    """A program whose ``grow_operands()`` is ``None`` and whose mesh
    block reads the learner's metadata as a constant (the program
    before its metadata became an argument) fails at the probe."""
    import lightgbm_tpu as lgb
    import lightgbm_tpu.parallel.learners as learners
    from lightgbm_tpu.observability.telemetry import get_telemetry
    cell = spec.load_cell(spec.load_benchmark(), CELL)
    params = dict(cell.config["params"], num_leaves=7, min_data_in_leaf=5)
    tel = get_telemetry()
    tel.ensure_ring()
    train_dp._require_table_free_block(lgb, tel, params, 9,
                                       cell.traffic["expect"], 1024)
    plain = learners.MeshPartitionedTreeLearner.traceable_grow
    monkeypatch.setattr(learners.MeshPartitionedTreeLearner,
                        "grow_operands", lambda self: None)
    monkeypatch.setattr(
        learners.MeshPartitionedTreeLearner, "traceable_grow",
        lambda self, *a, meta, **k: plain(self, *a, meta=self.meta, **k))
    with pytest.raises(spec.SpecError, match="different texts"):
        train_dp._require_table_free_block(lgb, tel, params, 9,
                                           cell.traffic["expect"], 1024)


ROWS, FEATURES = 40_000, 12


@pytest.fixture(scope="module")
def grown():
    """One 63-leaf tree grown over four shards from seeded scores, the
    scores, and the table it was grown on."""
    import lightgbm_tpu as lgb
    import lightgbm_tpu.parallel.learners as learners
    plain = learners.on_tpu
    learners.on_tpu = lambda: True
    try:
        rng = np.random.default_rng(11)
        x = rng.standard_normal((ROWS, FEATURES)).astype(np.float32)
        y = (x[:, 0] + x[:, 1] * x[:, 2] + rng.standard_normal(ROWS)
             > 0).astype(np.float32)
        params = {"objective": "binary", "num_leaves": 63,
                  "tree_learner": "data", "num_machines": 4,
                  "verbosity": -1}
        scores = (rng.standard_normal(ROWS) * 0.5).astype(np.float32)
        ds = lgb.Dataset(x, label=y, init_score=scores,
                         params=dict(params)).construct()
        bst = lgb.Booster(dict(params), ds)
        bst._gbdt.train(1)
        learner = type(bst._gbdt.learner).__name__
    finally:
        learners.on_tpu = plain
    inner = ds._inner
    assert learner == "MeshPartitionedTreeLearner"
    return (inner.binned, inner.num_bins_array(), scores,
            np.asarray(inner.metadata.label),
            train_dp._grown_tree(bst._gbdt.models[0]), params)


def _check(grown, tree=None, **kw):
    binned, num_bins, scores, labels, plain, params = grown
    cfg = spec.load_cell(spec.load_benchmark(), CELL).config["check"]
    grad, hess = tree_check_numpy.gradients(scores, labels)
    return tree_check_numpy.check_tree(
        binned, num_bins, grad, hess, tree or plain, params,
        levels=cfg["search_levels"], gain_rtol=cfg["split_gain_rtol"],
        gain_median_rtol=cfg["split_gain_median_rtol"], **kw)


def test_check_a_passes_the_program_tree(grown):
    out = _check(grown)
    assert out["ok"] is True, out
    assert out["splits"] == 62 and out["searched_nodes"] >= 7


def test_check_a_refuses_a_moved_threshold(grown):
    tree = grown[4]
    thr = tree.threshold.copy()
    thr[5] += 1
    out = _check(grown, tree._replace(threshold=thr))
    assert out["ok"] is False and out["count_mismatches"] >= 1


def test_check_a_refuses_a_tree_whose_histograms_lost_a_shard(grown):
    """The tree's record of each node as one shard short would leave it:
    the rows, sums and outputs of three quarters of the table."""
    binned, num_bins, scores, labels, tree, params = grown
    keep = np.arange(ROWS) < ROWS * 3 // 4
    grad, hess = tree_check_numpy.gradients(scores, labels)
    node = np.zeros(ROWS, np.int64)          # split node, or ~leaf
    counts = {}
    for i in range(len(tree.feature)):
        here = node == i
        right = binned[:, tree.feature[i]] > tree.threshold[i]
        for c, side in ((tree.left[i], here & ~right),
                        (tree.right[i], here & right)):
            node[side] = c
            mine = side & keep
            counts[int(c)] = (mine.sum(), hess[mine].sum(),
                              -grad[mine].sum() / hess[mine].sum())
    ic, iw, iv = (tree.internal_count.copy(), tree.internal_weight.copy(),
                  tree.internal_value.copy())
    lc, lw, lv = (tree.leaf_count.copy(), tree.leaf_weight.copy(),
                  tree.leaf_value.copy())
    for c, (cnt, h, out) in counts.items():
        if c >= 0:
            ic[c], iw[c], iv[c] = cnt, h, out * tree.shrinkage
        else:
            lc[~c], lw[~c], lv[~c] = cnt, h, out * tree.shrinkage
    lost = tree._replace(internal_count=ic, internal_weight=iw,
                         internal_value=iv, leaf_count=lc, leaf_weight=lw,
                         leaf_value=lv)
    out = _check(grown, lost)
    assert out["ok"] is False
    assert out["count_mismatches"] == out["splits"]


def test_check_a_refuses_sums_in_bfloat16(grown):
    """The nearest precision below the configuration's float32: every
    gradient and hessian rounded to bfloat16 moves the sums and gains
    past the tolerances."""
    out = _check(grown, quantize=lambda a: a.astype(
        ml_dtypes.bfloat16).astype(np.float64))
    assert out["ok"] is False
    cfg = spec.load_cell(spec.load_benchmark(), CELL).config["check"]
    assert out["gain_err_median"] > cfg["split_gain_median_rtol"]


def test_the_sizing_the_rows_rest_on():
    """6.5 M rows a chip at the table's width and 255 leaves: about
    4.3 GB on the fullest chip, a quarter of 16 GiB."""
    got = train_dp.reckoned_chip_bytes(6_500_000, 67, 255)
    assert abs(got - 4.3e9) <= 0.1 * 4.3e9
    assert got >= 0.25 * 16 * 2 ** 30 * 0.95
