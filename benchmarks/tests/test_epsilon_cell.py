"""The dense-wide cell (``epsilon-400k-train``: ``kinds/train.py``'s
run with ``kinds/train_gain.py``'s check (a)) end to end on the CPU at
a tiny size, its plain reference against lower precisions and a wrong
rule, and its three readers on made-up events and facts (ISSUE 31)."""

import json
import types

import numpy as np
import pytest
from lightgbm_tpu.observability import scopes as vocabulary

from benchmarks import run, scopes, spec
from benchmarks import trace_reduce as tr

CELL = "epsilon-400k-train"
# sizes cut here and nowhere else. 300 columns are three column slices
# of the histogram (the payload in the third slice's tile) and three
# feature blocks of the scan; the kernels' interpret twins, as the
# chip's route for a wide table runs them
TINY = {"config": {"features": 300, "params": {"num_leaves": 15},
                   "check": {"rows": 1000, "trees": 2, "auc_rows": 3000,
                             "auc_tol": 1e-3, "logloss_tol": 1e-3}},
        "traffic": {"rows": 3000,
                    "params": {"tree_learner": "partitioned"}}}


def _run(capsys, trace, scratch):
    rc = run.main(["--workload", CELL, "--seed", "2147483999",
                   "--seconds", "2", "--trace", str(trace)],
                  tiny=dict(TINY, allow_cpu=True, scratch=str(scratch)))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    info = {ln.split(" ", 2)[1]: json.loads(ln.split(" ", 2)[2])
            for ln in out if ln.startswith("info:")}
    return json.loads(out[-1]), info


def _read(name, facts):
    return spec.load_module("layers", name).read(facts)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_through_the_general_kind(capsys, tmp_path, trace):
    cell = spec.load_cell(spec.load_benchmark(), CELL)
    assert cell.traffic["kind"] == "train_gain"
    assert cell.traffic["measure_steps"] == 4       # ISSUE 31's window
    result, info = _run(capsys, trace, tmp_path)
    assert result["correct"] is True, info
    # check (a) compared the first tree split by split
    ref = info["check_reference"]
    assert ref["first_tree_compared_splits"] == ref["first_tree_splits"] > 0
    assert ref["gain_err_median"] <= cell.config["check"]["gain_median_rtol"]
    assert result["attempted"] > 0 and result["failed"] == 0
    path = info["check_path"]
    assert path["learner"] == "PartitionedTreeLearner"
    assert path["megakernel"] is False
    assert path["compiles_in_window"] == 0
    assert path["fused_block_hits"] == result["attempted"]
    assert info["check_reference"]["learner"] == "PartitionedTreeLearner"
    bench = spec.load_benchmark()
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in spec.metrics_for(bench, section, CELL)}
    got = set(result["metrics"])
    if not trace:
        assert got == declared == {"train_mrow_iters_per_s", "setup_s"}
        return
    assert not declared & {"split_loop_ms_per_split", "cat_split_share",
                           "cat_scan_ms_per_split"}
    # every metric listed for the cell is read but for those that need
    # a chip: its peak, and Mosaic calls in the trace (the CPU runs the
    # kernels' interpret twins); the CPU's trace does not always hold
    # an event of the gradients' one fused pass
    needs_a_chip = {"grow_kernels_roofline", "train_hbm_floor_share",
                    "hist_kernels_roofline"}
    assert needs_a_chip <= declared - got \
        <= needs_a_chip | {"grad_ms_per_tree"}
    assert {"hist_cache_ms_per_split", "root_hist_ms_per_tree",
            "partition_ms_per_split", "seg_hist_ms_per_split",
            "scan_ms_per_split"} <= got
    by = info["scopes"]
    assert by[vocabulary.SPLITS_CACHE] > 0
    assert sum(v for k, v in by.items() if k.startswith("lgbm.")) \
        + by["unattributed"] == pytest.approx(by["busy"], rel=0.02)


def test_the_generator_draws_unit_rows_of_one_table():
    gen = spec.load_module("generators", "epsilon_like")
    x, y = gen.make(2147483999, 3000, 2000)
    assert x.shape == (3000, 2000) and x.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0, rtol=1e-5)
    assert 0.4 < y.mean() < 0.6
    # the same seed gives the same rows; another seed gives other rows
    # of the same table: the weights come from table_seed
    x2, y2 = gen.make(2147483999, 3000, 2000)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    x3, y3 = gen.make(7, 3000, 2000)
    assert not np.array_equal(x, x3)
    # which columns carry the label, and which way, is the table's:
    # the ten that lean on it most under one seed lean the same way
    # under another
    lean, lean3 = x.T @ (y - 0.5), x3.T @ (y3 - 0.5)
    top = np.argsort(np.abs(lean))[-10:]
    assert np.array_equal(np.sign(lean[top]), np.sign(lean3[top]))
    # dense: those ten are a small part of what the label leans on
    assert np.abs(lean[top]).sum() < 0.2 * np.abs(lean).sum()


def _facts(monkeypatch, vocab):
    # one tree of 3 leaves (2 splits) on a 10-row, 2,000-column table:
    # root histogram [0, 4], then a split: cache read [4, 5],
    # histogram [5, 8], cache write [8, 10]; the second split alike
    kernel = ' custom-call(...), custom_call_target="tpu_custom_call"'
    names = ["%hist_root.1 =" + kernel, "%cache_read.2 = ...",
             "%hist_segment.3 =" + kernel, "%cache_write.4 = ..."]
    ops = tr.DeviceOps(
        [names[i] for i in (0, 1, 2, 3, 1, 2, 3)],
        [0, 4, 5, 8, 10, 11, 14], [4, 5, 8, 10, 11, 14, 16])
    table = {"hist_root.1": vocabulary.GROW_ROOT,
             "cache_read.2": getattr(vocab, "SPLITS_CACHE", None),
             "hist_segment.3": vocabulary.SPLITS_HIST,
             "cache_write.4": getattr(vocab, "SPLITS_CACHE", None)}
    table = {k: v for k, v in table.items() if v is not None}
    monkeypatch.setattr(scopes, "_table",
                        lambda facts: (vocab, table, 0.01))
    monkeypatch.setattr(scopes, "_idle_by_span", lambda trace, names: {})
    return {"trace": tr.Trace({0: ops}, [], 16.0), "rows": 10, "block": 1,
            "features": 2000, "chips": 1, "device_kind": "TPU v5 lite",
            "traced_trees": [{"leaves": 3, "split_rows": [10.0, 6.0],
                              "smaller_child_rows": [4.0, 2.0]}]}


def test_readers_on_made_up_events(monkeypatch):
    facts = _facts(monkeypatch, vocabulary)
    # 6 s under the cache scope over 2 splits; 4 s of root over 1 tree
    assert _read("hist_cache_ms_per_split", facts) \
        == pytest.approx(3000.0)
    assert _read("root_hist_ms_per_tree", facts) == pytest.approx(4000.0)
    # 10 + 4 + 2 rows of 2,012 bytes at 819 GB/s over 4 + 3 + 3 s
    want = 100.0 * 16 * 2012 / 819e9 / 10.0
    assert _read("hist_kernels_roofline", facts) == pytest.approx(want)
    assert want < 100.0


def test_a_program_without_the_new_scope_reads_as_nothing(monkeypatch):
    """The parent of ISSUE 31 has the vocabulary and a table, but no
    ``SPLITS_CACHE``: no metric, no error; its root scope and its
    histogram phase are there, so the other two still read."""
    old = types.SimpleNamespace(**{
        k: getattr(vocabulary, k) for k in dir(vocabulary)
        if k.isupper() and k != "SPLITS_CACHE"})
    facts = _facts(monkeypatch, old)
    assert _read("hist_cache_ms_per_split", facts) is None
    assert _read("root_hist_ms_per_tree", facts) == pytest.approx(4000.0)
    assert _read("hist_kernels_roofline", facts) is not None
    # a program whose split body is the megakernel names no histogram
    # phase: the roofline of the histogram passes has nothing to read
    mega = types.SimpleNamespace(**{
        k: getattr(vocabulary, k) for k in dir(vocabulary)
        if k.isupper() and not k.startswith("SPLIT")})
    assert _read("hist_kernels_roofline",
                 _facts(monkeypatch, mega)) is None
    for name in ("hist_cache_ms_per_split", "root_hist_ms_per_tree",
                 "hist_kernels_roofline"):
        assert _read(name, {}) is None


def test_the_new_metrics_are_listed_where_they_read():
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["hist_cache_ms_per_split"]["workloads"] \
        == ["expo-10m-train", CELL]
    assert by_name["root_hist_ms_per_tree"]["workloads"] == [CELL]
    assert by_name["hist_kernels_roofline"]["workloads"] == [CELL]
    entry = next(c for c in bench["configs"] if c["name"] == "epsilon-wide")
    assert entry["reduced"] == ["trees"]
    cfg = spec.load_cell(bench, CELL).config
    assert (cfg["features"], cfg["max_bin"], cfg["num_leaves"],
            cfg["train_rows"]) == (2000, 255, 255, 400000)


@pytest.mark.parametrize("foil,fails_by", [
    (None, ()), ("bfloat16", ("gain",)), ("float16", ("gain",)),
    ("half-shrinkage", ("auc", "logloss"))])
def test_the_reference_check_holds_rule_and_precision(monkeypatch, foil,
                                                      fails_by):
    """Check (a) as the cell runs it (``kinds/train_gain.py``) with the
    configuration's own limits. Against the plain reference it holds.
    Against the reference computed with gradients and hessians rounded
    to bfloat16 (the nearest precision below the configuration's; and
    to float16) it reads ok false by ONE limit, the first tree's median
    gain difference: AUC and log-loss stay inside theirs, which a tie
    that falls the other way moves further than a lower precision does
    (the configuration's ``why_tol``). Against a wrong rule (leaf
    values shrunk by half the learning rate) it reads ok false by AUC
    and log-loss, the first tree's gains untouched."""
    import functools

    import lightgbm_tpu as lgb
    import ml_dtypes

    from benchmarks.kinds import train_gain
    from benchmarks.reference import gbdt_cat_numpy
    cfg = spec.load_cell(spec.load_benchmark(), CELL).config
    check = dict(cfg["check"], trees=3)
    params = dict(cfg["params"], num_leaves=63,
                  tree_learner="partitioned")
    gen = spec.load_module("generators", cfg["generator"]["name"])
    x, y = gen.make(6, 20000, 200, **cfg["generator"]["params"])
    ds = lgb.Dataset(x, label=y, params=params).construct()
    plain = gbdt_cat_numpy.train
    rounded = {"bfloat16": ml_dtypes.bfloat16, "float16": np.float16}
    if foil in rounded:
        monkeypatch.setattr(gbdt_cat_numpy, "train", functools.partial(
            plain, quantize=lambda a: a.astype(rounded[foil]).astype(
                np.float64)))
    elif foil is not None:
        monkeypatch.setattr(
            gbdt_cat_numpy, "train",
            lambda binned, num_bins, labels, p, trees, **kw: plain(
                binned, num_bins, labels,
                dict(p, learning_rate=p["learning_rate"] / 2), trees, **kw))
    out = train_gain._check_against_reference(lgb, ds, params, check)
    assert out["learner"] == "PartitionedTreeLearner"
    assert out["first_tree_compared_splits"] == out["first_tree_splits"]
    over = {
        "auc": abs(out["auc"] - out["auc_reference"]) > check["auc_tol"],
        "logloss": abs(out["logloss"] - out["logloss_reference"])
        > check["logloss_tol"],
        "gain": out["gain_err_median"] > check["gain_median_rtol"]}
    assert tuple(k for k in over if over[k]) == fails_by, out
    assert out["ok"] is (not fails_by)
