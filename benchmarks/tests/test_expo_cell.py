"""The categorical cell (``expo-10m-train``, ``kinds/train_cat.py``) end
to end on the CPU at a tiny size, and its five readers on made-up
events and facts (ISSUE 27)."""

import json
import types

import pytest
from lightgbm_tpu.observability import scopes as vocabulary

from benchmarks import run, scopes, spec
from benchmarks import trace_reduce as tr

CELL = "expo-10m-train"
PHASES = {"partition_ms_per_split": vocabulary.SPLITS_PARTITION,
          "seg_hist_ms_per_split": vocabulary.SPLITS_HIST,
          "scan_ms_per_split": vocabulary.SPLITS_SCAN,
          "cat_scan_ms_per_split": vocabulary.CAT_SCAN}
# widths cut here and nowhere else; the per-phase kernels' interpret
# twins, as the chip's route for a categorical table runs them
TINY = {"config": {"params": {"num_leaves": 15},
                   "check": {"rows": 2000, "trees": 3, "auc_rows": 2000,
                             "min_cat_split_share": 0.05}},
        "traffic": {"rows": 6000, "block": 2,
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
def test_the_cell_runs_through_its_own_kind(capsys, tmp_path, trace):
    result, info = _run(capsys, trace, tmp_path)
    assert result["correct"] is True, info
    assert result["attempted"] > 0 and result["failed"] == 0
    path = info["check_path"]
    assert path["learner"] == "PartitionedTreeLearner"
    assert path["megakernel"] is False
    assert path["lut_partition"] is True and path["cat_scan"] is True
    assert path["compiles_in_window"] == 0
    # FlightNum, TailNum, Origin and Dest are cut by the binning (to
    # 255 bins at the cell's size), and their last bin is then no
    # category
    bins, category_bins = (info["dataset"][k] for k in
                           ("num_bins", "category_bins"))
    assert category_bins[5:9] == [b - 1 for b in bins[5:9]]
    assert category_bins[:5] + category_bins[9:] \
        == bins[:5] + bins[9:12] and max(bins) <= 255
    ref = info["check_reference"]
    assert ref["cat_splits"] > 0
    assert ref["cat_splits"] == ref["cat_splits_reference"]
    assert info["check_full_size"]["cat_split_share"] >= 0.05
    bench = spec.load_benchmark()
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in spec.metrics_for(bench, section, CELL)}
    got = set(result["metrics"])
    if not trace:
        assert got == declared == {"train_mrow_iters_per_s", "setup_s"}
        return
    assert "split_loop_ms_per_split" not in declared
    # every metric listed for the cell is read, the five new ones among
    # them, but for the two that need a chip: its peak, and Mosaic
    # calls in the trace (the CPU runs the kernels' interpret twins);
    # the CPU's trace does not always hold an event of the gradients'
    # one fused pass
    needs_a_chip = {"grow_kernels_roofline", "train_hbm_floor_share"}
    assert needs_a_chip <= declared - got \
        <= needs_a_chip | {"grad_ms_per_tree"}
    assert set(PHASES) | {"cat_split_share"} <= got
    assert 5.0 <= result["metrics"]["cat_split_share"]["value"] <= 100.0
    by = info["scopes"]
    parts = sum(by[s] for s in PHASES.values())
    assert parts > by[vocabulary.GROW_SPLITS]   # the while's own is small
    assert sum(v for k, v in by.items() if k.startswith("lgbm.")) \
        + by["unattributed"] == pytest.approx(by["busy"], rel=0.02)


def _facts(monkeypatch, vocab):
    # one tree of 3 leaves (2 splits): partition [0, 2] and [10, 12],
    # histogram [2, 5], numeric scan [5, 6], categorical [6, 8]
    ops = tr.DeviceOps(
        ["%partition_segment.3 = ...", "%_histogram_segment_nibble.8 = ...",
         "%fusion.5 = ...", "%sort.9 = ...", "%partition_segment.3 = ..."],
        [0, 2, 5, 6, 10], [2, 5, 6, 8, 12])
    table = {"partition_segment.3": vocabulary.SPLITS_PARTITION,
             "_histogram_segment_nibble.8": vocabulary.SPLITS_HIST,
             "fusion.5": vocabulary.SPLITS_SCAN,
             "sort.9": vocabulary.CAT_SCAN}
    monkeypatch.setattr(scopes, "_table",
                        lambda facts: (vocab, table, 0.01))
    monkeypatch.setattr(scopes, "_idle_by_span", lambda trace, names: {})
    return {"trace": tr.Trace({0: ops}, [], 12.0), "rows": 10, "block": 1,
            "traced_trees": [{"leaves": 3, "cat_splits": 1}]}


@pytest.mark.parametrize("name,ms", [
    ("partition_ms_per_split", 2000.0), ("seg_hist_ms_per_split", 1500.0),
    ("scan_ms_per_split", 500.0), ("cat_scan_ms_per_split", 1000.0)])
def test_phase_readers_on_made_up_events(monkeypatch, name, ms):
    assert _read(name, _facts(monkeypatch, vocabulary)) == pytest.approx(ms)


@pytest.mark.parametrize("name", sorted(PHASES))
def test_a_program_without_the_phase_scopes_reads_as_nothing(
        monkeypatch, name):
    """The parent of ISSUE 27 has the vocabulary and a table, but none
    of the four names: no metric, no error."""
    old = types.SimpleNamespace(**{
        k: getattr(vocabulary, k) for k in dir(vocabulary)
        if k.isupper() and not k.startswith("SPLIT") and k != "CAT_SCAN"})
    assert _read(name, _facts(monkeypatch, old)) is None
    assert _read(name, {}) is None


def test_cat_split_share_reads_the_trees():
    facts = {"traced_trees": [{"leaves": 5, "cat_splits": 1},
                              {"leaves": 7, "cat_splits": 4}]}
    assert _read("cat_split_share", facts) == pytest.approx(50.0)
    # trees without the count (another kind's facts): nothing
    assert _read("cat_split_share",
                 {"traced_trees": [{"leaves": 5}]}) is None
    assert _read("cat_split_share", {}) is None


def test_split_body_reads_the_loop_and_its_parts(monkeypatch):
    # all five events are the split body's: 10 s over 2 splits
    assert _read("split_body_ms_per_split",
                 _facts(monkeypatch, vocabulary)) == pytest.approx(5000.0)
    # a megakernel's table holds lgbm.grow.splits alone: there the
    # reading is split_loop_ms_per_split's
    facts = _facts(monkeypatch, vocabulary)
    table = {"partition_segment.3": vocabulary.GROW_SPLITS,
             "sort.9": vocabulary.GROW_ROOT}
    monkeypatch.setattr(scopes, "_table",
                        lambda facts: (vocabulary, table, 0.01))
    assert _read("split_body_ms_per_split", facts) \
        == _read("split_loop_ms_per_split", facts) \
        == pytest.approx(2000.0)
    # the parent of ISSUE 27 names no part: the same, and no error
    old = types.SimpleNamespace(**{
        k: getattr(vocabulary, k) for k in dir(vocabulary)
        if k.isupper() and not k.startswith("SPLIT")
        and k != "CAT_SCAN"})
    facts = _facts(monkeypatch, old)
    monkeypatch.setattr(scopes, "_table",
                        lambda facts: (old, table, 0.01))
    assert _read("split_body_ms_per_split", facts) \
        == pytest.approx(2000.0)
    assert _read("split_body_ms_per_split", {}) is None


def test_split_body_is_listed_for_every_training_cell():
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "split_body_ms_per_split")
    loop = next(m for m in bench["per_layer"]
                if m["name"] == "split_loop_ms_per_split")
    assert entry["workloads"] == loop["workloads"] + [CELL]


@pytest.mark.parametrize("precision,correct", [
    (None, True), ("bfloat16", False), ("float16", False)])
def test_a_lower_precision_reads_as_not_correct(monkeypatch, precision,
                                                correct):
    """Check (a) with the configuration's own limits: against the plain
    reference it holds, and against the reference computed with
    gradients and hessians rounded to the precisions below float32 it
    does not, by the gains of the first tree (AUC and log-loss stay
    inside their limits: they cannot see it)."""
    import functools

    import lightgbm_tpu as lgb
    import ml_dtypes
    import numpy as np

    from benchmarks.kinds import train_cat
    from benchmarks.reference import gbdt_cat_numpy
    cell = spec.load_cell(spec.load_benchmark(), CELL)
    cfg = cell.config
    params = dict(cfg["params"], num_leaves=63,
                  tree_learner="partitioned")
    gen = spec.load_module("generators", cfg["generator"]["name"])
    x, y = gen.make(2147483999, 20000, cfg["features"],
                    **cfg["generator"]["params"])
    ds = lgb.Dataset(x, label=y, params=params).construct()
    if precision is not None:
        dtype = getattr(ml_dtypes, precision, None) or np.dtype(precision)
        monkeypatch.setattr(gbdt_cat_numpy, "train", functools.partial(
            gbdt_cat_numpy.train,
            quantize=lambda a: a.astype(dtype).astype(np.float64)))
    out = train_cat._check_against_reference(
        lgb, ds, params, dict(cfg["check"], trees=2))
    assert out["learner"] == "PartitionedTreeLearner"
    assert out["first_tree_compared_splits"] >= 20
    assert out["ok"] is correct, out
    assert abs(out["auc"] - out["auc_reference"]) <= cfg["check"]["auc_tol"]
    if not correct:
        assert out["gain_err_median"] > cfg["check"]["gain_median_rtol"]


def _between(text, first, last):
    lines = text.splitlines()
    a = next(i for i, ln in enumerate(lines) if ln.strip() == first)
    b = next(i for i, ln in enumerate(lines) if ln.strip() == last)
    assert a < b
    return lines[a:b]


def test_the_window_and_the_rate_are_train_pys_letter_for_letter():
    """``train_cat.run`` is a copy of ``train.run`` (ISSUE 27 allowed
    no edit to a file that was there): the source of
    ``train_mrow_iters_per_s`` lives in both, from the booster's
    construction and the step through the window to the rate, and in
    the facts and the result that follow the checks. Whoever changes
    one changes the other, until a benchmark PR folds them."""
    import inspect

    from benchmarks.kinds import train, train_cat
    ours = inspect.getsource(train_cat.run)
    theirs = inspect.getsource(train.run)
    for first, last in (
            ("t_ds = time.perf_counter()",
             "# ---- correctness, outside the window "
             "-------------------------------"),
            ("trace = tracer.trace if tracer is not None else None",
             '"traced_trees": ['),
            ('"trace": trace, "device_kind": ctx.device["kind"],',
             '"facts": facts,')):
        assert _between(ours, first, last) == _between(theirs, first, last)
