"""Set-up by layer (``benchmarks/setup_spans.py``): the union and
self-time arithmetic on a hand-written ledger, the anchor, and the
seven readers on tiny traced cells (ISSUE 35)."""

import json

import pytest

from benchmarks import run, setup_spans, spec
from benchmarks.tests import test_allstate_cell as allstate
from benchmarks.tests.tiny import tiny_for

METRICS = ("setup_binning_s", "setup_table_io_s", "setup_booster_s",
           "setup_compile_s", "setup_compile_miss_s", "setup_warm_run_s",
           "setup_unattributed_share")
CELLS = ["higgs-10m-train", "criteo-7m-train", "expo-10m-train",
         "epsilon-400k-train", "allstate-12m-train"]


def span(name, t0, t1, path=None, **fields):
    path = path or name
    parent = path.rsplit("/", 1)[0] if "/" in path else None
    return dict(kind="span", name=name, path=path, parent=parent, t0=t0,
                t1=t1, dur_s=t1 - t0, **fields)


def compile_(program, stage, t0, t1, parent=None, cache=None):
    rec = dict(kind="compile", program=program, stage=stage, t0=t0, t1=t1,
               dur_s=t1 - t0, parent=parent)
    if cache:
        rec["cache"] = cache
    return rec


# a process that started at 100 and opened its window at 200: a table
# binned and saved, a booster, a first iteration and a warm-up block,
# three window steps, then the check's table, booster and two calls
LEDGER = [
    span("lgbm.data.find_bins", 110, 112,
         "lgbm.data.construct/lgbm.data.find_bins"),
    span("lgbm.data.bin_rows", 112, 130,
         "lgbm.data.construct/lgbm.data.bin_rows"),
    span("lgbm.data.construct", 110, 131, rows=1000),
    span("lgbm.data.save_binary", 131, 140),
    compile_("scatter", "backend", 141, 143,
             "lgbm.setup/lgbm.setup.learner/lgbm.setup.device_table",
             "hit"),
    span("lgbm.setup.device_table", 141, 144,
         "lgbm.setup/lgbm.setup.learner/lgbm.setup.device_table"),
    span("lgbm.setup.learner", 140, 145, "lgbm.setup/lgbm.setup.learner"),
    span("lgbm.setup", 140, 150, rows=1000),
    # a nested trace: the inner program's inside the outer's
    compile_("partitioned_grow", "trace", 152, 156, "train/boosting"),
    compile_("hist_child_stream", "trace", 153, 154, "train/boosting"),
    compile_("partitioned_grow", "backend", 156, 160, "train/boosting",
             "miss"),
    span("train", 150, 165, rows=1000),
    compile_("gbdt_fused_block", "trace", 166, 170, "train/boosting"),
    compile_("gbdt_fused_block", "lower", 170, 172, "train/boosting"),
    compile_("gbdt_fused_block", "backend", 172, 190, "train/boosting",
             "none"),
    span("train", 165, 195, rows=1000),
    span("train", 200.0001, 204, rows=1000),
    span("train", 204, 208, rows=1000),
    span("train", 208, 212, rows=1000),
    span("lgbm.setup", 213, 214, rows=100),
    compile_("gbdt_fused_block", "backend", 215, 218, "train/boosting",
             "miss"),
    span("train", 214, 219, rows=100),
    span("train", 219, 220, rows=100),
]
FACTS = {"setup_s": 100.0001, "rows": 1000, "steps": 3}


@pytest.mark.parametrize("what", [
    "anchor", "compile_is_a_union", "a_compile_leaves_its_span",
    "table_io", "binning", "warm_run", "miss_is_a_part", "the_sum",
    "after_the_window", "top_compiles"])
def test_the_arithmetic_on_a_hand_written_ledger(what):
    t_start, t_window = setup_spans.window_start(FACTS, LEDGER)
    got = setup_spans.reduce_ledger(LEDGER, t_start, t_window)
    s = got["seconds"]
    if what == "anchor":
        assert (t_start, t_window) == pytest.approx((100.0, 200.0001))
    elif what == "compile_is_a_union":
        # 2 + (4 + 4, the inner trace not counted twice) + 24
        assert s["compile"] == pytest.approx(34.0)
        assert got["spans"]["compile.trace"] == pytest.approx(8.0)
    elif what == "a_compile_leaves_its_span":
        # lgbm.setup is 10 s long and holds a 2 s compile
        assert got["spans"]["lgbm.setup"] == pytest.approx(10.0)
        assert s["booster"] == pytest.approx(8.0)
    elif what == "table_io":
        assert s["table_io"] == pytest.approx(9.0)
    elif what == "binning":
        assert s["binning"] == pytest.approx(21.0)
    elif what == "warm_run":
        # 15 + 30 s of train spans, 8 + 24 s of them compiling
        assert s["warm_run"] == pytest.approx(13.0)
    elif what == "miss_is_a_part":
        assert got["compile_miss"] == pytest.approx(4.0 + 18.0)
        assert got["compile_miss"] <= s["compile"]
        assert got["backend"] == {"hit": 1, "miss": 1, "none": 1}
    elif what == "the_sum":
        assert sum(s.values()) == pytest.approx(got["covered"]) \
            == pytest.approx(85.0)
        assert got["setup_s"] == pytest.approx(100.0001)
        assert got["orphans"] == []
    elif what == "after_the_window":
        assert got["after_window"] == 7 and got["records"] == 16
    else:
        assert got["top_compiles"][0] == [
            "gbdt_fused_block", "none", 18.0, "train/boosting"]


@pytest.mark.parametrize("case", ["no_setup_s", "no_records",
                                  "untimed_records", "too_few_steps",
                                  "orphan"])
def test_what_cannot_be_read_reads_nothing_and_an_orphan_shows(case):
    if case == "no_setup_s":
        assert setup_spans.window_start({"rows": 1000, "steps": 3},
                                        LEDGER) is None
        assert setup_spans.by_layer({}) is None
    elif case == "no_records":
        assert setup_spans.window_start(FACTS, []) is None
    elif case == "untimed_records":
        # the parent's records: a compile with a duration and no times
        old = [dict(kind="compile", event="backend_compile_duration",
                    dur_s=1.0, t=3.0)]
        assert setup_spans.window_start(FACTS, old) is None
        assert setup_spans.reduce_ledger(old, 0.0, 10.0) is None
    elif case == "too_few_steps":
        assert setup_spans.window_start(dict(FACTS, steps=5),
                                        LEDGER) is None
    else:
        stray = LEDGER + [span("lgbm.data.bundle", 196, 197)]
        got = setup_spans.reduce_ledger(stray, 100.0, 200.0001)
        assert got["orphans"] == ["lgbm.data.bundle"]
        assert got["covered"] == pytest.approx(85.0)


@pytest.mark.parametrize("name", METRICS)
def test_every_reader_is_listed_for_the_five_cells(name):
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == CELLS
    assert entry["source"] == "program_span" and entry["moves"] == "setup_s"
    assert entry["better"] == "lower"
    assert spec.load_module("layers", name).read({}) is None


def _traced(capsys, workload, tiny):
    rc = run.main(["--workload", workload, "--seed", "2147483999",
                   "--seconds", "2", "--trace", "1"], tiny=tiny)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    info = {ln.split(" ", 2)[1]: json.loads(ln.split(" ", 2)[2])
            for ln in out if ln.startswith("info:")}
    return json.loads(out[-1]), info


@pytest.mark.parametrize("workload", ["higgs-10m-train",
                                      "allstate-12m-train"])
def test_a_tiny_traced_cell_reports_the_seven(capsys, tmp_path, workload):
    tiny = tiny_for(workload, tmp_path) if workload in \
        ("higgs-10m-train",) else dict(allstate.TINY, allow_cpu=True,
                                       scratch=str(tmp_path))
    result, info = _traced(capsys, workload, tiny)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(METRICS) <= set(got)
    by = info["setup_spans"]
    assert by["orphans"] == []
    assert 0.0 <= got["setup_unattributed_share"] <= 100.0
    assert got["setup_compile_miss_s"] <= got["setup_compile_s"]
    parts = sum(got[m] for m in METRICS if m.endswith("_s")) \
        - got["setup_compile_miss_s"]
    assert parts == pytest.approx(by["covered"], abs=1e-3)
    assert parts <= info["setup"]["setup_s"]
    # the inside of dataset_construct_s: the table was binned and saved
    assert info["dataset"]["cache"] == "miss"
    inside = got["setup_binning_s"] + got["setup_table_io_s"]
    assert inside >= 0.5 * (info["dataset"]["bin_s"]
                            + info["dataset"]["save_s"])
    if workload == "higgs-10m-train":
        # the sparse kind bins a probe table of its own before this one
        assert inside <= got["dataset_construct_s"] + 1e-3
    # the window's steps and the check's second booster came after
    assert by["after_window"] >= result["attempted"] + 2
    assert by["spans"]["train"] > 0 and by["spans"]["lgbm.setup"] > 0
    if workload == "allstate-12m-train":
        assert got["bundle_s"] <= got["setup_binning_s"] + 1e-3
        assert by["spans"]["lgbm.data.bundle_plan"] > 0
