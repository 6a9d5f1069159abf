"""Device time by program scope (``benchmarks/scopes.py`` and the seven
readers over it): on made-up events, on a trace recorded on a TPU v5e
with the scope table the program built in that run
(``data/tiny-train-scoped-v5e.*``, recorded by ``record_scoped.py``:
20,000 rows x 28 features, 15 leaves, one traced step of 2 trees,
seed 1, jax 0.9.0, ISSUE 24), and in the CPU rehearsal of a listed
cell."""

import json
import os
import re

import pytest
from lightgbm_tpu.observability import scopes as vocabulary

from benchmarks import run, scopes, spec
from benchmarks import trace_reduce as tr
from benchmarks.tests.tiny import tiny_for

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STEM = os.path.join(DATA, "tiny-train-scoped-v5e")
NEW = ("grad_ms_per_tree", "grow_pack_ms_per_tree",
       "split_loop_ms_per_split", "leaf_of_pos_ms_per_tree",
       "score_update_ms_per_tree", "scope_unattributed_share",
       "block_boundary_idle_ms")


def _facts(trace, table, monkeypatch, trees=2, leaves=15):
    monkeypatch.setattr(scopes, "_table",
                        lambda facts: (vocabulary, table, 0.0125))
    return {"trace": trace, "rows": 20000, "block": trees,
            "traced_trees": [{"leaves": leaves}] * trees}


def _read(name, facts):
    return spec.load_module("layers", name).read(facts)


# -- on made-up events ----------------------------------------------------
def test_seconds_by_scope_idle_by_span_and_the_readers(monkeypatch, capsys):
    # a scan's while [0, 20] holding one tree: pack [1, 3], the grow
    # while [4, 12] with two kernel calls, leaf-of-pos [12, 15], an
    # unscoped copy [15, 16], the score update [16, 18]; another
    # program's operation [19.5, 20]
    ops = tr.DeviceOps(
        ["%while.1 = ...", "%fusion.84 = ...", "%while.2 = ...",
         "%fused_split_step_segment.7 = ...",
         "%fused_split_step_segment.7 = ...", "%fusion.87 = ...",
         "%copy.4 = ...", "%fusion.71 = ...", "%dynamic_slice.3 = ..."],
        [0, 1, 4, 4, 9, 12, 15, 16, 19.5],
        [20, 3, 12, 8, 12, 15, 16, 18, 20])
    table = {"fusion.84": vocabulary.GROW_PACK,
             "while.2": vocabulary.GROW_SPLITS,
             "fused_split_step_segment.7": vocabulary.GROW_SPLITS,
             "fusion.87": vocabulary.GROW_LEAF_OF_POS,
             "fusion.71": vocabulary.SCORE_UPDATE}
    host = [tr.HostEvent("python3", vocabulary.BLOCK_DISPATCH, 0.0, 0.5),
            tr.HostEvent("python3", vocabulary.BLOCK_SYNC, 0.5, 18.5),
            tr.HostEvent("python3", vocabulary.BLOCK_TREES, 18.5, 19.0),
            tr.HostEvent("python3", "bench.step", 0.0, 20.0)]
    facts = _facts(tr.Trace({0: ops}, host, 20.0), table, monkeypatch,
                   trees=1, leaves=3)
    got = scopes.by_scope(facts)
    assert got is scopes.by_scope(facts)            # computed once
    assert got["scopes"] == {
        vocabulary.GROW_PACK: pytest.approx(2.0),
        vocabulary.GROW_SPLITS: pytest.approx(7.0),     # leaves only
        vocabulary.GROW_LEAF_OF_POS: pytest.approx(3.0),
        vocabulary.SCORE_UPDATE: pytest.approx(2.0)}
    assert got["unattributed"] == pytest.approx(1.0 + 0.5)
    assert got["busy"] == pytest.approx(15.5)
    assert sum(got["scopes"].values()) + got["unattributed"] \
        == pytest.approx(got["busy"])
    # gaps: [3, 4], [8, 9] under the sync span; [18, 19.5] holds the
    # sync's end: half a second under it, half under the trees span
    idle = got["idle"]
    assert idle["gaps"] == pytest.approx(3.5)
    assert idle[vocabulary.BLOCK_SYNC] == pytest.approx(2.5)
    assert idle[vocabulary.BLOCK_TREES] == pytest.approx(0.5)
    assert idle[vocabulary.BLOCK_DISPATCH] == 0.0
    assert idle["boundary_gap"] == pytest.approx(1.5)
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and out[0].startswith("info: scopes {")
    line = json.loads(out[0].split(" ", 2)[2])
    assert line["busy"] == pytest.approx(15.5) and line["table_s"] == 0.0125
    assert line["ops_in_table"] == 5
    # the longest instructions, each with its scope
    assert line["top"][0] == ["fused_split_step_segment.7",
                              vocabulary.GROW_SPLITS, 7.0]
    assert ["copy.4", "unattributed", 1.0] in line["top"]
    assert "while.1" not in [row[0] for row in line["top"] if row[2]]
    # the readers: one tree of 3 leaves (2 splits), one block
    assert _read("grow_pack_ms_per_tree", facts) == pytest.approx(2000.0)
    assert _read("split_loop_ms_per_split", facts) == pytest.approx(3500.0)
    assert _read("leaf_of_pos_ms_per_tree", facts) == pytest.approx(3000.0)
    assert _read("score_update_ms_per_tree", facts) == pytest.approx(2000.0)
    assert _read("scope_unattributed_share", facts) \
        == pytest.approx(100 * 1.5 / 15.5)
    assert _read("block_boundary_idle_ms", facts) == pytest.approx(3000.0)
    # no operation under lgbm.gradients or lgbm.sample ran: nothing
    assert _read("grad_ms_per_tree", facts) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_scopes_reads_as_nothing(name, monkeypatch):
    """The parent of ISSUE 24 has no table: no metric, no error."""
    monkeypatch.setattr(scopes, "_table", lambda facts: None)
    ops = tr.DeviceOps(["%fusion.1 = ..."], [0.0], [1.0])
    facts = {"trace": tr.Trace({0: ops}, [], 1.0), "rows": 10, "block": 1,
             "traced_trees": [{"leaves": 3}]}
    assert _read(name, facts) is None
    assert _read(name, {}) is None


# -- on the trace recorded on a TPU v5e -----------------------------------
@pytest.fixture(scope="module")
def recorded():
    with open(STEM + ".scopes.json") as fh:
        held = json.load(fh)
    return tr.Trace.from_file(STEM + ".xplane.pb"), held


def test_recorded_join_scopes_and_unattributed_sum_to_busy(
        recorded, monkeypatch):
    trace, held = recorded
    assert held["program"] == scopes.PROGRAM
    facts = _facts(trace, held["table"], monkeypatch)
    got = scopes.by_scope(facts)
    # every scope the configuration reaches (no bagging: no sample)
    assert set(got["scopes"]) == set(vocabulary.DEVICE_SCOPES) \
        - {vocabulary.SAMPLE}
    total = sum(got["scopes"].values()) + got["unattributed"]
    assert total == pytest.approx(got["busy"], rel=0.01)
    assert got["busy"] == pytest.approx(trace.busy_s())
    assert got["unattributed"] / got["busy"] < 0.01
    # the grow while holds the megakernel's 28 calls and little else
    kernel = trace.time_matching(re.compile(r"^%fused_split_step"))
    assert kernel <= got["scopes"][vocabulary.GROW_SPLITS] <= 1.1 * kernel
    # one block in the trace: each span once
    for span in got["spans"]:
        assert got["idle"][span + ".count"] == 1
    assert got["idle"]["boundary_gap"] <= got["idle"]["gaps"]


def test_recorded_readers_report_all_seven(recorded, monkeypatch):
    trace, held = recorded
    facts = _facts(trace, held["table"], monkeypatch)
    got = {name: _read(name, facts) for name in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["scope_unattributed_share"] < 1.0
    by = scopes.by_scope(facts)["scopes"]
    assert got["split_loop_ms_per_split"] \
        == pytest.approx(1e3 * by[vocabulary.GROW_SPLITS] / 28)
    assert got["leaf_of_pos_ms_per_tree"] \
        == pytest.approx(1e3 * by[vocabulary.GROW_LEAF_OF_POS] / 2)


def test_recorded_modules_and_host_spans_carry_the_program_names():
    from jax.profiler import ProfileData
    modules, spans = [], []
    for plane in ProfileData.from_file(STEM + ".xplane.pb").planes:
        for line in plane.lines:
            for ev in line.events:
                if line.name == "XLA Modules":
                    modules.append(ev.name.split("(")[0])
                elif ev.name.startswith("lgbm."):
                    spans.append((line.name.split("/")[0], ev.name))
    assert modules.count("jit_gbdt_fused_block") == 1
    assert not [m for m in modules if "unknown" in m]
    assert sorted(spans) == [("python3", vocabulary.BLOCK_DISPATCH),
                             ("python3", vocabulary.BLOCK_SYNC),
                             ("python3", vocabulary.BLOCK_TREES)]


# -- the CPU rehearsal of a listed cell -----------------------------------
def test_a_listed_cell_reports_all_seven_and_one_scopes_line(
        capsys, tmp_path):
    vocabulary.forget()
    workload = "criteo-7m-train"
    rc = run.main(["--workload", workload, "--seed", "5", "--seconds", "2",
                   "--trace", "1"], tiny=tiny_for(workload, tmp_path))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    result = json.loads(out[-1])
    assert result["correct"] is True
    assert set(NEW) <= set(result["metrics"])
    lines = [ln for ln in out if ln.startswith("info: scopes ")]
    assert len(lines) == 1 and out.index(lines[0]) < len(out) - 1
    line = json.loads(lines[0].split(" ", 2)[2])
    by_scope = sum(v for k, v in line.items() if k.startswith("lgbm."))
    # XLA:CPU runs independent operations side by side, so the scopes'
    # unions may overlap here; a chip's do not (the recorded trace
    # above holds the sum to 1 %)
    assert line["busy"] * 0.99 <= by_scope + line["unattributed"] \
        <= line["busy"] * 1.1
    assert line["table_s"] < 5 and line["ops_in_table"] > 0
    # the table is built in set-up, never in the window
    path = json.loads(next(ln for ln in out if ln.startswith(
        "info: check_path")).split(" ", 2)[2])
    assert path["compiles_in_window"] == 0
    # the reference check's smaller booster ran the same block length
    # after the window: the window's program is found by its rows
    held = vocabulary.remembered(scopes.PROGRAM)
    assert [p.static for p in held] == [{"m": 2}, {"m": 2}]
    assert scopes._table({"rows": 5000, "block": 2})[1] is held[0].scopes()
    assert scopes._table({"rows": 2000, "block": 2})[1] is held[1].scopes()
    assert scopes._table({"rows": 5000, "block": 4}) is None
