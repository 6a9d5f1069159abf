"""graftcheck self-tests (ISSUE 9 tentpole).

The known-bad corpus (tests/lint_fixtures/graftcheck/) pins DETECTION:
each seeded defect — dropped donation, f64 leak, host callback,
surprise collective, dynamic shapes — yields its EXACT finding id and
nothing else. Pure-parser and manifest-workflow tests need no compile;
fixture programs are tiny (sub-second compiles on CPU).
"""

import importlib
import json
import os
import warnings

import jax

import pytest

from lightgbm_tpu.utils.jit_registry import JitProgram
from tools.graftcheck import (GcFinding, check_program, load_manifest,
                              measure, stale_entries)
from tools.graftcheck.findings import RULE_NAMES, sort_findings
from tools.graftcheck.hlo import (aliased_param_count,
                                  collective_census,
                                  dynamic_shape_lines,
                                  host_callback_lines,
                                  module_op_counts, nontrivial_total,
                                  wide_dtype_lines)
from tools.graftcheck.manifest import update_manifest

FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "lint_fixtures", "graftcheck")
FIXTURES = sorted(f[:-3] for f in os.listdir(FIXDIR)
                  if f.startswith("bad_") and f.endswith(".py"))

WIDE_OPEN = dict(ops=10_000, ops_slack=0, fusions=10_000,
                 fusions_slack=0, collectives={}, donation=0)


def _load(name):
    return importlib.import_module(
        f"tests.lint_fixtures.graftcheck.{name}")


def _fixture_hlo(mod) -> str:
    if hasattr(mod, "hlo"):
        return mod.hlo()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        if getattr(mod, "X64", False):
            with jax.enable_x64(True):
                return mod.build().compile().as_text()
        return mod.build().compile().as_text()


# ---------------------------------------------------------------------
@pytest.mark.parametrize("name", FIXTURES)
def test_bad_fixture_yields_exact_finding_ids(name):
    mod = _load(name)
    spec = JitProgram(name=mod.NAME, **mod.CONTRACT)
    txt = _fixture_hlo(mod)
    findings = check_program(spec, txt, dict(mod.ENTRY))
    assert sorted(f.rule for f in findings) == sorted(mod.EXPECT), \
        [(f.rule, f.message) for f in findings]
    for f in findings:
        assert f.program == mod.NAME
        assert f.rule in RULE_NAMES


def test_fixture_defect_is_contract_relative():
    """The same compiled artifacts pass under contracts that permit
    them — the checks gate the CONTRACT, not the construct."""
    mod = _load("bad_donation.py"[:-3])
    txt = _fixture_hlo(mod)
    ok = check_program(JitProgram(name="n"), txt, dict(WIDE_OPEN))
    assert ok == []  # no donation declared -> no GC101

    mod = _load("bad_collective.py"[:-3])
    txt = _fixture_hlo(mod)
    cols = collective_census(txt)
    assert cols  # the psum is really there
    entry = dict(WIDE_OPEN)
    entry["collectives"] = cols
    ok = check_program(JitProgram(name="n", collective=True), txt,
                      entry)
    assert ok == []

    mod = _load("bad_f64.py"[:-3])
    txt = _fixture_hlo(mod)
    ok = check_program(JitProgram(name="n", allow_f64=True), txt,
                      dict(WIDE_OPEN))
    assert ok == []


def test_allow_list_suppresses_rule():
    mod = _load("bad_callback")
    txt = _fixture_hlo(mod)
    entry = dict(mod.ENTRY)
    entry["allow"] = ["GC301"]
    assert check_program(JitProgram(name="n", **mod.CONTRACT), txt,
                         entry) == []


def test_cold_program_may_call_back():
    mod = _load("bad_callback")
    txt = _fixture_hlo(mod)
    assert host_callback_lines(txt)
    spec = JitProgram(name="n", hot=False)
    assert check_program(spec, txt, dict(WIDE_OPEN)) == []


# --- parser unit tests (no jax) --------------------------------------
ALIAS_HDR = ("HloModule jit_f, is_scheduled=true, input_output_alias="
             "{ {}: (0, {}, may-alias), {1}: (2, {}, must-alias) }, "
             "entry_computation_layout={(f32[8]{0})->f32[8]{0}}\n\n"
             "ENTRY %main.3 (Arg_0.1: f32[8]) -> f32[8] {\n"
             "  %Arg_0.1 = f32[8]{0} parameter(0)\n"
             "  ROOT %add.2 = f32[8]{0} add(f32[8]{0} %Arg_0.1, "
             "f32[8]{0} %Arg_0.1)\n"
             "}\n")


def test_alias_parsing():
    assert aliased_param_count(ALIAS_HDR) == 2
    assert aliased_param_count(ALIAS_HDR.replace(
        "input_output_alias={ {}: (0, {}, may-alias), "
        "{1}: (2, {}, must-alias) }, ", "")) == 0


def test_module_op_counts_exclude_fusion_bodies():
    txt = (
        "HloModule m, entry_computation_layout={()->f32[8]{0}}\n\n"
        "%fused_computation (p: f32[8]) -> f32[8] {\n"
        "  %p = f32[8]{0} parameter(0)\n"
        "  %m1 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p)\n"
        "  ROOT %a1 = f32[8]{0} add(f32[8]{0} %m1, f32[8]{0} %p)\n"
        "}\n\n"
        "ENTRY %main (Arg: f32[8]) -> f32[8] {\n"
        "  %Arg = f32[8]{0} parameter(0)\n"
        "  ROOT %f = f32[8]{0} fusion(f32[8]{0} %Arg), kind=kLoop, "
        "calls=%fused_computation\n"
        "}\n")
    ops = module_op_counts(txt)
    assert ops["fusion"] == 1
    assert "multiply" not in ops  # inside the fusion body
    assert nontrivial_total(ops) == 1


def test_dynamic_shape_detection_forms():
    mod = _load("bad_dynamic")
    lines = dynamic_shape_lines(mod.hlo())
    assert len(lines) == 1 and "set-dimension-size" in lines[0][1]
    pad = ('ENTRY %m (a: f32[8]) -> f32[8] {\n'
           '  %a = f32[8]{0} parameter(0)\n'
           '  ROOT %c = f32[8]{0} custom-call(f32[8]{0} %a), '
           'custom_call_target="PadToStatic"\n}\n')
    assert dynamic_shape_lines(pad)


def test_wide_dtype_detection_ignores_f32():
    mod = _load("bad_donation")
    txt = _fixture_hlo(mod)
    assert wide_dtype_lines(txt) == []


# --- budgets + manifest workflow -------------------------------------
def test_budget_findings_fire_past_slack():
    mod = _load("bad_donation")
    txt = _fixture_hlo(mod)
    cur = measure(txt)
    entry = dict(WIDE_OPEN)
    entry.update(ops=max(cur["ops"] - 1, 0), ops_slack=0,
                 fusions=0, fusions_slack=0, donation=0)
    spec = JitProgram(name="n")  # no donation declared
    rules = sorted(f.rule for f in check_program(spec, txt, entry))
    assert "GC601" in rules
    # inside slack -> silent
    entry.update(ops_slack=1 + cur["fusions"] * 0 + 1,
                 fusions=cur["fusions"])
    assert all(f.rule != "GC601"
               for f in check_program(spec, txt, entry))


def test_missing_contract_and_stale_entries():
    mod = _load("bad_donation")
    txt = _fixture_hlo(mod)
    spec = JitProgram(name="n")
    rules = [f.rule for f in check_program(spec, txt, None)]
    assert "GC002" in rules
    stale = stale_entries({"programs": {"ghost": {}}}, ["real"])
    assert [f.rule for f in stale] == ["GC003"]
    assert stale[0].program == "ghost"


def test_update_manifest_preserves_human_fields(tmp_path):
    path = str(tmp_path / "contracts.json")
    cur = {"config": {"backend": "cpu"},
           "programs": {"p": {"ops": 10, "fusions": 2,
                              "collectives": {}, "donation": 1}}}
    m1 = update_manifest(cur, path)
    assert m1["programs"]["p"]["ops_slack"] == 8  # default floor
    # human edits slack + allow; a re-update must keep both
    m1["programs"]["p"]["ops_slack"] = 3
    m1["programs"]["p"]["allow"] = ["GC202"]
    m1["programs"]["p"]["note"] = "why"
    with open(path, "w") as f:
        json.dump(m1, f)
    cur["programs"]["p"]["ops"] = 12
    m2 = update_manifest(cur, path)
    p = m2["programs"]["p"]
    assert p["ops"] == 12 and p["ops_slack"] == 3
    assert p["allow"] == ["GC202"] and p["note"] == "why"
    # untouched programs survive a partial update
    m2["programs"]["q"] = {"ops": 1, "fusions": 0}
    with open(path, "w") as f:
        json.dump(m2, f)
    m3 = update_manifest(cur, path)
    assert "q" in m3["programs"]


def test_committed_manifest_matches_builder_set():
    """Every example builder has a committed contract and vice versa —
    the fast half of the repo gate (the compile sweep is the slow
    half, tests/test_graftcheck_repo.py)."""
    from tools.graftcheck.programs import BUILDERS
    manifest = load_manifest()
    assert sorted(manifest["programs"]) == sorted(BUILDERS)
    assert stale_entries(manifest, list(BUILDERS)) == []


def test_census_reexport_is_shared_core():
    """ONE parser, two front-ends: hlo_census's census function IS the
    graftcheck core's (so the committed dispatch budget and the
    graftcheck sweeps can never disagree on counting rules)."""
    from tools import hlo_census
    from tools.graftcheck import hlo as core
    assert hlo_census.census_from_hlo is core.census_from_hlo


def test_reporters_and_sorting():
    from tools.graftcheck.reporters import render_json, render_table
    f1 = GcFinding("GC201", "b", "m1")
    f2 = GcFinding("GC101", "a", "m2", "d")
    cur = {"config": {}, "programs": {
        "a": {"ops": 1, "fusions": 0, "collectives": {},
              "donation": 1}}}
    ordered = sort_findings([f1, f2])
    assert [f.program for f in ordered] == ["a", "b"]
    table = render_table(ordered, cur)
    assert "GC101" in table and "donation" in table
    payload = json.loads(render_json(ordered, cur))
    assert payload["ok"] is False
    assert [x["rule"] for x in payload["findings"]] == \
        ["GC101", "GC201"]
    clean = json.loads(render_json([], cur))
    assert clean["ok"] is True


def test_cli_exit_codes():
    from tools.graftcheck.cli import main
    assert main(["--programs", "definitely_not_a_program"]) == 2
    assert main(["--check", "--programs", "finite_ok"]) == 0
