"""Set-up by layer, from the program's own ledger.

``setup_s`` is process start to window start. The program leaves one
``span`` record for every phase of table construction and booster
set-up and for every ``GBDT.train`` call, and one ``compile`` record a
stage of every registered or long compile, each with its start and end
as ``time.perf_counter()`` read them
(``lightgbm_tpu/observability/telemetry.py``; names in
``observability/scopes.py``). ``by_layer`` lays those intervals over
the stretch from process start to window start and gives every second
of it to one of five families, in this order of precedence:

  compile   any ``compile`` record (trace, lowering, XLA's compile or
            the persistent cache's load), whatever span it sits in
  table_io  ``lgbm.data.load_binary`` and ``lgbm.data.save_binary``
  binning   the ``lgbm.data.construct`` roots
  booster   the ``lgbm.setup`` roots
  warm_run  the ``train`` spans: the warm-up trees actually running

so the five are disjoint by construction, and what none of them covers
is the unattributed share: imports and runtime start-up, the
benchmark's own generator, its warm-up AUC. ``compile_miss`` is the
part of ``compile`` under backend records that the persistent cache
did not serve.

Every kind builds a second, small dataset and booster for its check
after the window, so only records that ended before the window opened
count. The anchor is the program's own: a kind's step is one
``GBDT.train`` call on the cell's booster, and each leaves a ``train``
record, so the window opened where the first of that booster's last
``facts["steps"]`` ``train`` spans begins (a few host instructions
after ``start_window`` read the clock), and the process started
``facts["setup_s"]`` seconds before that, on the clock the records are
stamped with. It needs neither the module that happens to be
``__main__`` nor an edit to the harness, so a test that calls
``run.main`` reads the same numbers as a driver's run. Where ``facts``
lacks any of the three, or the program leaves no such records (the
parent of the PR that added them), ``by_layer`` returns ``None`` and
every reader with it. Computed once per run and printed once, as
``info: setup_spans {...}``.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

from .trace_reduce import union_length

_MEMO = "_setup_by_layer"
TOP = 5                 # compile records named on the info line
FAMILIES = ("compile", "table_io", "binning", "booster", "warm_run")
# a span's family is that of the first name on its path
ROOTS = {"lgbm.data.construct": "binning",
         "lgbm.data.save_binary": "table_io",
         "lgbm.setup": "booster",
         "train": "warm_run"}
TABLE_IO = ("lgbm.data.load_binary", "lgbm.data.save_binary")
Interval = Tuple[float, float]


def window_start(facts, records) -> Optional[Tuple[float, float]]:
    """``(process start, window start)`` on ``time.perf_counter()``:
    the cell's booster is the last one set up on ``facts["rows"]`` rows
    that trained more often than the window has steps (the check's
    trains twice), and its last ``facts["steps"]`` ``train`` spans are
    the window's."""
    setup_s, rows = facts.get("setup_s"), facts.get("rows")
    steps = facts.get("steps")
    if setup_s is None or rows is None or not steps:
        return None
    boosters: List[Tuple[Any, List[float]]] = []   # (rows, train t0s)
    for r in records:
        if r.get("kind") != "span" or r.get("t0") is None:
            continue
        if r["name"] == "lgbm.setup":
            boosters.append((r.get("rows"), []))
        elif r["name"] == "train" and boosters:
            boosters[-1][1].append(float(r["t0"]))
    for booster_rows, trains in reversed(boosters):
        if booster_rows == rows and len(trains) > steps:
            t_window = trains[-steps]
            return t_window - float(setup_s), t_window
    return None


def _length(intervals: List[Interval]) -> float:
    return union_length([a for a, _ in intervals],
                        [b for _, b in intervals])


def reduce_ledger(records: List[Dict[str, Any]], t_start: float,
                  t_window: float) -> Optional[Dict[str, Any]]:
    """The records that ended inside ``[t_start, t_window]`` reduced to
    seconds by family, or ``None`` where no record carries a start and
    an end."""
    timed = [r for r in records
             if r.get("kind") in ("span", "compile")
             and r.get("t0") is not None and r.get("t1") is not None]
    if not timed:
        return None
    kept = [r for r in timed if r["t1"] <= t_window and r["t0"] >= t_start]
    by_family: Dict[str, List[Interval]] = {f: [] for f in FAMILIES}
    by_name: Dict[str, List[Interval]] = {}
    missed: List[Interval] = []
    orphans: List[str] = []
    compiles = []
    for r in kept:
        span = (float(r["t0"]), float(r["t1"]))
        if r["kind"] == "compile":
            by_family["compile"].append(span)
            by_name.setdefault("compile." + r.get("stage", "?"),
                               []).append(span)
            if r.get("stage") == "backend":
                compiles.append(r)
                if r.get("cache") != "hit":
                    missed.append(span)
            continue
        by_name.setdefault(r["name"], []).append(span)
        root = str(r.get("path") or r["name"]).split("/")[0]
        if root not in ROOTS:
            orphans.append(r.get("path") or r["name"])
        elif r["name"] in TABLE_IO:
            by_family["table_io"].append(span)
        elif r["name"] == root:
            by_family[ROOTS[root]].append(span)
    # a family's seconds: what it covers that no family before it does
    seconds: Dict[str, float] = {}
    covered: List[Interval] = []
    before = 0.0
    for family in FAMILIES:
        covered = covered + by_family[family]
        now = _length(covered)
        seconds[family] = now - before
        before = now
    compiles.sort(key=lambda r: -r["dur_s"])
    return {
        "seconds": seconds, "covered": before,
        "compile_miss": _length(missed),
        "setup_s": t_window - t_start,
        "spans": {name: _length(v) for name, v in sorted(by_name.items())},
        "backend": {"hit": sum(r.get("cache") == "hit" for r in compiles),
                    "miss": sum(r.get("cache") == "miss"
                                for r in compiles),
                    "none": sum(r.get("cache") == "none"
                                for r in compiles)},
        "top_compiles": [[r.get("program"), r.get("cache"),
                          round(float(r["dur_s"]), 3), r.get("parent")]
                         for r in compiles[:TOP]],
        "orphans": sorted(set(orphans)),
        "records": len(kept), "after_window": len(timed) - len(kept)}


def by_layer(facts) -> Optional[Dict[str, Any]]:
    """``reduce_ledger`` of this process's telemetry ring up to the
    window's start, or ``None`` where there is no anchor, no program or
    no timed record."""
    if _MEMO in facts:
        return facts[_MEMO]
    if facts.get("setup_s") is None:
        return None
    try:
        from lightgbm_tpu.observability.telemetry import get_telemetry
    except ImportError:
        return None
    records = get_telemetry().records
    anchor = window_start(facts, records)
    if anchor is None:
        return None
    result = reduce_ledger(records, *anchor)
    if result is not None:
        line = dict(result)
        for key in ("seconds", "spans"):
            line[key] = {k: round(v, 3) for k, v in result[key].items()}
        for key in ("covered", "compile_miss", "setup_s"):
            line[key] = round(result[key], 3)
        print(f"info: setup_spans {json.dumps(line)}", flush=True)
    facts[_MEMO] = result
    return result


def seconds(facts, family: str) -> Optional[float]:
    """Seconds of set-up in ``family`` (one of ``FAMILIES``, or
    ``compile_miss``)."""
    got = by_layer(facts)
    if got is None:
        return None
    if family == "compile_miss":
        return got["compile_miss"]
    return got["seconds"][family]


def unattributed_share(facts) -> Optional[float]:
    """Percent of ``setup_s`` that no span or compile record covers."""
    got = by_layer(facts)
    if got is None or got["setup_s"] <= 0:
        return None
    return 100.0 * (1.0 - got["covered"] / got["setup_s"])
