"""Round-over-round bench trend gate (ROADMAP item 5).

Parses the committed ``BENCH_r*.json`` series (the driver's round
files: ``{"n", "cmd", "rc", "tail", "parsed"}`` — every JSON result
line in ``tail`` is read, ``parsed`` is the headline), tracks the two
series that are *comparable across rounds*, writes a trend report, and
exits nonzero on a regression:

* ``cpu_fixed_baseline_throughput`` — the ONE pinned steady-state CPU
  configuration (``bench.py:CPU_BASELINE_ID``). Points are compared
  only when their ``baseline_config`` ids match: bumping the config id
  deliberately breaks the chain instead of flagging a bogus
  regression. Lower is worse; a drop of more than ``--threshold``
  (default 20%) between consecutive comparable rounds fails the gate.
* serving ``p99_ms`` — from any result line's ``serving`` block, keyed
  by (backend, buckets, batch_sizes) so only like-for-like serving
  measurements chain. Higher is worse.
* fleet ``p99_ms`` — from any result line's ``fleet`` block (the
  replica-pool soak, serving/fleet.py), keyed by (backend, replicas,
  models, buckets, batch_sizes, qps) so only like-for-like fleet
  soaks chain. Higher is worse.

The legacy headline (``higgs_like_train_throughput``) is REPORTED but
never gated: the r01-r05 history mixes row counts, iteration counts
and backends, which is exactly the noise the fixed baseline exists to
replace.

Stdlib-only on purpose: the CI job runs it without jax.

Usage::

    python tools/bench_trend.py [FILES...] [--threshold 0.2]
                                [--report trend_report.json] [--quiet]

No FILES -> ``BENCH_r*.json`` in the repo root, sorted. Exit codes:
0 = no regression, 1 = regression(s), 2 = no parsable input.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_THRESHOLD = 0.20

FIXED_METRIC = "cpu_fixed_baseline_throughput"
HEADLINE_METRIC = "higgs_like_train_throughput"
DISPATCH_METRIC = "dispatches_per_split"
MULTIBOOST_METRIC = "multiboost_speedup"


def extract_lines(text: str) -> List[Dict[str, Any]]:
    """Every parsable JSON result line in a blob (same acceptance rule
    as ``bench.find_result_line``, but keeping ALL lines)."""
    out = []
    for line in (text or "").splitlines():
        line = line.strip()
        if line.startswith("{") and '"metric"' in line:
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def round_label(path: str, data: Dict[str, Any]) -> str:
    m = re.search(r"r(\d+)", os.path.basename(path))
    if m:
        return f"r{int(m.group(1)):02d}"
    n = data.get("n")
    return f"r{int(n):02d}" if isinstance(n, (int, float)) else \
        os.path.basename(path)


def load_round(path: str) -> Optional[Dict[str, Any]]:
    """One round file -> {"label", "path", "lines"} or None."""
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        sys.stderr.write(f"bench_trend: skipping {path}: {e}\n")
        return None
    lines = extract_lines(data.get("tail", ""))
    parsed = data.get("parsed")
    if isinstance(parsed, dict) and parsed.get("metric") \
            and parsed not in lines:
        lines.append(parsed)
    return {"label": round_label(path, data), "path": path,
            "lines": lines}


def _fixed_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    """The round's fixed-baseline measurement: an explicit
    cpu_fixed_baseline_throughput line, else a headline that reused
    the fixed config as its CPU fallback (source=cpu_fixed_baseline).
    The LAST matching line wins (bench prints escalating attempts).
    The line's per-phase wall-time decomposition (``phases``) rides
    along for regression attribution."""
    found = None
    for ln in lines:
        if ln.get("metric") == FIXED_METRIC \
                or (ln.get("metric") == HEADLINE_METRIC
                    and ln.get("source") == "cpu_fixed_baseline"):
            if ln.get("value") is not None \
                    and ln.get("baseline_config"):
                found = {"value": float(ln["value"]),
                         "key": str(ln["baseline_config"])}
                ph = ln.get("phases")
                if isinstance(ph, dict) and ph:
                    found["phases"] = {str(k): float(v)
                                       for k, v in ph.items()
                                       if isinstance(v, (int, float))}
    return found


def phase_shares(phases: Dict[str, float]) -> Dict[str, float]:
    """Normalize absolute per-phase seconds into shares of the total
    (shares compare across rounds even when the absolute wall time
    moved — which is exactly the regression case)."""
    tot = sum(v for v in phases.values() if v > 0)
    if tot <= 0:
        return {}
    return {k: round(v / tot, 4) for k, v in phases.items() if v >= 0}


def attribute_regression(prev_phases: Dict[str, float],
                         cur_phases: Dict[str, float]
                         ) -> Optional[Dict[str, Any]]:
    """Name the phase whose share of the wall time GREW the most
    between two comparable rounds — when the headline regresses, that
    phase is where the regression lives. Returns None when either
    round lacks a phase decomposition."""
    ps, cs = phase_shares(prev_phases or {}), \
        phase_shares(cur_phases or {})
    if not ps or not cs:
        return None
    deltas = {k: round(cs.get(k, 0.0) - ps.get(k, 0.0), 4)
              for k in set(ps) | set(cs)}
    worst = max(deltas, key=lambda k: deltas[k])
    return {
        "phase": worst,
        "from_share": ps.get(worst, 0.0),
        "to_share": cs.get(worst, 0.0),
        "share_delta": deltas[worst],
        "share_deltas": dict(sorted(deltas.items(),
                                    key=lambda kv: -kv[1])),
    }


def _serving_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    """The round's serving p99, keyed by the measurement shape."""
    found = None
    for ln in lines:
        sv = ln.get("serving")
        if not isinstance(sv, dict) or sv.get("p99_ms") is None:
            continue
        key = json.dumps({
            "backend": ln.get("backend"),
            "buckets": sv.get("buckets"),
            "batch_sizes": sv.get("batch_sizes"),
            "mode": sv.get("mode"),
        }, sort_keys=True)
        found = {"value": float(sv["p99_ms"]), "key": key,
                 "p50": sv.get("p50_ms"), "p95": sv.get("p95_ms")}
    return found


def _fleet_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    """The round's fleet-soak p99, keyed by the soak shape."""
    found = None
    for ln in lines:
        fv = ln.get("fleet")
        if not isinstance(fv, dict) or fv.get("p99_ms") is None:
            continue
        key = json.dumps({
            "backend": fv.get("backend", ln.get("backend")),
            "replicas": fv.get("replicas"),
            "models": fv.get("models"),
            "buckets": fv.get("buckets"),
            "batch_sizes": fv.get("batch_sizes"),
            "qps": fv.get("offered_qps"),
        }, sort_keys=True)
        found = {"value": float(fv["p99_ms"]), "key": key,
                 "p50": fv.get("p50_ms"),
                 "throughput_rps": fv.get("throughput_rps"),
                 "shed_rate": fv.get("shed_rate"),
                 "availability": fv.get("availability")}
    return found


def _dispatch_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    """The round's census-derived dispatches/split (bench.py
    run_dispatch_census): the serial grow program's compiled while-body
    op count on the fixed CPU config — lower is better; keyed by the
    baseline config id so shape bumps break the chain deliberately.
    The value also rides the cpu_fixed_baseline_throughput line."""
    found = None
    for ln in lines:
        v = None
        if ln.get("metric") == DISPATCH_METRIC:
            v = ln.get("value")
        elif ln.get("metric") == FIXED_METRIC:
            v = ln.get("dispatches_per_split")
        if v is not None and ln.get("baseline_config"):
            found = {"value": float(v),
                     "key": str(ln["baseline_config"])}
    return found


def _multiboost_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    """The round's multiboost sweep speedup (bench.py
    run_multiboost_sweep → tools/multiboost_dryrun): batched-sweep
    wall time vs the train-in-a-loop foil for the same models, keyed
    by the sweep shape — higher is better. Only ``ok`` runs (all
    models batched, byte-identical, dispatch budget met) chain; a
    failing dryrun trips CI's own exit code and must not seed the
    trend with a broken point."""
    found = None
    for ln in lines:
        if ln.get("metric") != MULTIBOOST_METRIC \
                or ln.get("value") is None or not ln.get("ok"):
            continue
        key = json.dumps({"models": ln.get("models"),
                          "rows": ln.get("rows"),
                          "iters": ln.get("iters")}, sort_keys=True)
        found = {"value": float(ln["value"]), "key": key,
                 "dispatch_ratio": ln.get("dispatch_ratio"),
                 "batched_s": ln.get("batched_s"),
                 "loop_s": ln.get("loop_s")}
    return found


def _fleet_isolation_point(lines: List[Dict]
                           ) -> Optional[Dict[str, Any]]:
    """The round's process-isolation p99 (bench.py
    measure_fleet_isolation): the process-mode fleet soak p99, keyed
    by the measurement shape, with the thread-mode p99 and the
    restart-to-ready latency carried alongside. Higher is worse."""
    found = None
    for ln in lines:
        fi = ln.get("fleet_isolation")
        if not isinstance(fi, dict) or fi.get("process_p99_ms") is None:
            continue
        key = json.dumps({
            "backend": ln.get("backend"),
            "replicas": fi.get("replicas"),
            "buckets": fi.get("buckets"),
            "qps": fi.get("offered_qps"),
        }, sort_keys=True)
        found = {"value": float(fi["process_p99_ms"]), "key": key,
                 "thread_p99_ms": fi.get("thread_p99_ms"),
                 "restart_ready_ms": fi.get("restart_ready_ms"),
                 "process_overhead_pct": fi.get(
                     "process_overhead_pct")}
    return found


def _single_row_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    """The round's AOT single-row serving p99 (bench.py
    measure_aot_serving inside the fleet_isolation block): a
    sequential closed loop of 1-row predicts through the process
    fleet's AOT device route — the per-call floor of the zero-Python
    hot path. Higher is worse. The shm/JSON large-batch legs ride
    along for gate-trip leg attribution."""
    found = None
    for ln in lines:
        fi = ln.get("fleet_isolation")
        if not isinstance(fi, dict) \
                or fi.get("single_row_p99_ms") is None:
            continue
        key = json.dumps({
            "backend": ln.get("backend"),
            "buckets": fi.get("buckets"),
        }, sort_keys=True)
        found = {"value": float(fi["single_row_p99_ms"]), "key": key,
                 "aot_p99_ms": fi.get("aot_p99_ms"),
                 "shm_large_batch_p99_ms": fi.get(
                     "shm_large_batch_p99_ms"),
                 "json_large_batch_p99_ms": fi.get(
                     "json_large_batch_p99_ms"),
                 "aot_restart_ready_ms": fi.get(
                     "aot_restart_ready_ms")}
    return found


def _shm_batch_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    """The round's shm-transport large-batch p99 (same bench block):
    the batch leg that rides the shared-memory ring instead of JSON
    framing, keyed by the batch shape. Higher is worse."""
    found = None
    for ln in lines:
        fi = ln.get("fleet_isolation")
        if not isinstance(fi, dict) \
                or fi.get("shm_large_batch_p99_ms") is None:
            continue
        key = json.dumps({
            "backend": ln.get("backend"),
            "batch_rows": fi.get("aot_batch_rows"),
        }, sort_keys=True)
        found = {"value": float(fi["shm_large_batch_p99_ms"]),
                 "key": key,
                 "single_row_p99_ms": fi.get("single_row_p99_ms"),
                 "json_large_batch_p99_ms": fi.get(
                     "json_large_batch_p99_ms"),
                 "shm_speedup_pct": fi.get("shm_speedup_pct")}
    return found


def _rel_change(a, b) -> Optional[float]:
    try:
        a, b = float(a), float(b)
    except (TypeError, ValueError):
        return None
    return (b - a) / a if a > 0 else None


def attribute_hot_path_leg(trips: List[Dict[str, Any]],
                           series_name: str,
                           series: List[Tuple[str, Dict]],
                           threshold: float) -> None:
    """Name which leg of the zero-Python hot path a gate trip lives
    in: single rows travel JSON framing but run the AOT executables
    (the ``aot`` leg), large batches additionally ride the shm ring
    (the ``shm`` leg). A trip where BOTH legs worsened past the
    threshold is ``both``; a trip where only the other leg's series
    stayed flat pins the regression to this one."""
    pts = {label: pt for label, pt in series}
    for reg in trips:
        if reg.get("series") != series_name:
            continue
        prev = pts.get(reg["from_round"])
        cur = pts.get(reg["to_round"])
        if not prev or not cur:
            continue
        if series_name == "single_row_p99_ms":
            aot_chg = _rel_change(prev["value"], cur["value"])
            shm_chg = _rel_change(prev.get("shm_large_batch_p99_ms"),
                                  cur.get("shm_large_batch_p99_ms"))
        else:
            shm_chg = _rel_change(prev["value"], cur["value"])
            aot_chg = _rel_change(prev.get("single_row_p99_ms"),
                                  cur.get("single_row_p99_ms"))
        aot_bad = aot_chg is not None and aot_chg > threshold
        shm_bad = shm_chg is not None and shm_chg > threshold
        if aot_bad and shm_bad:
            leg = "both"
        elif aot_bad:
            leg = "aot"
        elif shm_bad:
            leg = "shm"
        else:
            leg = "aot" if series_name == "single_row_p99_ms" \
                else "shm"
        reg["leg"] = leg
        reg["leg_changes"] = {
            "aot_single_row_pct":
                None if aot_chg is None else round(aot_chg * 100, 2),
            "shm_large_batch_pct":
                None if shm_chg is None else round(shm_chg * 100, 2)}


def _mesh_scaling_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    """The round's mesh-scaling number (bench.py
    run_mesh_scaling_block): total ms/split across the mesh learner
    modes at the max device count, keyed by (backend, shape id) —
    lower is better. The per-mode curves and scaling efficiencies
    ride along for the report."""
    found = None
    for ln in lines:
        ms = ln.get("mesh_scaling")
        if ln.get("metric") != "mesh_scaling" \
                or not isinstance(ms, dict) \
                or ln.get("value") is None:
            continue
        key = json.dumps({
            "backend": ln.get("backend"),
            "config": ln.get("baseline_config"),
        }, sort_keys=True)
        found = {"value": float(ln["value"]), "key": key,
                 "devices": ms.get("devices"),
                 "modes": ms.get("modes"),
                 "speedup": ms.get("speedup")}
    return found


def _headline_point(lines: List[Dict]) -> Optional[Dict[str, Any]]:
    for ln in reversed(lines):
        if ln.get("metric") == HEADLINE_METRIC \
                and ln.get("value") is not None:
            return {"value": float(ln["value"]),
                    "backend": ln.get("backend"),
                    "rows": ln.get("rows")}
    return None


def _gate(series: List[Tuple[str, Dict]], higher_is_better: bool,
          threshold: float, name: str) -> List[Dict[str, Any]]:
    """Consecutive comparable points (equal ``key``) whose worsening
    exceeds the threshold. A regression between two points that both
    carry a ``phases`` decomposition additionally names the phase
    whose span share regressed (``attribution``) — the gate trip says
    *where*, not just *how much*."""
    regressions = []
    prev_label, prev = None, None
    for label, point in series:
        if prev is not None and point["key"] == prev["key"] \
                and prev["value"] > 0:
            change = (point["value"] - prev["value"]) / prev["value"]
            worsening = -change if higher_is_better else change
            if worsening > threshold:
                reg = {
                    "series": name,
                    "from_round": prev_label, "to_round": label,
                    "from_value": prev["value"],
                    "to_value": point["value"],
                    "change_pct": round(change * 100.0, 2),
                    "threshold_pct": round(threshold * 100.0, 2),
                    "key": point["key"],
                }
                attr = attribute_regression(prev.get("phases"),
                                            point.get("phases"))
                if attr is not None:
                    reg["attribution"] = attr
                regressions.append(reg)
        prev_label, prev = label, point
    return regressions


def analyze(rounds: List[Dict[str, Any]],
            threshold: float = DEFAULT_THRESHOLD) -> Dict[str, Any]:
    fixed, serving, headline, dispatch, fleet = [], [], [], [], []
    mesh, fleet_iso = [], []
    single_row, shm_batch, mboost = [], [], []
    for rnd in rounds:
        p = _fixed_point(rnd["lines"])
        if p is not None:
            fixed.append((rnd["label"], p))
        p = _serving_point(rnd["lines"])
        if p is not None:
            serving.append((rnd["label"], p))
        p = _headline_point(rnd["lines"])
        if p is not None:
            headline.append((rnd["label"], p))
        p = _dispatch_point(rnd["lines"])
        if p is not None:
            dispatch.append((rnd["label"], p))
        p = _fleet_point(rnd["lines"])
        if p is not None:
            fleet.append((rnd["label"], p))
        p = _mesh_scaling_point(rnd["lines"])
        if p is not None:
            mesh.append((rnd["label"], p))
        p = _fleet_isolation_point(rnd["lines"])
        if p is not None:
            fleet_iso.append((rnd["label"], p))
        p = _single_row_point(rnd["lines"])
        if p is not None:
            single_row.append((rnd["label"], p))
        p = _shm_batch_point(rnd["lines"])
        if p is not None:
            shm_batch.append((rnd["label"], p))
        p = _multiboost_point(rnd["lines"])
        if p is not None:
            mboost.append((rnd["label"], p))

    regressions = _gate(fixed, True, threshold,
                        FIXED_METRIC)
    regressions += _gate(serving, False, threshold, "serving_p99_ms")
    regressions += _gate(dispatch, False, threshold, DISPATCH_METRIC)
    regressions += _gate(fleet, False, threshold, "fleet_p99_ms")
    regressions += _gate(mesh, False, threshold, "mesh_scaling_ms")
    regressions += _gate(fleet_iso, False, threshold,
                         "fleet_isolation_p99_ms")
    sr_trips = _gate(single_row, False, threshold,
                     "single_row_p99_ms")
    attribute_hot_path_leg(sr_trips, "single_row_p99_ms",
                           single_row, threshold)
    shm_trips = _gate(shm_batch, False, threshold,
                      "shm_large_batch_p99_ms")
    attribute_hot_path_leg(shm_trips, "shm_large_batch_p99_ms",
                           shm_batch, threshold)
    regressions += sr_trips + shm_trips
    regressions += _gate(mboost, True, threshold, MULTIBOOST_METRIC)
    return {
        "rounds": [r["label"] for r in rounds],
        "threshold_pct": round(threshold * 100.0, 2),
        # per-round phase-share decomposition of the fixed baseline
        # (informational; the attribution inside a regression entry is
        # the gated use of this data)
        "phase_shares": [
            {"round": lb, "key": pt["key"],
             "shares": phase_shares(pt["phases"])}
            for lb, pt in fixed if pt.get("phases")],
        "series": {
            FIXED_METRIC: [
                {"round": lb, **pt} for lb, pt in fixed],
            "serving_p99_ms": [
                {"round": lb, **pt} for lb, pt in serving],
            "fleet_p99_ms": [
                {"round": lb, **pt} for lb, pt in fleet],
            "mesh_scaling_ms": [
                {"round": lb, **pt} for lb, pt in mesh],
            "fleet_isolation_p99_ms": [
                {"round": lb, **pt} for lb, pt in fleet_iso],
            "single_row_p99_ms": [
                {"round": lb, **pt} for lb, pt in single_row],
            "shm_large_batch_p99_ms": [
                {"round": lb, **pt} for lb, pt in shm_batch],
            DISPATCH_METRIC: [
                {"round": lb, **pt} for lb, pt in dispatch],
            MULTIBOOST_METRIC: [
                {"round": lb, **pt} for lb, pt in mboost],
            # informational only — config drifts across rounds
            HEADLINE_METRIC + "_ungated": [
                {"round": lb, **pt} for lb, pt in headline],
        },
        "gated_points": {FIXED_METRIC: len(fixed),
                         "serving_p99_ms": len(serving),
                         "fleet_p99_ms": len(fleet),
                         "mesh_scaling_ms": len(mesh),
                         "fleet_isolation_p99_ms": len(fleet_iso),
                         "single_row_p99_ms": len(single_row),
                         "shm_large_batch_p99_ms": len(shm_batch),
                         DISPATCH_METRIC: len(dispatch),
                         MULTIBOOST_METRIC: len(mboost)},
        "regressions": regressions,
        "verdict": "regression" if regressions else "ok",
    }


def render(report: Dict[str, Any]) -> str:
    L = [f"bench trend over rounds: {', '.join(report['rounds'])}",
         f"threshold: {report['threshold_pct']:.0f}%"]
    for name, pts in report["series"].items():
        L.append("")
        gated = "" if not name.endswith("_ungated") else " (not gated)"
        L.append(f"== {name}{gated} ==")
        if not pts:
            L.append("(no measurements in the series yet)")
            continue
        for pt in pts:
            extra = f"  [{pt['key']}]" if "key" in pt else ""
            L.append(f"{pt['round']:>6}  {pt['value']:>12.4f}{extra}")
    if report.get("phase_shares"):
        L.append("")
        L.append("== fixed-baseline phase shares (attribution "
                 "input) ==")
        for row in report["phase_shares"]:
            body = " ".join(
                f"{k}={100 * v:.0f}%" for k, v in sorted(
                    row["shares"].items(), key=lambda kv: -kv[1]))
            L.append(f"{row['round']:>6}  {body}")
    L.append("")
    if report["regressions"]:
        L.append("REGRESSIONS:")
        for r in report["regressions"]:
            L.append(
                f"  {r['series']}: {r['from_round']} -> "
                f"{r['to_round']}: {r['from_value']:.4f} -> "
                f"{r['to_value']:.4f} ({r['change_pct']:+.1f}% vs "
                f"{r['threshold_pct']:.0f}% allowed)")
            attr = r.get("attribution")
            if attr:
                L.append(
                    f"    attributed to phase '{attr['phase']}': "
                    f"span share {100 * attr['from_share']:.1f}% -> "
                    f"{100 * attr['to_share']:.1f}% "
                    f"({100 * attr['share_delta']:+.1f}pp)")
            if r.get("leg"):
                chg = r.get("leg_changes", {})
                L.append(
                    f"    attributed to the {r['leg']} leg "
                    f"(aot single-row "
                    f"{chg.get('aot_single_row_pct')}%, shm "
                    f"large-batch "
                    f"{chg.get('shm_large_batch_pct')}%)")
    else:
        L.append("verdict: ok (no gated regression)")
    return "\n".join(L) + "\n"


def main(argv: List[str]) -> int:
    threshold = DEFAULT_THRESHOLD
    report_path = None
    files: List[str] = []
    quiet = False
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--threshold":
            i += 1
            threshold = float(argv[i])
        elif a.startswith("--threshold="):
            threshold = float(a.split("=", 1)[1])
        elif a == "--report":
            i += 1
            report_path = argv[i]
        elif a.startswith("--report="):
            report_path = a.split("=", 1)[1]
        elif a == "--quiet":
            quiet = True
        elif a.startswith("--"):
            sys.stderr.write(__doc__ + f"\nunknown option {a}\n")
            return 2
        else:
            files.append(a)
        i += 1
    if not files:
        files = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if not files:
        sys.stderr.write("bench_trend: no BENCH round files found\n")
        return 2
    rounds = [r for r in (load_round(f) for f in files) if r]
    if not rounds:
        sys.stderr.write("bench_trend: no parsable round files\n")
        return 2
    report = analyze(rounds, threshold)
    if report_path:
        with open(report_path, "w") as fh:
            json.dump(report, fh, indent=1)
            fh.write("\n")
    if not quiet:
        sys.stdout.write(render(report))
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
