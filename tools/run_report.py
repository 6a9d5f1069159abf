"""Render a telemetry JSONL trace into a phase/throughput report.

Usage:  python tools/run_report.py <trace.jsonl | dump.crash.json>
                                   [--json]

Also renders a crash flight-recorder dump
(``<telemetry_out>.crash.json``, lightgbm_tpu/observability/
flightrec.py): a file whose whole body is one JSON object with a
``flight_recorder`` key is detected and rendered as the black-box
report (reason, faulting iteration, fingerprints, guard trips, the
last ring records) instead of as a trace.

Reads the trace written by LGBM_TPU_TELEMETRY / telemetry_out (schema:
docs/Observability.md) and prints, for the LAST training run in the
file: backend provenance, compile-vs-steady-state breakdown, the
per-phase timing table (grad/grow/tree/update — host phase wall times
from the per-iteration records; the fused driver's device time by
scope comes from a profile, docs/Observability.md), throughput,
counters and final eval results. ``--json`` emits
the same digest as one machine-readable JSON object (used by CI).

Stdlib-only on purpose: the report must render on any box, including
ones without jax installed.
"""

import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _classify_probe(detail: str) -> str:
    """Reason-code fallback for probe records written before the
    taxonomy existed (tools/probe_taxonomy.py)."""
    try:
        from tools.probe_taxonomy import classify_probe_failure
        return classify_probe_failure(detail)
    except Exception:
        return "unknown"


def load(path: str) -> List[Dict[str, Any]]:
    records = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # tolerate a torn tail line
    return records


def _last(records, kind):
    out = None
    for r in records:
        if r.get("kind") == kind:
            out = r
    return out


def _union_len(intervals) -> float:
    """Total length the ``(start, end)`` intervals cover."""
    total, reach = 0.0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


_SPAN_FIELDS = ("rows", "columns", "source", "sample_rows", "bytes",
                "groups", "learner", "plan")


def setup_digest(records: List[Dict[str, Any]]):
    """The set-up timeline: every ``span`` and ``compile`` record that
    ended by the end of the first ``train`` span (the first tree), on
    seconds since telemetry started. ``None`` where the trace holds no
    timed record (telemetry older than the span ledger)."""
    timed = [r for r in records
             if r.get("kind") in ("span", "compile")
             and r.get("t0") is not None and r.get("t1") is not None]
    if not timed:
        return None
    # a record is emitted as its region ends: t is t1 on the file's
    # clock, so their difference is when telemetry started
    origin = min(r["t1"] - r.get("t", 0.0) for r in timed)
    first = next((r for r in timed if r["kind"] == "span"
                  and r.get("name") == "train"), None)
    end = first["t1"] if first else max(r["t1"] for r in timed)
    kept = [r for r in timed if r["t1"] <= end]
    compiles = [r for r in kept if r["kind"] == "compile"]
    spans = []
    for r in sorted((r for r in kept if r["kind"] == "span"),
                    key=lambda r: (r["t0"], -r["t1"])):
        inside = [(max(c["t0"], r["t0"]), min(c["t1"], r["t1"]))
                  for c in compiles
                  if c["t0"] < r["t1"] and c["t1"] > r["t0"]]
        spans.append({
            "name": r["name"], "depth": str(r.get("path", "")).count("/"),
            "start_s": r["t0"] - origin, "dur_s": r["t1"] - r["t0"],
            "compile_s": _union_len(inside),
            "fields": {k: r[k] for k in _SPAN_FIELDS
                       if r.get(k) is not None}})
    programs: Dict[str, Dict[str, Any]] = {}
    for c in compiles:
        p = programs.setdefault(c.get("program") or "?", {
            "trace": 0.0, "lower": 0.0, "backend": 0.0, "cache": "",
            "parent": c.get("parent")})
        p[c.get("stage", "backend")] += c["t1"] - c["t0"]
        if c.get("cache"):
            p["cache"] = c["cache"]
    roots = [(r["t0"], r["t1"]) for r in kept
             if r["kind"] == "span" and not r.get("parent")]
    everything = roots + [(c["t0"], c["t1"]) for c in compiles]
    return {"first_tree_s": end - origin,
            "covered_s": _union_len(everything),
            "compile_s": _union_len([(c["t0"], c["t1"])
                                     for c in compiles]),
            "spans": spans, "programs": programs}


def digest(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate a record list into the report's data model."""
    run = _last(records, "run_start") or {}
    end = _last(records, "train_end") or {}
    iters = [r for r in records if r.get("kind") == "iter"]
    blocks = [r for r in records if r.get("kind") == "block"]

    phases: Dict[str, Dict[str, float]] = {}
    for r in iters:
        for name, dur in (r.get("phases") or {}).items():
            p = phases.setdefault(name, {"total_s": 0.0, "count": 0})
            p["total_s"] += float(dur)
            p["count"] += 1
    for p in phases.values():
        p["total_s"] = round(p["total_s"], 6)
        p["mean_s"] = round(p["total_s"] / max(p["count"], 1), 6)

    # per-iteration dispatch/host-sync accounting (counts tables on the
    # iter records; see Telemetry.count_iter)
    iter_counts: Dict[str, Dict[str, float]] = {}
    for r in iters:
        for name, v in (r.get("counts") or {}).items():
            c = iter_counts.setdefault(name, {"total": 0.0, "iters": 0})
            c["total"] += float(v)
            c["iters"] += 1
    for c in iter_counts.values():
        c["per_iter"] = round(c["total"] / max(c["iters"], 1), 3)

    n_iters = int(end.get("iters") or 0) or (
        len(iters) + sum(int(b.get("iters", 0)) for b in blocks))
    rows = int(end.get("num_data") or
               (iters[-1].get("num_data") if iters else 0) or 0)
    dur = float(end.get("dur_s") or 0.0)
    block_rows_per_s = [b["rows_per_s"] for b in blocks
                       if b.get("rows_per_s")]

    evals: Dict[str, float] = {}
    ev = _last(records, "eval")
    if ev:
        for ds, metric, value, _bigger in ev.get("results", []):
            evals[f"{ds} {metric}"] = value

    serving = _last(records, "serving_stats") or {}
    serving = {k: v for k, v in serving.items()
               if k not in ("kind", "t")}

    fleet = _last(records, "fleet_stats") or {}
    fleet = {k: v for k, v in fleet.items()
             if k not in ("kind", "t")}

    # histogram snapshots (kind=hist, emitted by the live metrics
    # plane on engine stop): keep the LAST snapshot per (name, labels)
    hists: Dict[str, Dict[str, Any]] = {}
    for r in records:
        if r.get("kind") != "hist" or not r.get("name"):
            continue
        labels = r.get("labels") or {}
        key = r["name"] + "".join(
            f"{{{k}={labels[k]}}}" for k in sorted(labels))
        hists[key] = {k: r.get(k) for k in
                      ("name", "labels", "count", "sum",
                       "p50", "p95", "p99")}

    probe_rec = _last(records, "probe")

    # probe timeline: EVERY probe verdict in the file, classified —
    # bench appends across rounds, so this is the round-over-round
    # failure-mode history ROADMAP item 6 asks for
    probe_history = []
    for r in records:
        if r.get("kind") != "probe":
            continue
        code = r.get("reason_code")
        if code is None and r.get("verdict") != "ok":
            code = _classify_probe(str(r.get("reason", "")))
        probe_history.append({
            "verdict": r.get("verdict"),
            "reason_code": code,
            "reason": str(r.get("reason", ""))[:120],
            "dur_s": r.get("dur_s"),
            "wall_time": r.get("wall_time")})

    # replica lifecycle timeline (serving/procfleet.py + fleet.py):
    # every spawn/ready/death/respawn/quarantine event in the trace,
    # with the worker reason codes (tools/probe_taxonomy.py
    # WORKER_REASON_CODES) — the same diagnosability treatment the
    # TPU probe history gets below
    replica_timeline = []
    for r in records:
        if r.get("kind") != "replica":
            continue
        replica_timeline.append({
            "t": r.get("t"),
            "rid": r.get("rid"),
            "event": r.get("event"),
            "state": r.get("state"),
            "pid": r.get("pid"),
            "incarnation": r.get("incarnation"),
            "reason_code": r.get("reason_code"),
            "ready_ms": r.get("ready_ms"),
            "restarts": r.get("restarts"),
            "detail": str(r.get("detail", ""))[:80]})

    # elastic distributed-training timeline (robustness/elastic.py):
    # watchdog lifecycle, peer hellos/goodbyes, and classified aborts
    # (ELASTIC_REASON_CODES) — the training-side twin of the replica
    # timeline above
    elastic_timeline = []
    for r in records:
        if r.get("kind") not in ("elastic", "elastic_abort"):
            continue
        elastic_timeline.append({
            "t": r.get("t"),
            "event": r.get("event") or r.get("kind"),
            "rank": r.get("rank"),
            "iteration": r.get("iteration"),
            "reason_code": r.get("reason_code"),
            "world_size": r.get("world_size"),
            "detail": str(r.get("detail", ""))[:80]})

    # SLO burn-rate history (observability/slo.py `slo` telemetry
    # records): latest state per spec plus how often it was breached
    # (every configured window burning > 1.0 at once)
    slo: Dict[str, Dict[str, Any]] = {}
    for r in records:
        if r.get("kind") != "slo" or not r.get("name"):
            continue
        e = slo.setdefault(str(r["name"]),
                           {"evaluations": 0, "breaches": 0})
        e["evaluations"] += 1
        if r.get("breached"):
            e["breaches"] += 1
        e["slo_kind"] = r.get("slo_kind")
        e["objective"] = r.get("objective")
        e["max_burn"] = r.get("max_burn")
        e["windows"] = r.get("windows")

    # multiboost bucketing report (engine.train_many / batched lgb.cv):
    # how many models rode batched grow programs vs the loop fallback
    mb = _last(records, "multiboost_report")
    multiboost = None if mb is None else {
        k: v for k, v in mb.items() if k not in ("kind", "t")}

    # per-tenant pipeline cycles (pipeline/driver.py tenant mode): one
    # row per (cycle, tenant) — the refit-and-promote timeline of the
    # whole tenant fleet
    tenant_cycles = []
    for r in records:
        if r.get("kind") != "pipeline_tenant_cycle":
            continue
        tenant_cycles.append({
            "cycle": r.get("cycle"), "tenant": r.get("tenant"),
            "candidate": r.get("candidate"),
            "status": r.get("status"),
            "promoted": r.get("promoted"),
            "rows": r.get("rows")})

    counters_all = end.get("counters") or {}
    robustness = {k: v for k, v in counters_all.items()
                  if k.startswith(("guard.", "checkpoint.", "retry.",
                                   "faults.", "elastic."))}
    # mesh collective traffic: the comm recipes' per-op byte/call
    # counters (learner/comm.py _count_collective — trace-time bytes
    # per compiled grow program) -> {op: {bytes, calls}}
    comms: Dict[str, Dict[str, float]] = {}
    for k, v in counters_all.items():
        if not k.startswith("comm."):
            continue
        for suffix in ("_sent_bytes", "_bytes", "_calls"):
            if k.endswith(suffix):
                op = k[len("comm."):-len(suffix)]
                comms.setdefault(op, {})[suffix[1:]] = float(v)
                break
    ingest = {k.split(".", 1)[1]: v for k, v in counters_all.items()
              if k.startswith("ingest.")}

    return {
        "robustness": robustness,
        "multiboost": multiboost,
        "tenant_cycles": tenant_cycles,
        "comms": comms,
        "ingest": ingest,
        "replica_timeline": replica_timeline,
        "elastic_timeline": elastic_timeline,
        "backend": run.get("backend"),
        "device_count": run.get("device_count"),
        "serving": serving,
        "fleet": fleet,
        "slo": slo,
        "hists": hists,
        "tpu_probe": None if probe_rec is None else {
            k: probe_rec.get(k) for k in
            ("verdict", "reason", "reason_code", "dur_s")},
        "probe_history": probe_history,
        "jax_version": run.get("jax_version"),
        "config": run.get("config") or {},
        "iters": n_iters,
        "num_data": rows,
        "dur_s": dur,
        "rows_per_s": end.get("rows_per_s"),
        "block_rows_per_s": block_rows_per_s,
        "compile": end.get("compile") or {},
        "setup": setup_digest(records),
        "phases": phases,
        "iter_counts": iter_counts,
        "fused_block_hits": int((end.get("counters") or {}).get(
            "fused.block_hits", 0)) or len(blocks),
        "phase_totals": end.get("phase_totals") or {},
        "counters": end.get("counters") or {},
        "memory": end.get("memory") or {},
        "eval": evals,
        "eval_iter": ev.get("iter") if ev else None,
    }


def _render_setup(su: Dict[str, Any]) -> List[str]:
    """Why the job took N seconds before its first tree."""
    L = ["", "== set-up (to the end of the first train call) =="]
    L.append(f"first tree after {su['first_tree_s']:.3f}s of telemetry: "
             f"{su['covered_s']:.3f}s under a span or a compile, "
             f"{su['compile_s']:.3f}s of it compiling")
    L.append(f"{'span':<34}{'start_s':>9}{'dur_s':>9}{'compile_s':>10}"
             "  fields")
    for sp in su["spans"]:
        name = "  " * sp["depth"] + sp["name"]
        fields = " ".join(f"{k}={v}" for k, v in sp["fields"].items())
        L.append(f"{name:<34}{sp['start_s']:>9.3f}{sp['dur_s']:>9.3f}"
                 f"{sp['compile_s']:>10.3f}  {fields}"[:160])
    if su["programs"]:
        L.append(f"{'compiled program':<28}{'trace_s':>9}{'lower_s':>9}"
                 f"{'backend_s':>10}  cache  under")
        ranked = sorted(su["programs"].items(), key=lambda kv: -(
            kv[1]["trace"] + kv[1]["lower"] + kv[1]["backend"]))
        for name, p in ranked[:12]:
            L.append(f"{name:<28}{p['trace']:>9.3f}{p['lower']:>9.3f}"
                     f"{p['backend']:>10.3f}  {p['cache'] or '-':<5}  "
                     f"{p['parent'] or '-'}")
    return L


def render(records: List[Dict[str, Any]]) -> str:
    d = digest(records)
    L: List[str] = []
    L.append("== run ==")
    L.append(f"backend={d['backend']} devices={d['device_count']} "
             f"jax={d['jax_version']}")
    if d["config"]:
        cfg = " ".join(f"{k}={v}" for k, v in sorted(
            d["config"].items()))
        L.append(f"config: {cfg}")

    L.append("")
    L.append("== compile vs steady state ==")
    comp = d["compile"]
    L.append(f"compiles={comp.get('count', 0)} "
             f"compile_s={comp.get('seconds', 0.0):.3f} "
             f"trace_s={comp.get('trace_seconds', 0.0):.3f}")
    L.append(f"train wall: {d['dur_s']:.3f}s for {d['iters']} iters "
             f"on {d['num_data']} rows")
    if d["rows_per_s"]:
        L.append(f"throughput: {d['rows_per_s'] / 1e6:.4f} "
                 "Mrow-iters/s (incl. host loop)")
    if d["block_rows_per_s"]:
        best = max(d["block_rows_per_s"])
        L.append(f"fused blocks: {len(d['block_rows_per_s'])}, best "
                 f"{best / 1e6:.4f} Mrow-iters/s (steady state)")

    if d["setup"]:
        L.extend(_render_setup(d["setup"]))

    L.append("")
    L.append("== phases (host wall, per-iteration records) ==")
    phases = d["phases"] or {k: {"total_s": v, "count": d["iters"],
                                 "mean_s": v / max(d["iters"], 1)}
                             for k, v in d["phase_totals"].items()}
    if phases:
        tot = sum(p["total_s"] for p in phases.values()) or 1.0
        L.append(f"{'phase':<12}{'total_s':>10}{'mean_s':>10}"
                 f"{'count':>7}{'share':>7}")
        for name, p in sorted(phases.items(),
                              key=lambda kv: -kv[1]["total_s"]):
            L.append(f"{name:<12}{p['total_s']:>10.4f}"
                     f"{p.get('mean_s', 0.0):>10.4f}"
                     f"{p['count']:>7}"
                     f"{100 * p['total_s'] / tot:>6.1f}%")
    else:
        L.append("(no per-iteration records — fused/pipelined run; "
                 "see fused blocks above)")

    if d["iter_counts"]:
        L.append("")
        L.append("== dispatch / host-sync accounting (per iteration) ==")
        L.append(f"{'counter':<22}{'total':>10}{'per_iter':>10}")
        for name, c in sorted(d["iter_counts"].items()):
            L.append(f"{name:<22}{c['total']:>10,.0f}"
                     f"{c['per_iter']:>10.2f}")
    if d["fused_block_hits"]:
        L.append(f"fused_block_hits: {d['fused_block_hits']}")

    interesting = {k: v for k, v in d["counters"].items()
                   if not k.startswith(("jit.", "guard.", "checkpoint.",
                                        "retry.", "faults.", "comm.",
                                        "ingest."))}
    if interesting:
        L.append("")
        L.append("== counters ==")
        for k, v in sorted(interesting.items()):
            L.append(f"{k:<32}{v:>16,.0f}")

    if d.get("comms"):
        # per-op collective traffic of the mesh comm recipes
        # (trace-time payload bytes per compiled grow program; the
        # GC401 contract pins the op multiset, this table shows the
        # weight behind each op)
        L.append("")
        L.append("== mesh comms (collective payload per compiled "
                 "program) ==")
        L.append(f"{'op':<16}{'calls':>8}{'bytes':>16}"
                 f"{'bytes/call':>14}")
        for op, row in sorted(d["comms"].items(),
                              key=lambda kv: -kv[1].get("bytes", 0)):
            calls = row.get("calls", 0)
            nbytes = row.get("bytes", 0)
            per = nbytes / calls if calls else 0.0
            L.append(f"{op:<16}{calls:>8,.0f}{nbytes:>16,.0f}"
                     f"{per:>14,.0f}")
        if d.get("ingest"):
            ing = d["ingest"]
            L.append(
                "ingest: "
                + " ".join(f"{k}={v:,.0f}"
                           for k, v in sorted(ing.items())))

    if d.get("robustness"):
        r = d["robustness"]
        L.append("")
        L.append("== robustness (guards / checkpoints / retries) ==")
        L.append(f"guards: nonfinite_iters="
                 f"{r.get('guard.nonfinite_iters', 0):.0f} "
                 f"skipped={r.get('guard.skipped_iters', 0):.0f} "
                 f"rollbacks={r.get('guard.rollbacks', 0):.0f} "
                 f"loss_spikes={r.get('guard.loss_spikes', 0):.0f}")
        L.append(f"checkpoints: writes="
                 f"{r.get('checkpoint.writes', 0):.0f} "
                 f"bytes={r.get('checkpoint.bytes', 0):.0f} "
                 f"restores={r.get('checkpoint.restores', 0):.0f} "
                 f"fallbacks={r.get('checkpoint.fallbacks', 0):.0f} "
                 f"preemptions={r.get('checkpoint.preemptions', 0):.0f}")
        L.append(f"retries: calls={r.get('retry.calls', 0):.0f} "
                 f"retries={r.get('retry.retries', 0):.0f} "
                 f"giveups={r.get('retry.giveups', 0):.0f} "
                 f"sleep_s={r.get('retry.sleep_s', 0):.3f}")
        if r.get("faults.injected"):
            L.append(f"faults injected: "
                     f"{r.get('faults.injected', 0):.0f} "
                     + " ".join(
                         f"{k.split('.', 1)[1]}={v:.0f}"
                         for k, v in sorted(r.items())
                         if k.startswith("faults.")
                         and k != "faults.injected"))
        if any(k.startswith("elastic.") for k in r):
            L.append(f"elastic: heartbeats="
                     f"{r.get('elastic.heartbeats', 0):.0f} "
                     f"aborts={r.get('elastic.aborts', 0):.0f} "
                     f"barrier_timeouts="
                     f"{r.get('elastic.barrier_timeouts', 0):.0f} "
                     + " ".join(
                         f"{k.split('.', 1)[1]}={v:.0f}"
                         for k, v in sorted(r.items())
                         if k.startswith("elastic.abort.")))

    if d["memory"]:
        m = d["memory"]
        L.append("")
        L.append("== memory ==")
        L.append(" ".join(f"{k}={v}" for k, v in sorted(m.items())))

    if d["eval"]:
        L.append("")
        L.append(f"== eval (iter {d['eval_iter']}) ==")
        for k, v in sorted(d["eval"].items()):
            L.append(f"{k:<32}{v:>14.6f}")

    if d.get("serving"):
        s = d["serving"]
        L.append("")
        L.append("== serving (lightgbm_tpu/serving/) ==")
        L.append(f"requests={s.get('requests', 0)} "
                 f"rows={s.get('rows', 0)} "
                 f"batches={s.get('batches', 0)} "
                 f"queue_peak={s.get('queue_peak', 0)}")
        lat = s.get("latency_ms") or {}
        if lat:
            L.append(f"latency_ms: p50={lat.get('p50')} "
                     f"p95={lat.get('p95')} p99={lat.get('p99')} "
                     f"max={lat.get('max')}")
        hit = s.get("bucket_hit_rate")
        L.append(f"buckets: hits={s.get('bucket_hits', 0)} "
                 f"misses={s.get('bucket_misses', 0)}"
                 + (f" hit_rate={hit}" if hit is not None else ""))
        L.append(f"degradation: shed={s.get('shed', 0)} "
                 f"timeouts={s.get('timeouts', 0)} "
                 f"fallbacks={s.get('fallbacks', 0)} "
                 f"errors={s.get('errors', 0)} "
                 f"reloads={s.get('reloads', 0)}")
        model = s.get("model") or {}
        if model:
            L.append(f"model: v{model.get('version')} "
                     f"{model.get('num_trees')} trees "
                     f"device_ready={model.get('device_ready')}")

    if d.get("fleet"):
        f = d["fleet"]
        L.append("")
        L.append("== fleet (lightgbm_tpu/serving/fleet.py) ==")
        L.append(f"requests={f.get('requests', 0)} "
                 f"shed={f.get('shed', 0)} "
                 f"quota_shed={f.get('quota_shed', 0)} "
                 f"errors={f.get('errors', 0)} "
                 f"redispatches={f.get('redispatches', 0)}")
        L.append(f"pool: starts={f.get('replica_starts', 0)} "
                 f"deaths={f.get('replica_deaths', 0)} "
                 f"drains={f.get('replica_drains', 0)} "
                 f"reloads={f.get('reloads', 0)} "
                 f"promotions={f.get('promotions', 0)}")
        L.append(f"shadow: mirrored={f.get('shadow_mirrored', 0)} "
                 f"parity_ok={f.get('shadow_parity_ok', 0)} "
                 f"mismatch={f.get('shadow_parity_mismatch', 0)} "
                 f"skipped={f.get('shadow_skipped', 0)}")
        if f.get("replica_restarts") or f.get("replica_quarantines"):
            L.append(f"isolation: restarts="
                     f"{f.get('replica_restarts', 0)} "
                     f"quarantines={f.get('replica_quarantines', 0)}")
        if f.get("aot_publishes"):
            # zero-Python hot path (serving/aot.py): publishes that
            # shipped an AOT artifact so process workers replay the
            # device route with zero retraces
            L.append(f"aot: publishes={f.get('aot_publishes', 0)}")

    tl = d.get("replica_timeline") or []
    if tl:
        L.append("")
        L.append("== replica lifecycle (serving/procfleet.py) ==")
        L.append(f"{'t':>9} {'rid':>4} {'event':<12}{'state':<12}"
                 f"{'inc':>4} {'reason_code':<18}detail")
        for e in tl:
            t = e.get("t")
            extra = e.get("detail") or ""
            if e.get("ready_ms") is not None:
                extra = f"ready_ms={e['ready_ms']} {extra}".strip()
            L.append(f"{t if t is not None else '-':>9} "
                     f"{str(e.get('rid')):>4} "
                     f"{str(e.get('event')):<12}"
                     f"{str(e.get('state')):<12}"
                     f"{str(e.get('incarnation') or '-'):>4} "
                     f"{str(e.get('reason_code') or '-'):<18}"
                     f"{extra[:50]}")
        codes: Dict[str, int] = {}
        for e in tl:
            if e.get("reason_code"):
                codes[e["reason_code"]] = \
                    codes.get(e["reason_code"], 0) + 1
        if codes:
            L.append("death modes: " + " ".join(
                f"{k}={v}" for k, v in sorted(codes.items(),
                                              key=lambda kv: -kv[1])))

    etl = d.get("elastic_timeline") or []
    if etl:
        L.append("")
        L.append("== elastic training (robustness/elastic.py) ==")
        L.append(f"{'t':>9} {'rank':>4} {'event':<20}{'iter':>6} "
                 f"{'reason_code':<18}detail")
        for e in etl:
            t = e.get("t")
            L.append(f"{t if t is not None else '-':>9} "
                     f"{str(e.get('rank')):>4} "
                     f"{str(e.get('event')):<20}"
                     f"{str(e.get('iteration') or '-'):>6} "
                     f"{str(e.get('reason_code') or '-'):<18}"
                     f"{(e.get('detail') or '')[:50]}")
        acodes: Dict[str, int] = {}
        for e in etl:
            if e.get("reason_code"):
                acodes[e["reason_code"]] = \
                    acodes.get(e["reason_code"], 0) + 1
        if acodes:
            L.append("abort modes: " + " ".join(
                f"{k}={v}" for k, v in sorted(acodes.items(),
                                              key=lambda kv: -kv[1])))

    if d.get("multiboost"):
        mb = d["multiboost"]
        L.append("")
        L.append("== multiboost (many-model batched training) ==")
        L.append(f"models={mb.get('models', 0)} "
                 f"batched={mb.get('batched_models', 0)} "
                 f"buckets={mb.get('buckets', 0)}"
                 + (f" sizes=[{mb['bucket_sizes']}]"
                    if mb.get("bucket_sizes") else ""))
        bs = float(mb.get("batched_seconds") or 0.0)
        ls = float(mb.get("loop_seconds") or 0.0)
        L.append(f"batched_s={bs:.3f} loop_fallback_s={ls:.3f} "
                 f"loop_fallback_models={mb.get('loop_fallback', 0)}")
        if mb.get("fallback_reasons"):
            L.append(f"fallback reasons: {mb['fallback_reasons']}")

    tc = d.get("tenant_cycles") or []
    if tc:
        L.append("")
        L.append("== tenant pipeline cycles (pipeline/driver.py) ==")
        L.append(f"{'cycle':>6} {'tenant':<16}{'cand':>6} "
                 f"{'status':<14}{'promoted':<9}{'rows':>8}")
        for e in tc:
            L.append(f"{str(e.get('cycle')):>6} "
                     f"{str(e.get('tenant')):<16}"
                     f"{str(e.get('candidate')):>6} "
                     f"{str(e.get('status')):<14}"
                     f"{str(bool(e.get('promoted'))):<9}"
                     f"{str(e.get('rows')):>8}")
        by_tenant: Dict[str, List[int]] = {}
        for e in tc:
            row = by_tenant.setdefault(str(e.get("tenant")), [0, 0])
            row[0] += 1
            row[1] += 1 if e.get("promoted") else 0
        L.append("per tenant: " + " ".join(
            f"{t}={p}/{n} promoted"
            for t, (n, p) in sorted(by_tenant.items())))

    if d.get("slo"):
        L.append("")
        L.append("== slo burn rates (observability/slo.py) ==")
        L.append(f"{'slo':<16}{'kind':<14}{'objective':>10}"
                 f"{'max_burn':>10}{'breaches':>10}  windows")
        for name, e in sorted(d["slo"].items()):
            wins = e.get("windows") or {}
            wtxt = " ".join(f"{w}={b:g}" for w, b in sorted(
                wins.items())) if isinstance(wins, dict) else "-"
            burn = e.get("max_burn")
            br = f"{e['breaches']}/{e['evaluations']}"
            L.append(
                f"{name:<16}{str(e.get('slo_kind')):<14}"
                f"{e.get('objective'):>10}"
                f"{'-' if burn is None else format(burn, '.3g'):>10}"
                f"{br:>10}  {wtxt}")

    if d.get("hists"):
        L.append("")
        L.append("== histograms (live metrics plane) ==")
        L.append(f"{'series':<48}{'count':>8}{'p50':>10}{'p95':>10}"
                 f"{'p99':>10}")
        for key, h in sorted(d["hists"].items()):
            def _f(v):
                return "-" if v is None else f"{float(v):.3f}"
            L.append(f"{key:<48}{h.get('count', 0):>8}"
                     f"{_f(h.get('p50')):>10}{_f(h.get('p95')):>10}"
                     f"{_f(h.get('p99')):>10}")

    if d.get("tpu_probe"):
        p = d["tpu_probe"]
        L.append("")
        L.append("== tpu probe ==")
        L.append(f"verdict={p.get('verdict')} "
                 f"dur_s={p.get('dur_s')}"
                 + (f" reason_code={p['reason_code']}"
                    if p.get("reason_code") else ""))
        if p.get("reason"):
            L.append(f"reason: {str(p['reason'])[:200]}")

    hist = d.get("probe_history") or []
    if len(hist) > 1:
        L.append("")
        L.append("== tpu probe timeline (all rounds in this trace) ==")
        L.append(f"{'#':>3} {'verdict':<8}{'reason_code':<15}"
                 f"{'dur_s':>7}  cause")
        for i, p in enumerate(hist):
            L.append(f"{i:>3} {str(p.get('verdict')):<8}"
                     f"{str(p.get('reason_code') or '-'):<15}"
                     f"{p.get('dur_s') if p.get('dur_s') is not None else '-':>7}"
                     f"  {str(p.get('reason', ''))[:60]}")
        codes: Dict[str, int] = {}
        for p in hist:
            if p.get("reason_code"):
                codes[p["reason_code"]] = \
                    codes.get(p["reason_code"], 0) + 1
        if codes:
            L.append("failure modes: " + " ".join(
                f"{k}={v}" for k, v in sorted(codes.items(),
                                              key=lambda kv: -kv[1])))
    return "\n".join(L) + "\n"


# ----------------------------------------------------------------------
# compiled-HLO dispatch census artifacts (tools/hlo_census.py): the
# per-split op budget lives next to the per-phase histograms so one
# report answers both "where does the time go" and "how many dispatches
# does a split cost"
def load_census(path: str):
    """Parse a census artifact (bench_census.json / hlo_census.json);
    None when the file is not one."""
    try:
        with open(path) as fh:
            d = json.load(fh)
    except (OSError, ValueError):
        return None
    progs = d.get("programs")
    if not isinstance(progs, dict) or not all(
            isinstance(p, dict) and "ops_per_split" in p
            for p in progs.values()):
        return None
    return d


def sibling_census(trace_path: str):
    """The census artifact bench.py writes next to its telemetry."""
    cand = os.path.join(os.path.dirname(os.path.abspath(trace_path)),
                        "bench_census.json")
    return load_census(cand) if os.path.exists(cand) else None


def render_census(d: Dict[str, Any]) -> str:
    cfg = d.get("config") or {}
    L = ["== per-split dispatch census (tools/hlo_census.py) ==",
         f"config: {cfg.get('features')}f x {cfg.get('leaves')}l "
         f"backend={cfg.get('backend')} "
         f"split_fusion={cfg.get('split_fusion')}",
         f"{'program':<20}{'ops/split':>10}{'fusions':>9}"
         f"{'whiles':>8}{'coll':>6}{'carry':>7}{'bytes':>12}"]
    for name, p in sorted((d.get("programs") or {}).items()):
        L.append(f"{name:<20}{p.get('ops_per_split', 0):>10}"
                 f"{p.get('fusions', '-'):>9}"
                 f"{p.get('inner_whiles', '-'):>8}"
                 f"{p.get('collectives', '-'):>6}"
                 f"{p.get('carry_arrays', '-'):>7}"
                 f"{p.get('carry_bytes', 0):>12,}")
    return "\n".join(L) + "\n"


# ----------------------------------------------------------------------
# graftcheck contract artifacts (tools/graftcheck): the per-program
# contract verdicts render next to the census section — one report
# answers "how many dispatches" AND "do the compiled contracts hold"
def load_graftcheck(path: str):
    """Parse a graftcheck artifact (graftcheck.json); None when the
    file is not one."""
    try:
        with open(path) as fh:
            d = json.load(fh)
    except (OSError, ValueError):
        return None
    progs = d.get("programs")
    if "findings" not in d or not isinstance(progs, dict) or not all(
            isinstance(p, dict) and "ops" in p
            for p in progs.values()):
        return None
    return d


def sibling_graftcheck(trace_path: str):
    cand = os.path.join(os.path.dirname(os.path.abspath(trace_path)),
                        "graftcheck.json")
    return load_graftcheck(cand) if os.path.exists(cand) else None


def render_graftcheck(d: Dict[str, Any]) -> str:
    cfg = d.get("config") or {}
    verdict = "PASS" if d.get("ok") else \
        f"FAIL ({len(d.get('findings') or [])} finding(s))"
    L = ["== compiled-program contracts (tools/graftcheck) ==",
         f"backend={cfg.get('backend')} devices={cfg.get('devices')} "
         f"jax={cfg.get('jax')}  verdict: {verdict}",
         f"{'program':<28}{'ops':>6}{'fusions':>9}{'donation':>10}"
         "  collectives"]
    for name, p in sorted((d.get("programs") or {}).items()):
        cols = ",".join(f"{k}={v}" for k, v in sorted(
            (p.get("collectives") or {}).items())) or "-"
        L.append(f"{name:<28}{p.get('ops', 0):>6}"
                 f"{p.get('fusions', 0):>9}"
                 f"{p.get('donation', 0):>10}  {cols}")
    for f in d.get("findings") or []:
        L.append(f"  {f.get('program')}: {f.get('rule')} "
                 f"{f.get('message')}")
    return "\n".join(L) + "\n"


# ----------------------------------------------------------------------
# graftsync runtime guard stats (tools/graftsync/runtime.py): the
# per-creation-site lock hold-time histograms + acquisition-order
# graph a --sync-guards soak publishes into its report JSON
def load_syncguard(path: str):
    """The guard_stats() block when ``path`` is one (raw, or nested
    under ``sync_guards`` in a serve_bench result); None otherwise."""
    try:
        with open(path) as fh:
            d = json.load(fh)
    except (OSError, ValueError):
        return None
    if isinstance(d, dict) and isinstance(d.get("sync_guards"), dict):
        d = d["sync_guards"]
    if isinstance(d, dict) and d.get("tool") == "graftsync-runtime" \
            and isinstance(d.get("sites"), dict):
        return d
    return None


def _hold_bucket_label(k: int) -> str:
    lo, hi = 2.0 ** k, 2.0 ** (k + 1)
    if k <= -10:
        return f"<{hi * 1000:.3g}us"
    if k >= 20:
        return f">={lo:g}ms"
    if hi <= 1.0:
        return f"{lo * 1000:.3g}-{hi * 1000:.3g}us"
    return f"{lo:g}-{hi:g}ms"


def render_syncguard(d: Dict[str, Any]) -> str:
    sites = d.get("sites") or {}
    violations = d.get("violations") or []
    total_acq = sum(s.get("acquires", 0) for s in sites.values())
    agg: Dict[int, int] = {}
    for s in sites.values():
        for k, v in (s.get("hold_ms_hist") or {}).items():
            agg[int(k)] = agg.get(int(k), 0) + v
    verdict = "PASS" if not violations else \
        f"FAIL ({len(violations)} inversion(s))"
    L = ["== lock-order guard (tools/graftsync runtime) ==",
         f"sites={len(sites)} acquires={total_acq} "
         f"edges={len(d.get('edges') or [])} verdict: {verdict}",
         "hold-time histogram (all sites, log2 ms buckets):"]
    peak = max(agg.values(), default=1)
    for k in sorted(agg):
        bar = "#" * max(1, round(28 * agg[k] / peak))
        L.append(f"  [{_hold_bucket_label(k):>12}] {bar} {agg[k]}")
    L.append("hottest sites:")
    hot = sorted(sites.items(), key=lambda kv: -kv[1].get("acquires", 0))
    for site, s in hot[:10]:
        hist = s.get("hold_ms_hist") or {}
        worst = _hold_bucket_label(max((int(k) for k in hist), default=-10))
        L.append(f"  {site:<44} acquires={s.get('acquires', 0):<7} "
                 f"max-hold {worst}")
    for v in violations:
        L.append(f"  INVERSION {v.get('held_site')} <-> "
                 f"{v.get('acquired_site')} (threads "
                 f"{v.get('thread')} / {v.get('reverse_thread')})")
    return "\n".join(L) + "\n"


# ----------------------------------------------------------------------
# Chrome-trace timelines (observability/tracing.py): the Perfetto-
# loadable span export, summarized offline — per-category totals plus
# the slowest requests' full span chains with their trace ids
def load_chrome_trace(path: str):
    """The whole-file JSON object when ``path`` is a Chrome trace
    export (``{"traceEvents": [...]}``), else None."""
    try:
        with open(path) as fh:
            obj = json.load(fh)
    except (OSError, ValueError):
        return None
    if isinstance(obj, dict) and isinstance(obj.get("traceEvents"),
                                            list):
        return obj
    return None


def trace_digest(d: Dict[str, Any]) -> Dict[str, Any]:
    events = [e for e in d.get("traceEvents", [])
              if e.get("ph") == "X" and e.get("args")]
    by_cat: Dict[str, Dict[str, float]] = {}
    by_name: Dict[str, Dict[str, float]] = {}
    traces: Dict[str, List[Dict]] = {}
    for e in events:
        for table, key in ((by_cat, e.get("cat") or "span"),
                           (by_name, e.get("name") or "?")):
            row = table.setdefault(key, {"count": 0, "total_us": 0.0})
            row["count"] += 1
            row["total_us"] += float(e.get("dur", 0.0))
        tid = e["args"].get("trace_id")
        if tid:
            traces.setdefault(tid, []).append(e)
    # roots: the request/iteration-level spans (no parent link)
    roots = [e for e in events if e["args"].get("trace_id")
             and not e["args"].get("parent_id")]
    roots.sort(key=lambda e: -float(e.get("dur", 0.0)))
    slowest = []
    for e in roots[:5]:
        tid = e["args"]["trace_id"]
        chain = sorted(traces.get(tid, []),
                       key=lambda ev: float(ev.get("ts", 0.0)))
        slowest.append({
            "trace_id": tid, "root": e.get("name"),
            "dur_ms": round(float(e.get("dur", 0.0)) / 1000.0, 3),
            "spans": [{
                "name": ev.get("name"), "cat": ev.get("cat"),
                "dur_ms": round(float(ev.get("dur", 0.0)) / 1000.0, 3),
                "program": ev["args"].get("program"),
                "queue_ms": ev["args"].get("queue_ms"),
                "compute_ms": ev["args"].get("compute_ms"),
            } for ev in chain]})
    return {"events": len(events),
            "traces": len(traces),
            "dropped": (d.get("otherData") or {}).get("dropped_events"),
            "by_cat": by_cat, "by_name": by_name, "slowest": slowest}


def render_timeline(d: Dict[str, Any]) -> str:
    t = trace_digest(d)
    L = ["== span timeline (observability/tracing.py; load the file "
         "in Perfetto for the visual form) ==",
         f"events={t['events']} traces={t['traces']} "
         f"dropped={t['dropped']}"]
    L.append("")
    L.append(f"{'category':<12}{'spans':>8}{'total_ms':>12}")
    for cat, row in sorted(t["by_cat"].items(),
                           key=lambda kv: -kv[1]["total_us"]):
        L.append(f"{cat:<12}{row['count']:>8}"
                 f"{row['total_us'] / 1000.0:>12.3f}")
    L.append("")
    L.append(f"{'span':<24}{'count':>8}{'total_ms':>12}{'mean_ms':>10}")
    for name, row in sorted(t["by_name"].items(),
                            key=lambda kv: -kv[1]["total_us"]):
        mean = row["total_us"] / max(row["count"], 1) / 1000.0
        L.append(f"{name:<24}{row['count']:>8}"
                 f"{row['total_us'] / 1000.0:>12.3f}{mean:>10.3f}")
    if t["slowest"]:
        L.append("")
        L.append("== slowest traces (root span -> chain) ==")
        for s in t["slowest"]:
            L.append(f"trace {s['trace_id']}  {s['root']}  "
                     f"{s['dur_ms']:.3f} ms")
            for sp in s["spans"]:
                extra = ""
                if sp.get("program"):
                    extra += f" program={sp['program']}"
                if sp.get("queue_ms") is not None:
                    extra += f" queue_ms={sp['queue_ms']}"
                if sp.get("compute_ms") is not None:
                    extra += f" compute_ms={sp['compute_ms']}"
                L.append(f"    {sp['name']:<22}"
                         f"{sp['dur_ms']:>10.3f} ms{extra}")
    return "\n".join(L) + "\n"


# ----------------------------------------------------------------------
# crash flight-recorder dumps (observability/flightrec.py)
def load_crash(path: str):
    """The whole-file JSON object when ``path`` is a flight-recorder
    dump, else None."""
    try:
        with open(path) as fh:
            obj = json.load(fh)
    except (OSError, ValueError):
        return None
    if isinstance(obj, dict) and "flight_recorder" in obj:
        return obj
    return None


def render_crash(d: Dict[str, Any]) -> str:
    L = ["== crash flight recorder =="]
    L.append(f"reason={d.get('reason')} pid={d.get('pid')} "
             f"iteration={d.get('iteration')} "
             f"schema=v{d.get('flight_recorder')}")
    L.append(f"config_fingerprint={d.get('config_fingerprint')}")
    L.append(f"bin_layout_fingerprint="
             f"{d.get('bin_layout_fingerprint')}")
    cfg = d.get("config") or {}
    if cfg:
        L.append("config: " + " ".join(
            f"{k}={v}" for k, v in sorted(cfg.items())))
    exc = d.get("exception")
    if exc:
        L.append("")
        L.append(f"exception: {exc.get('type')}: "
                 f"{exc.get('message')}")
        for ln in (exc.get("traceback") or [])[-6:]:
            L.append("  " + ln.rstrip())
    trips = d.get("trips") or []
    if trips:
        L.append("")
        L.append("== guard trips / signals ==")
        for t in trips:
            desc = " ".join(f"{k}={v}" for k, v in sorted(t.items())
                            if k != "wall_time")
            L.append(f"  {desc}")
    workers = d.get("worker_dumps") or []
    if workers:
        L.append("")
        L.append("== collected worker dumps (process fleet) ==")
        for w in workers:
            dump = w.get("dump") or {}
            L.append(f"  rid={w.get('rid')} "
                     f"reason={w.get('reason_code')} "
                     f"inc={w.get('incarnation')} "
                     f"dump={'yes (' + str(dump.get('reason')) + ')' if dump else 'none'}"
                     + (f" path={w.get('dump_path')}"
                        if w.get("dump_path") else ""))
    spans = d.get("trace_spans") or []
    if spans:
        L.append("")
        L.append("== in-flight span stacks at trip time ==")
        for s in spans:
            L.append(f"  {s.get('name'):<24}"
                     f"trace={s.get('trace_id')} "
                     f"elapsed_ms={s.get('elapsed_ms')} "
                     f"thread={s.get('thread')}")
    counters = d.get("counters") or {}
    rob = {k: v for k, v in counters.items()
           if k.startswith(("guard.", "checkpoint.", "retry.",
                            "faults."))}
    if rob:
        L.append("")
        L.append("== robustness counters at dump time ==")
        for k, v in sorted(rob.items()):
            L.append(f"  {k:<32}{v:>12,.0f}")
    mem = d.get("memory") or {}
    if mem:
        L.append("")
        L.append("memory: " + " ".join(
            f"{k}={v}" for k, v in sorted(mem.items())))
    records = d.get("records") or []
    L.append("")
    L.append(f"== last {len(records)} ring records ==")
    if len(records) > 12:
        L.append(f"  ... ({len(records) - 12} earlier records in "
                 "the dump file)")
    for r in records[-12:]:
        kind = r.get("kind")
        extra = ""
        if kind == "iter":
            extra = (f" iter={r.get('iter')} phases="
                     + ",".join(f"{k}:{v:.3f}"
                                for k, v in
                                (r.get('phases') or {}).items()))
        elif kind == "eval":
            extra = f" iter={r.get('iter')} {r.get('results')}"
        elif kind == "compile":
            extra = (f" {r.get('program')} {r.get('stage')} "
                     f"dur_s={r.get('dur_s')}")
        elif kind == "span":
            extra = f" {r.get('path')} dur_s={r.get('dur_s')}"
        L.append(f"  t={r.get('t')} {kind}{extra}"[:100])
    return "\n".join(L) + "\n"


def main(argv: List[str]) -> int:
    args = [a for a in argv if not a.startswith("--")]
    if not args:
        sys.stderr.write(__doc__ + "\n")
        return 2
    crash = load_crash(args[0])
    if crash is not None:
        if "--json" in argv:
            print(json.dumps(crash))
        else:
            sys.stdout.write(render_crash(crash))
        return 0
    chrome = load_chrome_trace(args[0])
    if chrome is not None:
        if "--json" in argv:
            print(json.dumps(trace_digest(chrome)))
        else:
            sys.stdout.write(render_timeline(chrome))
        return 0
    census = load_census(args[0])
    if census is not None:
        if "--json" in argv:
            print(json.dumps(census))
        else:
            sys.stdout.write(render_census(census))
        return 0
    gc = load_graftcheck(args[0])
    if gc is not None:
        if "--json" in argv:
            print(json.dumps(gc))
        else:
            sys.stdout.write(render_graftcheck(gc))
        return 0
    sg = load_syncguard(args[0])
    if sg is not None:
        if "--json" in argv:
            print(json.dumps(sg))
        else:
            sys.stdout.write(render_syncguard(sg))
        return 0
    records = load(args[0])
    if not records:
        sys.stderr.write(f"no records in {args[0]}\n")
        return 1
    if "--json" in argv:
        print(json.dumps(digest(records)))
    else:
        sys.stdout.write(render(records))
        sib = sibling_census(args[0])
        if sib is not None:
            sys.stdout.write("\n" + render_census(sib))
        sgc = sibling_graftcheck(args[0])
        if sgc is not None:
            sys.stdout.write("\n" + render_graftcheck(sgc))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
