"""Device time by program scope, from a reduced trace.

A device event carries the name of a compiled instruction
(``fusion.87``) and nothing else; the program says which of its scopes
(``lgbm.grow.leaf_of_pos``) each instruction of the fused training
block belongs to (``lightgbm_tpu/observability/scopes.py``
``program_scopes``). ``by_scope`` joins the two: seconds of *leaf*
operations per scope, the rest as ``unattributed`` (other programs'
operations land there too), and the device's idle time by the
``lgbm.block.*`` host span it falls under. Computed once per run and
printed once, as ``info: scopes {...}`` (with the run's longest
instructions and the scope of each); the readers under ``layers/``
divide it by trees or splits.

A program that has no scopes (the parent of the PR that added them)
makes ``by_scope`` return ``None``, and every reader with it.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional

import numpy as np

from .trace_reduce import merge, overlap_length, short_name, union_length

PROGRAM = "gbdt_fused_block"
UNATTRIBUTED = "unattributed"
_MEMO = "_by_scope"
TOP = 12                # instructions named on the info line
# the fused driver's host spans; read from the program where it has them
_BLOCK_SPANS = ("BLOCK_DISPATCH", "BLOCK_SYNC", "BLOCK_TREES")


def _table(facts):
    """``(the program's vocabulary module, scope table, seconds it took
    to build)`` of the fused block that the window ran: the remembered
    instance with steps of ``block`` trees on ``rows`` rows. The
    reference check builds a smaller booster after the window, so the
    program dispatched last is not it. ``None`` where the program has
    no scopes or remembered no such instance."""
    try:
        import jax
        from lightgbm_tpu.observability import scopes
    except ImportError:
        return None
    for prog in reversed(scopes.remembered(PROGRAM)):
        rows = {a.shape[0] for a in jax.tree.leaves(prog.avals) if a.shape}
        if prog.static.get("m") == facts.get("block") \
                and facts.get("rows") in rows:
            table = prog.scopes()
            return (scopes, table, prog.table_s) if table else None
    return None


def _device_seconds(trace, table):
    """``(seconds by scope, [[instruction, scope, seconds], ...])``:
    the union of the leaf intervals of each scope, and the leaf time of
    each instruction, longest first; both are means over chips."""
    by_scope: Dict[str, float] = {}
    by_name: Dict[str, list] = {}
    n = max(len(trace.devices), 1)
    for ops in trace.devices.values():
        names = [short_name(t) for t in ops.texts]
        scope_of_text = np.asarray(
            [table.get(name, UNATTRIBUTED) for name in names])
        scope_of_event = scope_of_text[ops.which]
        for scope in np.unique(scope_of_event[ops.leaf]):
            mine = ops.leaf & (scope_of_event == scope)
            by_scope[str(scope)] = by_scope.get(str(scope), 0.0) \
                + union_length(ops.start[mine], ops.end[mine]) / n
        leaf_s = np.bincount(ops.which[ops.leaf],
                             (ops.end - ops.start)[ops.leaf], len(names))
        for name, scope, t in zip(names, scope_of_text, leaf_s):
            held = by_name.setdefault(name, [name, str(scope), 0.0])
            held[2] += float(t) / n
    return by_scope, sorted(by_name.values(), key=lambda row: -row[2])


def _idle_by_span(trace, names) -> Dict[str, Any]:
    """The gaps between merged leaf intervals on the first chip,
    intersected with the host events of each name; ``boundary_gap`` is
    the gaps that hold the end of a sync span: from the block's last
    operation to the next block's first."""
    ops = trace.devices[min(trace.devices)]
    s, e = merge(ops.start[ops.leaf], ops.end[ops.leaf])
    gap_s, gap_e = e[:-1], s[1:]
    out: Dict[str, Any] = {"gaps": float((gap_e - gap_s).sum())}
    spans = {}
    for name in names:
        found = [(h.start, h.end) for h in trace.host if h.name == name]
        spans[name] = (np.asarray([f[0] for f in found], np.float64),
                       np.asarray([f[1] for f in found], np.float64))
        out[name] = overlap_length(gap_s, gap_e, *spans[name])
        out[name + ".count"] = len(found)
    sync_ends = spans[names[1]][1]
    holds = np.zeros(len(gap_s), bool)
    for t in sync_ends:
        holds |= (gap_s <= t) & (t <= gap_e)
    out["boundary_gap"] = float((gap_e - gap_s)[holds].sum())
    return out


def by_scope(facts) -> Optional[Dict[str, Any]]:
    """``{"scopes": {scope: s}, "unattributed": s, "busy": s, "idle":
    {span: s, ...}, "spans": (dispatch, sync, trees), "table_s",
    "ops_in_table", "vocabulary": the program's module of names}`` for
    the run's trace, or ``None`` where there is no trace, no device
    operation or no scope table."""
    if _MEMO in facts:
        return facts[_MEMO]
    trace = facts.get("trace")
    if trace is None or not trace.devices:
        return None
    found = _table(facts)
    result = None
    if found is not None:
        scopes, table, table_s = found
        seconds, by_name = _device_seconds(trace, table)
        names = tuple(getattr(scopes, n) for n in _BLOCK_SPANS)
        result = {"vocabulary": scopes,
                  "scopes": {k: v for k, v in seconds.items()
                             if k != UNATTRIBUTED},
                  UNATTRIBUTED: seconds.get(UNATTRIBUTED, 0.0),
                  "busy": trace.busy_s(),
                  "idle": _idle_by_span(trace, names), "spans": names,
                  "table_s": table_s, "ops_in_table": len(table)}
        # "top" answers "what is fusion.87?" for the run's longest
        # instructions, beside the ledger's breakdown by bare name
        line = dict(result["scopes"], unattributed=result[UNATTRIBUTED],
                    busy=result["busy"], idle=result["idle"],
                    top=[[name, scope, round(t, 6)]
                         for name, scope, t in by_name[:TOP]],
                    table_s=round(table_s, 4),
                    ops_in_table=len(table))
        print(f"info: scopes {json.dumps(line)}", flush=True)
    facts[_MEMO] = result
    return result


def trees(facts) -> int:
    return len(facts.get("traced_trees", []))


def ms_per(facts, constants, per: int) -> Optional[float]:
    """Milliseconds under the scopes the program's vocabulary holds as
    ``constants`` (``"GROW_PACK"``), over ``per`` (trees or splits);
    ``None`` where there is no table, nothing to divide by, or none of
    these scopes ran."""
    got = by_scope(facts)
    if got is None or not per:
        return None
    ran = [got["scopes"][name] for name in
           (getattr(got["vocabulary"], c) for c in constants)
           if name in got["scopes"]]
    return 1e3 * sum(ran) / per if ran else None
