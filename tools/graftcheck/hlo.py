"""Shared compiled-HLO parsing/counting core.

ONE parser, two front-ends: ``tools/hlo_census`` (the per-split
dispatch budget over the grow while-bodies, PR 8) and
``tools/graftcheck`` (the per-program contract checker over every
registered jit entry point). The census helpers here are moved
verbatim from the original ``tools/hlo_census.py`` — the committed
budget and the reported fixed-config counts depend on their exact
counting rules, so any change here must keep
``tools/hlo_census_budget.json`` green without --update.

Everything operates on the textual form of a compiled module
(``jitted.lower(...).compile().as_text()``).
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Iterator, List, Tuple

# --- census counting rules (see tools/hlo_census.py header) -----------
TRIVIAL_OPS = ("get-tuple-element", "parameter", "constant", "tuple",
               "bitcast")
DTYPE_TOKENS = ("f32", "s32", "u32", "u8", "pred", "u16", "bf16", "s8",
                "s64", "f64", "u64", "c64", "c128", "s16", "f16")
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
               "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
               "s64": 8, "u64": 8, "f64": 8}

# 8-byte element types: the x64 family a silent widening pays double
# bandwidth for (c128 is 16 but never legitimate here either)
WIDE_DTYPES = ("f64", "s64", "u64", "c128")

# custom-call targets that round-trip through the host per dispatch
HOST_CALLBACK_MARKERS = ("callback", "outside_compilation", "host_")
# ops that ARE host round-trips regardless of target
HOST_TRANSFER_OPS = ("infeed", "outfeed", "send", "recv", "send-done",
                     "recv-done")
# dynamic-shape machinery (bounded dynamism / padded programs)
DYNAMIC_SHAPE_OPS = ("set-dimension-size", "get-dimension-size",
                     "dynamic-reshape")
DYNAMIC_CALL_MARKERS = ("PadToStatic", "SliceToDynamic")


def op_of(line: str):
    """HLO opcode of one instruction line (first known-op token
    preceding a paren that is not a dtype)."""
    rhs = line.split(" = ", 1)[1]
    for cand in re.findall(r"([a-z][a-z0-9\-]*)\(", rhs):
        if cand not in DTYPE_TOKENS:
            return cand
    return None


def shape_bytes(shape: str) -> int:
    m = re.match(r"([a-z0-9]+)\[([\d,]*)\]", shape)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(m.group(1), 4)


def carry_stats(line: str) -> Tuple[int, int]:
    """(elements, bytes) of a while instruction's carry tuple."""
    m = re.search(r"= \((.*?)\) while\(", line)
    if not m:
        return 0, 0
    shapes = re.findall(r"[a-z0-9]+\[[\d,]*\](?:\{[\d,]*\})?",
                        m.group(1))
    return len(shapes), sum(shape_bytes(s) for s in shapes)


_CALLED_RE = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|"
    r"false_computation)=(%[\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _computation_graph(txt: str):
    """(ops per computation, computations referenced per computation):
    the call graph the grow-while selection walks."""
    lines = txt.splitlines()
    comps: Dict[str, Counter] = {}
    refs: Dict[str, set] = {}
    name = None
    for ln in lines:
        stripped = ln.strip()
        if stripped.endswith("{") and "(" in stripped:
            head = stripped.split("(", 1)[0].strip()
            if head.startswith("ENTRY "):
                head = head[len("ENTRY "):].strip()
            name = head.split()[-1] if head else name
            comps.setdefault(name, Counter())
            refs.setdefault(name, set())
            continue
        if name is None or " = " not in ln:
            continue
        op = op_of(ln)
        if op:
            comps[name][op] += 1
        for m in _CALLED_RE.finditer(ln):
            refs[name].add(m.group(1))
        for m in _BRANCHES_RE.finditer(ln):
            refs[name].update(re.findall(r"%[\w.\-]+", m.group(1)))
    return comps, refs


def census_from_hlo(txt: str) -> dict:
    """Census of the grow while loop inside one compiled HLO module.

    The grow while is the ``while`` op WITHOUT a ``known_trip_count``
    backend_config (scatter expansions and pallas grid loops are
    trip-counted) whose body TRANSITIVELY holds the most non-trivial
    ops — the outermost loop of the program, which always contains any
    nested dynamic loop (e.g. the megakernel's interpret-mode DMA
    streams). Reported counts are the body's DIRECT ops: non-trivial =
    everything except parameter / constant / tuple /
    get-tuple-element / bitcast; inner ``while`` ops count as ONE op
    each (on TPU they are one kernel)."""
    comps, refs = _computation_graph(txt)

    def nontrivial_of(counter: Counter) -> int:
        return sum(counter.values()) - sum(counter[t]
                                           for t in TRIVIAL_OPS)

    trans_cache: Dict[str, int] = {}

    def trans_ops(name: str, stack=()):
        if name in trans_cache:
            return trans_cache[name]
        if name not in comps or name in stack:
            return 0
        total = nontrivial_of(comps[name])
        for r in refs.get(name, ()):
            total += trans_ops(r, stack + (name,))
        trans_cache[name] = total
        return total

    candidates = []  # (body_name, carry_elems, carry_bytes)
    for m in re.finditer(r"body=(%[\w.\-]+)", txt):
        s = txt.rfind("\n", 0, m.start()) + 1
        line = txt[s:txt.find("\n", m.end())]
        if "known_trip_count" in line:
            continue
        elems, nbytes = carry_stats(line)
        candidates.append((m.group(1), elems, nbytes))
    best = None
    best_trans = -1
    for body, elems, nbytes in candidates:
        if body not in comps:
            continue
        ops = comps[body]
        total = sum(ops.values())
        tr = trans_ops(body)
        if best is None or tr > best_trans:
            best_trans = tr
            best = dict(
                body=body.lstrip("%"),
                ops_per_split=nontrivial_of(ops),
                total_instructions=total,
                fusions=ops.get("fusion", 0),
                inner_whiles=ops.get("while", 0),
                collectives=sum(ops.get(c, 0) for c in COLLECTIVE_OPS),
                carry_arrays=elems,
                carry_bytes=nbytes,
                op_histogram={k: v for k, v in sorted(
                    ops.items(), key=lambda kv: -kv[1])},
            )
    if best is None:
        raise RuntimeError("no grow while loop found in compiled HLO")
    return best


# --- whole-module views (the graftcheck front-end) --------------------
def iter_instructions(txt: str) -> Iterator[Tuple[int, str, str, str]]:
    """Yield (1-based line, computation name, opcode, line text) for
    every instruction in the module. The computation name tracks the
    enclosing ``%name ... {`` block (``ENTRY`` blocks report their
    entry name)."""
    comp = ""
    for i, ln in enumerate(txt.splitlines(), start=1):
        stripped = ln.strip()
        if stripped.endswith("{") and ("(" in stripped):
            head = stripped.split("(", 1)[0].strip()
            if head.startswith("ENTRY "):
                head = head[len("ENTRY "):].strip()
            comp = head.split()[-1].lstrip("%") if head else comp
            continue
        if " = " not in ln:
            continue
        op = op_of(ln)
        if op:
            yield i, comp, op, ln


def module_op_counts(txt: str) -> Counter:
    """Non-bookkeeping opcode counts across the module, EXCLUDING the
    bodies of fusion computations (a fusion is one dispatch; its inner
    element ops are already paid for by the ``fusion`` op itself)."""
    ops: Counter = Counter()
    for _line, comp, op, _txt in iter_instructions(txt):
        if "fused_computation" in comp:
            continue
        ops[op] += 1
    return ops


def nontrivial_total(ops: Counter) -> int:
    return sum(ops.values()) - sum(ops[t] for t in TRIVIAL_OPS)


def collective_census(txt: str) -> Dict[str, int]:
    """Exact multiset of collective ops in the module (fusion bodies
    excluded — collectives never fuse)."""
    ops = module_op_counts(txt)
    return {c: ops[c] for c in COLLECTIVE_OPS if ops.get(c)}


def result_dtype(line: str) -> str:
    """Element type of an instruction's result shape ('' when the
    result is a tuple or unparsable)."""
    rhs = line.split(" = ", 1)[1].lstrip()
    m = re.match(r"([a-z0-9]+)\[", rhs)
    return m.group(1) if m and m.group(1) in DTYPE_TOKENS else ""


def wide_dtype_lines(txt: str) -> List[Tuple[int, str]]:
    """Instructions producing 8-byte-element results (f64/s64/u64/c128)
    — the dtype-discipline violations GC2xx reports. ``constant`` ops
    are exempt: XLA embeds s64 scalar constants for machinery (e.g.
    callback target pointers) that never touches the compute path — a
    REAL f64 leak always surfaces in the converts/arithmetic too. So
    is the threefry counter of ``jax.random`` (jax 0.9.0 builds its
    2x32 counter from one u64 iota, op_name ``iota_2x32_shape``):
    library machinery in every program that draws random numbers. An
    f64 parameter still counts: it means an f64 input crossed the jit
    boundary."""
    out = []
    for i, _comp, op, ln in iter_instructions(txt):
        if op == "constant" or "/iota_2x32_shape" in ln:
            continue
        dt = result_dtype(ln)
        if dt in WIDE_DTYPES:
            out.append((i, ln.strip()))
    return out


def widening_convert_lines(txt: str) -> List[Tuple[int, str]]:
    """``convert`` instructions whose result element type is one of the
    8-byte x64 family and whose operand is narrower — the classic
    python-float / np-scalar promotion leak. This jaxlib prints
    operands by name only (``convert(%param_0.1)``), so the operand's
    type is read from the instruction that defines it."""
    defined: Dict[str, str] = {}
    converts = []
    for i, _comp, op, ln in iter_instructions(txt):
        name = ln.split(" = ", 1)[0].split()[-1]
        defined[name] = result_dtype(ln)
        if op == "convert" and defined[name] in WIDE_DTYPES:
            converts.append((i, ln, defined[name]))
    out = []
    for i, ln, dst in converts:
        m = re.search(r"convert\((?:([a-z0-9]+)\[\S* )?(%[\w.\-]+)", ln)
        src = (m.group(1) or defined.get(m.group(2), "")) if m else ""
        if src and DTYPE_BYTES.get(src, 4) < DTYPE_BYTES.get(dst, 8):
            out.append((i, ln.strip()))
    return out


def host_callback_lines(txt: str) -> List[Tuple[int, str]]:
    """Host round-trips compiled into the program: python callbacks
    (``custom-call`` whose target mentions a callback), infeed/outfeed
    and host send/recv ops."""
    out = []
    for i, _comp, op, ln in iter_instructions(txt):
        if op in HOST_TRANSFER_OPS:
            out.append((i, ln.strip()))
            continue
        if op == "custom-call":
            m = re.search(r'custom_call_target="([^"]+)"', ln)
            tgt = m.group(1) if m else ""
            if any(k in tgt for k in HOST_CALLBACK_MARKERS):
                out.append((i, ln.strip()))
    return out


def dynamic_shape_lines(txt: str) -> List[Tuple[int, str]]:
    """Dynamic-shape machinery: bounded-dynamic result shapes
    (``f32[<=128]``), set/get-dimension-size, dynamic-reshape, and the
    PadToStatic/SliceToDynamic custom calls."""
    out = []
    for i, _comp, op, ln in iter_instructions(txt):
        if op in DYNAMIC_SHAPE_OPS:
            out.append((i, ln.strip()))
            continue
        if op == "custom-call":
            m = re.search(r'custom_call_target="([^"]+)"', ln)
            if m and any(k in m.group(1)
                         for k in DYNAMIC_CALL_MARKERS):
                out.append((i, ln.strip()))
                continue
        rhs = ln.split(" = ", 1)[1].lstrip()
        if re.match(r"[a-z0-9]+\[[^\]]*<=", rhs):
            out.append((i, ln.strip()))
    return out


_ALIAS_RE = re.compile(r"input_output_alias=\{")


def alias_entries(txt: str) -> List[Tuple[str, int]]:
    """Parse the module header's ``input_output_alias`` map into
    (output index tuple text, aliased parameter number) pairs. An
    empty list means NO donation materialized."""
    m = _ALIAS_RE.search(txt)
    if not m:
        return []
    depth = 1
    i = m.end()
    while i < len(txt) and depth:
        if txt[i] == "{":
            depth += 1
        elif txt[i] == "}":
            depth -= 1
        i += 1
    block = txt[m.end():i - 1]
    return [(out_idx, int(param))
            for out_idx, param in re.findall(
                r"\{([\d,\s]*)\}:\s*\((\d+)", block)]


def aliased_param_count(txt: str) -> int:
    """Number of DISTINCT input parameters aliased to outputs — the
    materialized-donation count the GC1xx contract checks."""
    return len({p for _o, p in alias_entries(txt)})
