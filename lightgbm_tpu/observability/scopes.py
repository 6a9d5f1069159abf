"""Device time by program scope: the names, and which compiled
instruction belongs to which of them.

The fused training block (``models/gbdt.py`` ``_fused_iter_block``) is
one XLA program; a profile of it shows ``fusion.87``, not "the leaf per
position after a tree". The program therefore names its parts with
``jax.named_scope`` under the constants below (the only place the
strings live), and says which instruction of the *compiled* module each
name owns: a device event in a profile carries the instruction's name
and nothing else, so the join has to come from here
(docs/Observability.md, "Device time by program scope").

Nothing in this module runs on the hot path: ``remember`` is a list
scan once per dispatched block, and only while telemetry is on. The
driver builds a program's table once, right after the first dispatch
of each block length (the compiled module is then a lookup in JAX's
in-memory caches): a booster's jitted program dies with the booster,
and whoever reads a profile asks later.
"""

from __future__ import annotations

import re
import time
import weakref
from collections import Counter
from typing import Any, Dict, List, Optional

# -- the vocabulary ------------------------------------------------------
# device scopes of the fused training block (jax.named_scope)
GRADIENTS = "lgbm.gradients"
SAMPLE = "lgbm.sample"
GROW = "lgbm.grow"                          # parent of the next four
GROW_PACK = "lgbm.grow.pack"
GROW_ROOT = "lgbm.grow.root"
GROW_SPLITS = "lgbm.grow.splits"
GROW_LEAF_OF_POS = "lgbm.grow.leaf_of_pos"
SCORE_UPDATE = "lgbm.score_update"
DEVICE_SCOPES = (GRADIENTS, SAMPLE, GROW, GROW_PACK, GROW_ROOT,
                 GROW_SPLITS, GROW_LEAF_OF_POS, SCORE_UPDATE)
# the parts of a ranking objective's gradient program
# (objective/rank.py), children of GRADIENTS, which keeps what is
# outside them (the weights' multiply): scores and labels into the
# query layout and gradients back to row order; both sorts a class;
# the pair block, its sums and the normalisation. An elementwise
# objective's program traces none of them.
RANK_LAYOUT = "lgbm.gradients.rank.layout"
RANK_SORT = "lgbm.gradients.rank.sort"
RANK_PAIRS = "lgbm.gradients.rank.pairs"
RANK_SCOPES = (RANK_LAYOUT, RANK_SORT, RANK_PAIRS)
# the parts of the per-phase split body, the one a table the megakernel
# refuses runs (categorical, bundled): the megakernel's body traces
# none of them. Four are children of GROW_SPLITS, opened by the
# learner's loop (the cache: the parent's histogram read from the
# per-leaf cache and the two children's written into it; the sibling's
# subtraction is the histogram phase's). The categorical scan is opened by ops/split.py, which
# knows no learner: it is named for what it is, wherever it runs (the
# root's one scan a tree, every learner's call of per_feature_splits).
SPLITS_PARTITION = "lgbm.grow.splits.partition"
SPLITS_HIST = "lgbm.grow.splits.hist"
SPLITS_SCAN = "lgbm.grow.splits.scan"
SPLITS_CACHE = "lgbm.grow.splits.cache"
CAT_SCAN = "lgbm.cat_scan"
SPLIT_PHASE_SCOPES = (SPLITS_PARTITION, SPLITS_HIST, SPLITS_SCAN,
                      SPLITS_CACHE, CAT_SCAN)
# a bundled table's own (EFB; ops/histogram.py debundle_leaf_hist): the
# group histograms of a leaf expanded to one histogram a logical
# feature, before the scan. In the split body it is a child of
# GROW_SPLITS beside the four above, at the root a child of GROW_ROOT,
# so that the scan's scope holds the scan alone and the root's the
# root histogram. An unbundled table's program traces neither.
SPLITS_DEBUNDLE = "lgbm.grow.splits.debundle"
ROOT_DEBUNDLE = "lgbm.grow.root.debundle"
BUNDLE_SCOPES = (SPLITS_DEBUNDLE, ROOT_DEBUNDLE)
# a mesh learner's collectives (learner/comm.py), each scope around the
# collective alone: per split the reduce-scatter of the smaller child's
# histogram and the packed winner gather, children of GROW_SPLITS; the
# root's packed psum of histogram and sums, a child of GROW_ROOT. A
# one-chip program traces neither.
SPLITS_COLLECTIVE = "lgbm.grow.splits.collective"
ROOT_COLLECTIVE = "lgbm.grow.root.collective"
COLLECTIVE_SCOPES = (SPLITS_COLLECTIVE, ROOT_COLLECTIVE)

# host spans of the fused driver, on the profiler's clock
# (Telemetry.span(..., trace=<name>))
BLOCK_DISPATCH = "lgbm.block.dispatch"
BLOCK_SYNC = "lgbm.block.sync"
BLOCK_TREES = "lgbm.block.trees"
EVAL = "lgbm.eval"
# host spans of set-up (Telemetry.setup_span): each leaves one ``span``
# record with its start and end on ``time.perf_counter()`` and is a
# named profiler region as well (docs/Observability.md, "Set-up from
# the inside"). One table's construction; the root is opened by
# Dataset.from_numpy, from_scipy, from_file_two_round and load_binary
DATA_CONSTRUCT = "lgbm.data.construct"
DATA_FIND_BINS = "lgbm.data.find_bins"      # row sample + find_bin
DATA_BIN_ROWS = "lgbm.data.bin_rows"        # dense values -> bin bytes
DATA_BUNDLE = "lgbm.data.bundle"            # EFB of a dense table
# a sparse table's own two (PR 33): the bundle plan, and the binned
# matrix written from the stored entries
DATA_BUNDLE_PLAN = "lgbm.data.bundle_plan"
DATA_EXTRACT = "lgbm.data.extract"
DATA_LOAD_BINARY = "lgbm.data.load_binary"  # the read and the inflate
DATA_SAVE_BINARY = "lgbm.data.save_binary"  # the write
# one booster's set-up; the root is opened by GBDT._setup_train
SETUP = "lgbm.setup"
SETUP_LEARNER = "lgbm.setup.learner"        # create_tree_learner
SETUP_DEVICE_TABLE = "lgbm.setup.device_table"  # closes on a dispatch
SETUP_OBJECTIVE = "lgbm.setup.objective"
SETUP_SCORES = "lgbm.setup.scores"
# GBDT.train's own span leaves a record too: one a call, not a block
TRAIN = "train"
# every ``span`` record's name is one of these
LEDGER_SPANS = (DATA_CONSTRUCT, DATA_FIND_BINS, DATA_BIN_ROWS, DATA_BUNDLE,
                DATA_BUNDLE_PLAN, DATA_EXTRACT, DATA_LOAD_BINARY,
                DATA_SAVE_BINARY, SETUP, SETUP_LEARNER, SETUP_DEVICE_TABLE,
                SETUP_OBJECTIVE, SETUP_SCORES, TRAIN)

PREFIX = "lgbm."

# -- compiled HLO text -> {instruction: scope} ----------------------------
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")


# a scope opened inside a transformed function is printed inside the
# transform's name: ``vmap(lgbm.cat_scan)``
_COMPONENT = re.compile(r"(?:\w+\()*(" + re.escape(PREFIX) + r"[\w.]+)\)*")


def _scope_of(op_name: str) -> Optional[str]:
    """The last ``lgbm.`` component of an op path."""
    for part in reversed(op_name.split("/")):
        found = _COMPONENT.fullmatch(part)
        if found is not None:
            return found.group(1)
    return None


def parse_hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction short name -> scope, from the text of a compiled
    module (``compiled.as_text()``). An instruction's scope is the last
    ``lgbm.`` component of its ``op_name`` (the innermost
    ``named_scope`` it was traced under). One that has none of its own
    inherits the commonest scope among the instructions of the
    computation it ``calls=`` (a fusion whose root lost its metadata)
    or reduces with ``to_apply=`` (the TPU compiler rewrites a small
    reduce-scatter as an all-reduce that keeps none, but its reduction
    keeps the scope); one outside every scope is absent."""
    table: Dict[str, str] = {}
    in_computation: Dict[str, Counter] = {}
    callers: List[tuple] = []               # (instruction, callee)
    tally: Optional[Counter] = None
    for line in hlo_text.splitlines():
        inst = _INSTRUCTION.match(line)
        if inst is None:
            comp = _COMPUTATION.match(line)
            if comp is not None:
                tally = in_computation.setdefault(comp.group(1),
                                                  Counter())
            continue
        name = inst.group(1)
        found = _OP_NAME.search(line)
        scope = _scope_of(found.group(1)) if found else None
        if scope is not None:
            table[name] = scope
            if tally is not None:
                tally[scope] += 1
        else:
            callee = _CALLS.search(line)
            if callee is not None:
                callers.append((name, callee.group(1)))
    for name, callee in callers:
        inside = in_computation.get(callee)
        if inside:
            table[name] = inside.most_common(1)[0][0]
    return table


# -- what the program remembers of its dispatched programs ----------------
_KEEP = 8       # remembered programs per registered name, newest last


class RememberedProgram:
    """One dispatched instance of a registered jit program: its
    abstract arguments and static arguments, enough to look the
    compiled module up again without the (donated) arrays."""

    def __init__(self, fn: Any, avals: Any, static: Dict[str, Any]):
        self.avals = avals
        self.static = static
        self._fn = weakref.ref(fn)      # never pins a booster's program
        self._table: Optional[Dict[str, str]] = None
        self.table_s: Optional[float] = None    # what building it took

    def is_of(self, fn: Any, static: Dict[str, Any]) -> bool:
        return self._fn() is fn and self.static == static

    def scopes(self) -> Optional[Dict[str, str]]:
        """The scope table of this program, built at the first ask and
        kept: ``fn.lower(avals).compile()`` is a lookup in JAX's
        in-memory caches once the program has been called with these
        avals. ``None`` if the jitted callable went before anyone
        asked."""
        if self._table is None:
            fn = self._fn()
            if fn is None:
                return None
            t0 = time.perf_counter()
            text = fn.lower(*self.avals, **self.static).compile().as_text()
            self._table = parse_hlo_scopes(text)
            self.table_s = time.perf_counter() - t0
        return self._table


_REMEMBERED: Dict[str, List[RememberedProgram]] = {}


def remember(name: str, fn: Any, args: Any,
             **static) -> Optional[RememberedProgram]:
    """Note that ``fn`` (registered as ``name``) is about to be called
    with ``args`` and ``static``. Call it before the dispatch (the
    arguments may be donated) and only while telemetry is on. The
    avals are taken once per (callable, static), and that once the
    program is returned, for the caller to build its table after the
    call; a repeat returns ``None`` and moves the program to the end of
    the list: the last one is the one dispatched last."""
    held = _REMEMBERED.setdefault(name, [])
    for i, prog in enumerate(held):
        if prog.is_of(fn, static):
            if i != len(held) - 1:
                held.append(held.pop(i))
            return None
    import jax

    def aval(x):
        # what the call's cache is keyed on, or the ask compiles anew
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=getattr(x, "weak_type", False),
            sharding=x.sharding if getattr(x, "committed", False)
            else None)
    prog = RememberedProgram(fn, jax.tree.map(aval, args), static)
    held.append(prog)
    del held[:-_KEEP]
    return prog


def remembered(name: str) -> List[RememberedProgram]:
    """The remembered programs of ``name``, the one dispatched last at
    the end."""
    return list(_REMEMBERED.get(name, []))


def program_scopes(name: str, **static) -> Optional[Dict[str, str]]:
    """Instruction short name -> scope for the registered program
    ``name``: of the instance dispatched last, or of the last one whose
    static arguments include ``static`` (instruction numbers differ
    between the programs of different block lengths, so
    ``program_scopes("gbdt_fused_block", m=4)``). ``None`` when
    nothing was remembered under the name (telemetry was off, or the
    driver of that program remembers nothing). Memoised per remembered
    program."""
    for prog in reversed(remembered(name)):
        if all(prog.static.get(k) == v for k, v in static.items()):
            return prog.scopes()
    return None


def forget() -> None:
    """Test helper: drop everything remembered."""
    _REMEMBERED.clear()
