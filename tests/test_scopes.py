"""Device time by program scope (lightgbm_tpu/observability/scopes.py):
the vocabulary reaches the compiled module, the scope table joins
instruction names to it, the fused driver's spans reach the profiler's
clock, and none of it changes the program (ISSUE 24)."""

import collections
import contextlib
import functools
import gc
import re

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability import scopes
from lightgbm_tpu.observability.telemetry import _NULL_SPAN, get_telemetry
from lightgbm_tpu.utils import jit_registry

HLO = '''HloModule jit_gbdt_fused_block, is_scheduled=true

%fused_computation.7 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %gather.3 = f32[8]{0} gather(%param_0.1), metadata={op_name="jit(gbdt_fused_block)/while/body/closed_call/lgbm.grow/lgbm.grow.pack/gather" stack_frame_id=4}
  %mul.2 = f32[8]{0} multiply(%gather.3, %gather.3), metadata={op_name="jit(gbdt_fused_block)/while/body/closed_call/lgbm.grow/lgbm.grow.pack/mul"}
  ROOT %copy.9 = f32[8]{0} copy(%mul.2)
}

%region_3.2 (reduce_scatter.1: f32[], reduce_scatter.2: f32[]) -> f32[] {
  %reduce_scatter.2 = f32[] parameter(1)
  %reduce_scatter.1 = f32[] parameter(0)
  ROOT %add.7 = f32[] add(%reduce_scatter.1, %reduce_scatter.2), metadata={op_name="lgbm.grow.splits/while/body/lgbm.grow.splits.hist/lgbm.grow.splits.collective/add"}
}

%region_0.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %fusion.87 = s32[8]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(gbdt_fused_block)/while/body/closed_call/lgbm.grow/lgbm.grow.leaf_of_pos/jit(searchsorted)/while/body/select_n"}
  %fusion.7 = f32[8]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.7
  %custom-call.2 = f32[8]{0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(gbdt_fused_block)/while/body/closed_call/lgbm.grow/lgbm.grow.splits/while/body/jit(fused_split_step_segment)/pallas_call"}
  %add.5 = s32[] add(%arg, %arg), metadata={op_name="jit(gbdt_fused_block)/while/body/add"}
  %copy.4 = f32[8]{0} copy(%fusion.7)
  %all-reduce.3 = f32[8]{0} all-reduce(%copy.4), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_3.2
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%add.5, %copy.4)
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT grad.1 = f32[8]{0} negate(%x), metadata={op_name="jit(gbdt_fused_block)/lgbm.gradients/jit(gbdt_grad)/neg"}
}
'''


@pytest.mark.parametrize("instruction,scope", [
    # nested scopes: the last lgbm. component, not the first
    ("gather.3", scopes.GROW_PACK),
    ("fusion.87", scopes.GROW_LEAF_OF_POS),
    ("custom-call.2", scopes.GROW_SPLITS),
    # a name printed without % and behind ROOT is still an instruction
    ("grad.1", scopes.GRADIENTS),
    # a fusion without op_name inherits from the computation it calls
    ("fusion.7", scopes.GROW_PACK),
    # a collective the compiler rewrote without op_name (a small
    # reduce-scatter made an all-reduce) inherits from its reduction
    ("all-reduce.3", scopes.SPLITS_COLLECTIVE),
    # outside every scope, with and without an op_name: absent
    ("add.5", None), ("copy.4", None), ("copy.9", None),
    ("tuple.1", None), ("x", None),
])
def test_parse_hlo_scopes_on_a_literal_module(instruction, scope):
    assert scopes.parse_hlo_scopes(HLO).get(instruction) == scope


def test_the_vocabulary_is_eight_scopes_and_four_spans():
    assert len(set(scopes.DEVICE_SCOPES)) == 8
    spans = (scopes.BLOCK_DISPATCH, scopes.BLOCK_SYNC, scopes.BLOCK_TREES,
             scopes.EVAL)
    assert all(n.startswith(scopes.PREFIX)
               for n in scopes.DEVICE_SCOPES + spans)
    # the four parts of a tree's growth are children of lgbm.grow
    assert sum(n.startswith(scopes.GROW + ".")
               for n in scopes.DEVICE_SCOPES) == 4
    # and the per-phase split body's five parts are a list of their
    # own: the megakernel's body has none of them. Four are children
    # of the grow loop's scope (the histogram cache's traffic among
    # them, ISSUE 31); the categorical scan belongs to ops/ and is
    # named after no learner's loop
    assert len(set(scopes.SPLIT_PHASE_SCOPES)) == 5
    assert sum(n.startswith(scopes.GROW_SPLITS + ".")
               for n in scopes.SPLIT_PHASE_SCOPES) == 4
    assert scopes.SPLITS_CACHE == scopes.GROW_SPLITS + ".cache"
    assert scopes.CAT_SCAN == scopes.PREFIX + "cat_scan"
    assert not set(scopes.SPLIT_PHASE_SCOPES) & set(scopes.DEVICE_SCOPES)


@pytest.mark.parametrize("op_name,scope", [
    # a scope opened inside a vmapped function is printed inside the
    # transform's name (both children's scans are one vmapped scan)
    ("jit(f)/lgbm.grow/lgbm.grow.splits/while/body/"
     "lgbm.grow.splits.scan/vmap(lgbm.cat_scan)/sort",
     scopes.CAT_SCAN),
    ("jit(f)/lgbm.grow/jvp(vmap(lgbm.grow.root))/mul", scopes.GROW_ROOT),
    ("jit(f)/lgbm.grow/lgbm.grow.splits/while/body/vmap(jit(g))/add",
     scopes.GROW_SPLITS),
    ("jit(f)/while/body/vmap(jit(lgbmish))/add", None),
])
def test_scope_of_sees_through_a_transform(op_name, scope):
    assert scopes._scope_of(op_name) == scope


# ---------------------------------------------------------------------
@pytest.fixture
def tel(monkeypatch):
    """Fresh telemetry and nothing remembered; the fused-scan driver on
    (it is the TPU default; this is its CPU switch)."""
    monkeypatch.setenv("LGBM_TPU_FUSE_ITERS", "1")
    t = get_telemetry()
    t.reset()
    scopes.forget()
    yield t
    t.reset()
    scopes.forget()


def _gbdt(bagging=False, seed=0, rows=500, num_leaves=7, **more):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, 6)
    if "categorical_feature" in more:
        X[:, 3] = rng.randint(0, 9, rows)
        X[:, 0] += X[:, 3] % 2
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": num_leaves,
              "verbosity": -1,
              "tree_learner": "partitioned", "metric": ""}
    params.update(more)
    if bagging:
        params.update(bagging_fraction=0.5, bagging_freq=1)
    return lgb.Booster(params, lgb.Dataset(X, label=y))._gbdt


def _module_name(jitted, *avals, **static):
    text = jitted.lower(*avals, **static).compile().as_text()
    return re.match(r"HloModule (\S+?),", text).group(1)


@pytest.mark.parametrize("bagging", [False, True],
                         ids=["plain", "bagging"])
def test_scope_table_of_the_fused_block(tel, bagging):
    tel.ensure_ring()
    g = _gbdt(bagging)
    g.train(1)                  # the first iteration's own path
    g.train(3)                  # one block of 2
    g.train(5)                  # and another: the same program
    assert tel.counters["fused.block_hits"] == 2
    progs = scopes.remembered("gbdt_fused_block")
    assert [p.static for p in progs] == [{"m": 2}]
    compiles = tel.counters["jit.compiles"]
    table = scopes.program_scopes("gbdt_fused_block")
    assert table and table is scopes.program_scopes("gbdt_fused_block")
    assert table is scopes.program_scopes("gbdt_fused_block", m=2)
    assert scopes.program_scopes("gbdt_fused_block", m=4) is None
    # built at the first dispatch, from JAX's caches: no compile, then
    # or now
    assert tel.counters["jit.compiles"] == compiles
    assert progs[0].table_s is not None
    owned = collections.Counter(table.values())
    # off a TPU the grow loop is the per-phase body, so its four
    # numeric parts are there; no column is categorical
    reachable = set(scopes.DEVICE_SCOPES + scopes.SPLIT_PHASE_SCOPES) \
        - {scopes.CAT_SCAN}
    if not bagging:
        reachable.discard(scopes.SAMPLE)
    assert set(owned) == reachable
    # the program carries its registry name
    fn = jit_registry.get("gbdt_fused_block").fn
    assert _module_name(fn, *progs[0].avals, m=2) \
        == "jit_gbdt_fused_block"


def test_the_table_outlives_the_booster(tel):
    """A booster's jitted program dies with it; whoever reads a profile
    asks later (the benchmark asks after its reference check has built
    another booster)."""
    tel.ensure_ring()
    g = _gbdt()
    g.train(1)
    g.train(3)
    first = scopes.program_scopes("gbdt_fused_block")
    del g
    gc.collect()
    other = _gbdt(seed=1)
    other.train(1)
    other.train(5)              # a block of 4: another program
    held = scopes.remembered("gbdt_fused_block")
    assert [p.static for p in held] == [{"m": 2}, {"m": 4}]
    assert held[0].scopes() is first
    assert scopes.program_scopes("gbdt_fused_block") is held[1].scopes()
    assert scopes.program_scopes("gbdt_fused_block", m=2) is first


def test_gradient_programs_carry_their_registry_names(tel):
    import jax
    import jax.numpy as jnp
    g = _gbdt(bagging=True)
    score = jax.ShapeDtypeStruct((500,), jnp.float32)
    assert _module_name(jit_registry.get("gbdt_grad").fn, score) \
        == "jit_gbdt_grad"
    g._grad_hess_bag(g.train_score[:, 0], 0)    # builds the program
    assert _module_name(jit_registry.get("gbdt_grad_bag").fn, score,
                        jax.ShapeDtypeStruct((), jnp.int32)) \
        == "jit_gbdt_grad_bag"


def test_nothing_is_remembered_or_timed_with_telemetry_off(tel):
    assert not tel.enabled
    g = _gbdt()
    g.train(3)
    assert scopes.remembered("gbdt_fused_block") == []
    assert scopes.program_scopes("gbdt_fused_block") is None
    assert scopes.program_scopes("no_such_program") is None
    assert tel.span("device_sync") is _NULL_SPAN
    assert tel.span("boosting", trace=scopes.BLOCK_DISPATCH) \
        is not _NULL_SPAN


def test_one_block_opens_its_three_spans_in_order(tel, monkeypatch):
    import lightgbm_tpu.utils.log as log
    seen = []

    @contextlib.contextmanager
    def recorder(name):
        seen.append(("open", name))
        yield
        seen.append(("close", name))

    g = _gbdt()
    g.train(1)
    monkeypatch.setattr(log, "annotate", recorder)
    g.train(3)                  # one block of 2, no eval
    assert seen == [(what, name)
                    for name in (scopes.BLOCK_DISPATCH, scopes.BLOCK_SYNC,
                                 scopes.BLOCK_TREES)
                    for what in ("open", "close")]
    assert len(g.models) == 3 and g.iter == 3


# the fused block's optimised module at the graftcheck size (XLA:CPU,
# jax 0.9.0), by opcode: the parent of ISSUE 24's (commit 1f5d278) but
# for the leaf-of-position pass ISSUE 26 replaced (the search's while
# loop, two gathers and a clamp went; the block pass's slices came)
# and for the interpret-mode expansion of the partition kernel, which
# ISSUE 28 pipelined (two input slots, a write in flight a side, a
# fast and a merge branch per window: 13 more conditionals; on a TPU
# the kernel is one Mosaic call either way), and for the per-leaf
# histogram cache's write, which ISSUE 31 made two in-place row updates
# in place of one scatter of the stacked pair (a scatter and a
# concatenate went, two dynamic-update-slices and their index clamps
# came; as many fusions as before), and for the interpret-mode
# expansion of the two histogram calls, which ISSUE 32 made the
# one-hot stream (a dot and five one-row accumulates a feature,
# unrolled, where the nibble kernel's groups of three were; on a TPU
# each is one Mosaic call either way), and for the interpret-mode
# expansion of the partition kernel's block step, which ISSUE 34 made
# one compaction a block (three dots fewer: one prefix product, one
# permutation product a forward block, none in the back-copy, whose
# roll the interpreter writes as slices and a concatenate; on a TPU
# the kernel is one Mosaic call either way), and for the block pass
# painting the leaf's value, ISSUE 36 (the leaf-value gather over the
# positions went and one over the num_leaves sorted segments came; the
# f32 table goes through the pass as int32 words: three
# bitcast-converts, two small fusions), and for the gh payload written
# and the row ids read as bitcasts, not as shifted byte planes
# (ops/hist_pallas.py pack_gh, extract_row_ids: seven fusions fewer),
# and for the fixture's binary score and labels kept in the matrix rows
# (models/gbdt.py RowScore: the pack's row gather, its clamps and the
# scatter-add went; the rows' words read as bitcasts, one write of the
# payload with the score, the leaf values scaled on the num_leaves
# table and the block's rebuild scatter-set came; as many fusions)
PARENT_OPCODES = {
    "abs": 20, "add": 458, "and": 110, "bitcast": 790,
    "bitcast-convert": 86, "broadcast": 341, "compare": 371,
    "concatenate": 31, "conditional": 15, "constant": 1091, "convert": 147,
    "copy": 183, "divide": 12, "dot": 19, "dynamic-slice": 93,
    "dynamic-update-slice": 222, "exponential": 1, "fusion": 473,
    "gather": 8, "get-tuple-element": 272, "iota": 42, "is-finite": 4,
    "maximum": 25, "minimum": 13, "multiply": 141, "negate": 122, "not": 4,
    "or": 14, "pad": 20, "parameter": 1169, "reduce": 15,
    "reduce-window": 8, "remainder": 1, "reverse": 2, "scatter": 2,
    "select": 348, "shift-left": 3, "shift-right-logical": 32, "sign": 45,
    "slice": 655, "sort": 1, "subtract": 102, "transpose": 8, "tuple": 42}
_OPCODE = re.compile(
    r"^\s+(?:ROOT\s+)?%?[\w.\-]+\s+=\s+(?:\([^=]*?\)|\S+)\s+([a-z\-]+)\(")


def test_named_scopes_leave_the_compiled_program_as_it_was():
    """``jax.named_scope`` and the program's name are metadata: the
    optimised module has the parent's instructions, opcode by opcode
    (and so tools/graftcheck/contracts.json needs no new number)."""
    from tools.graftcheck import load_manifest
    from tools.graftcheck.programs import BUILDERS
    text = BUILDERS["gbdt_fused_block"]().compile().as_text()
    assert text.startswith("HloModule jit_gbdt_fused_block,")
    got = collections.Counter(
        m.group(1) for m in map(_OPCODE.match, text.splitlines()) if m)
    assert dict(got) == PARENT_OPCODES
    pinned = load_manifest()["programs"]["gbdt_fused_block"]
    assert got["fusion"] == pinned["fusions"]
    # (the per-phase body: the four numeric split phases are named)
    assert set(scopes.parse_hlo_scopes(text).values()) \
        == set(scopes.DEVICE_SCOPES + scopes.SPLIT_PHASE_SCOPES) \
        - {scopes.SAMPLE, scopes.CAT_SCAN}


# ---------------------------------------------------------------------
# the leaf-of-position pass inside the fused block (ISSUE 26)
_SHAPE = re.compile(r"\b(?:pred|[suf]\d+|bf16)\[([\d,]+)\]")


def _fused_block_text(tel, rows, num_leaves):
    tel.ensure_ring()
    g = _gbdt(rows=rows, num_leaves=num_leaves)
    g.train(1)
    g.train(3)
    prog = scopes.remembered("gbdt_fused_block")[-1]
    fn = jit_registry.get("gbdt_fused_block").fn
    return fn.lower(*prog.avals, **prog.static).compile().as_text()


def test_the_fused_block_holds_no_leaves_by_positions_buffer(tel):
    """The leaf of each position comes from compares against the few
    segments that begin inside a block of positions: nothing of the
    compiled block has a dimension of num_leaves beside one of the
    rows (which a compare of all positions with all segments, the
    one-word version of this pass, would need: 10.7 GB at the
    benchmark's size)."""
    rows, num_leaves = 1013, 13         # primes: no other table's dims
    text = _fused_block_text(tel, rows, num_leaves)
    assert tel.counters["learner.leaf_of_pos_dense_traces"] >= 1
    dims = {tuple(map(int, m.group(1).split(",")))
            for m in _SHAPE.finditer(text)}
    assert any(rows in d for d in dims) and any(num_leaves in d
                                                for d in dims)
    assert not [d for d in dims if rows in d and num_leaves in d]


def test_the_block_pass_runs_under_the_leaf_of_pos_scope(tel):
    text = _fused_block_text(tel, 500, 7)
    table = scopes.parse_hlo_scopes(text)
    of_the_pass = [m.group(1) for m in map(
        re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s.*op_name=\"[^\"]*"
                   r"/leaf_of_pos_blocks[/\"]").match, text.splitlines())
        if m]
    assert of_the_pass
    assert {table.get(name) for name in of_the_pass} \
        == {scopes.GROW_LEAF_OF_POS}
    # and the search it replaced is gone from the program
    assert "searchsorted" not in text


# ---------------------------------------------------------------------
# the score update reads no table by position (ISSUE 36)
def _eqns_under(jaxpr, scope):
    """Every equation of ``jaxpr`` and of the jaxprs its equations
    hold (scan and while bodies, closed calls) whose name stack has
    ``scope``."""
    import jax
    for eqn in jaxpr.eqns:
        if scope in str(eqn.source_info.name_stack).split("/"):
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns_under(sub, scope)


def _fused_block_of(g, m):
    """``_fused_iter_block`` over ``m`` trees of ``g``'s table, as the
    booster wraps it, and its arguments (nothing donated)."""
    import jax.numpy as jnp

    from lightgbm_tpu.models.gbdt import _fused_iter_block
    ln = g.learner
    fn = functools.partial(
        _fused_iter_block, learner=ln, grad_fn=g._grad_fn,
        bag_fn=g._traceable_bag_fn(), valid_data=(), m=m, k=1)
    return fn, (ln.mat, ln.ws, g.train_score, (), jnp.float32(0.1),
                jnp.int32(0))


def test_the_score_update_is_one_scatter_add_and_no_gather(tel):
    import jax
    tel.ensure_ring()
    fn, args = _fused_block_of(_gbdt(), 3)
    jaxpr = jax.make_jaxpr(fn)(*args)
    assert tel.counters["learner.leaf_value_pass_traces"] >= 1
    # every dense pass is still counted, the value pass among them
    assert tel.counters["learner.leaf_of_pos_dense_traces"] \
        >= tel.counters["learner.leaf_value_pass_traces"]
    got = collections.Counter(
        e.primitive.name for e in _eqns_under(jaxpr.jaxpr,
                                              scopes.SCORE_UPDATE))
    assert got["scatter-add"] == 1, got
    assert not [p for p in got if "gather" in p], got
    # what is left beside it: the product with the shrinkage
    assert got["mul"] == 1, got


@pytest.mark.parametrize("bagging", [False, True],
                         ids=["plain", "bagging"])
def test_scores_bit_equal_to_the_parents_statement(tel, bagging):
    """Three trees through ``_fused_iter_block`` as it stands against
    three through the parent's statement,
    ``score.at[row_ids, tid].add((leaf_value * scale)[pos_leaf])``, on
    the same leaf parts: ``pos_leaf = leaf_of_pos(...)`` is what the
    un-fused grow call scatters into ``leaf_id`` by ``row_ids``, so it
    is read back from there. And against the un-fused route's own
    update within a rounding (its grow program is another compiled
    program; the fused-scan tests hold the two routes' models equal)."""
    import jax
    import jax.numpy as jnp
    tel.ensure_ring()
    g = _gbdt(bagging, rows=700)
    fn, args = _fused_block_of(g, 3)
    _, _, got, _, trees, oks = jax.jit(fn)(*args)
    assert bool(np.asarray(oks).all())
    assert int(np.asarray(trees.num_leaves).min()) > 1

    ln, lr, score = g.learner, args[4], args[2]
    unfused = score
    bag_fn = g._traceable_bag_fn()
    assert (bag_fn is not None) == bagging
    parts = jax.jit(functools.partial(ln.traceable_grow,
                                      meta=ln.grow_operands()))
    for it in range(3):
        grad, hess = g._grad_fn(score[:, 0])
        bag = None if bag_fn is None else bag_fn(jnp.int32(it), grad, hess)
        _, _, tree, (row_ids, pos_value) = parts(ln.mat, ln.ws, grad,
                                                 hess, bag)
        # the same tree through the un-fused return: the index pass
        res = ln.train(grad, hess, bag)
        for field in ("num_leaves", "split_feature", "threshold_bin",
                      "leaf_count"):
            assert np.array_equal(np.asarray(getattr(tree, field)),
                                  np.asarray(getattr(res.tree, field)))
        # a permutation of the rows, with bagging too (what
        # unique_indices would promise of the scatter)
        assert np.array_equal(np.sort(np.asarray(row_ids)),
                              np.arange(700))
        pos_leaf = res.leaf_id[row_ids]
        assert np.array_equal(
            np.asarray(pos_value).view(np.uint32),
            np.asarray(tree.leaf_value[pos_leaf]).view(np.uint32))
        scale = jnp.where(tree.num_leaves > 1, lr, jnp.float32(0.0))
        score = score.at[row_ids, 0].add(
            (tree.leaf_value * scale)[pos_leaf])
        unfused = unfused.at[:, 0].add(
            (res.tree.leaf_value * lr)[res.leaf_id])
    assert np.array_equal(np.asarray(got).view(np.uint32),
                          np.asarray(score).view(np.uint32))
    assert not np.array_equal(np.asarray(got), np.asarray(args[2]))

    # the un-fused route's own statement on its own return
    # (``_score_add_leaf``: leaf values read by ``leaf_id`` by row)
    np.testing.assert_allclose(np.asarray(unfused), np.asarray(got),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------
# the per-phase split body's four scopes (ISSUE 27)
_NAMED = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s.*op_name=\"([^\"]*)\"")


def _owners(text, table, *needles):
    """Scopes of the instructions whose op path holds every needle."""
    return {table.get(m.group(1)) for m in map(_NAMED.match,
                                               text.splitlines())
            if m and all(n in m.group(2) for n in needles)}


@pytest.mark.parametrize("categorical", [False, True],
                         ids=["numeric", "categorical"])
def test_the_split_phases_own_the_per_phase_body(tel, categorical):
    tel.ensure_ring()
    more = {"categorical_feature": "3"} if categorical else {}
    g = _gbdt(rows=600, **more)
    g.train(1)
    g.train(3)
    assert tel.counters.get("learner.megakernel_traces", 0) == 0
    assert bool(tel.counters.get("learner.cat_scan_traces", 0)) \
        is categorical
    assert bool(tel.counters.get("learner.lut_partition_traces", 0)) \
        is categorical
    prog = scopes.remembered("gbdt_fused_block")[-1]
    text = jit_registry.get("gbdt_fused_block").fn.lower(
        *prog.avals, **prog.static).compile().as_text()
    table = prog.scopes()
    in_loop = scopes.GROW_SPLITS + "/while/body"
    # each kernel's instructions inside the grow loop belong to its
    # phase; the root histogram stays the root's
    assert _owners(text, table, in_loop, "partition_segment") \
        == {scopes.SPLITS_PARTITION}
    assert _owners(text, table, in_loop, "histogram_child_stream") \
        == {scopes.SPLITS_HIST}
    # the children's histograms go into the per-leaf cache under a
    # scope of their own
    assert _owners(text, table, in_loop, scopes.SPLITS_CACHE + "/") \
        == {scopes.SPLITS_CACHE}
    assert _owners(text, table, scopes.GROW_ROOT + "/",
                   "histogram_child_stream") == {scopes.GROW_ROOT}
    phases = set(table.values()) & set(scopes.SPLIT_PHASE_SCOPES)
    want = set(scopes.SPLIT_PHASE_SCOPES)
    if not categorical:
        want.discard(scopes.CAT_SCAN)
    assert phases == want
    if categorical:
        # both children's scans are one vmapped scan: the categorical
        # part is found inside the transform's name, and its sort is
        # not booked to the numeric scan
        assert _owners(text, table, in_loop,
                       f"vmap({scopes.CAT_SCAN})") \
            == {scopes.CAT_SCAN}
    # what is left to the loop's own scope: the while and its carry
    assert scopes.GROW_SPLITS in table.values()


def test_the_megakernel_body_has_none_of_the_split_phases(tel):
    """The fused body (the interpret twin here, the compiled kernel on
    a TPU) traces none of the per-phase code: its table is the eight
    scopes' it was, so the accepted cells' tables do not change."""
    tel.ensure_ring()
    g = _gbdt(rows=600, fused_split_kernel="on")
    g.train(1)
    g.train(3)
    assert tel.counters["learner.megakernel_traces"] >= 1
    assert tel.counters.get("learner.lut_partition_traces", 0) == 0
    table = scopes.program_scopes("gbdt_fused_block")
    assert set(table.values()) \
        == set(scopes.DEVICE_SCOPES) - {scopes.SAMPLE}
