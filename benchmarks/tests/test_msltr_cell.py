"""The learning-to-rank cell (``msltr-2m-train``: ``kinds/train.py``'s
run through ``kinds/train_rank.py``) end to end on the CPU at a tiny
size, its probe against a padded layout, its plain reference against a
lower precision and a dropped step, its generator and its readers on
made-up facts (ISSUE 37)."""

import json
import types

import numpy as np
import pytest
from lightgbm_tpu.observability import scopes as vocabulary

from benchmarks import run, scopes, spec
from benchmarks import trace_reduce as tr

CELL = "msltr-2m-train"
# sizes cut here and nowhere else: 20 columns, so that the megakernel's
# interpret twin takes seconds; 6,000 rows are 50 queries of 13 to
# 1,251 documents in eight length classes
TINY = {"config": {"features": 20, "params": {"num_leaves": 15},
                   "check": {"queries": 20, "trees": 2,
                             "ndcg_queries": 30, "min_ndcg": 0.2}},
        "traffic": {"rows": 6000, "measure_steps": 2,
                    "params": {"tree_learner": "partitioned",
                               "fused_split_kernel": "on"}}}
RANK_METRICS = {"rank_grad_ms_per_tree", "rank_pairs_ms_per_tree",
                "rank_sort_ms_per_tree", "rank_layout_ms_per_tree",
                "rank_slots_per_doc", "rank_pair_slots_per_doc_pair",
                "rank_grad_roofline"}


def _run(capsys, trace, scratch):
    rc = run.main(["--workload", CELL, "--seed", "2147483999",
                   "--seconds", "2", "--trace", str(trace)],
                  tiny=dict(TINY, allow_cpu=True, scratch=str(scratch)))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-5:]
    info = {ln.split(" ", 2)[1]: json.loads(ln.split(" ", 2)[2])
            for ln in out if ln.startswith("info:")}
    return json.loads(out[-1]), info


def _read(name, facts):
    return spec.load_module("layers", name).read(facts)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_through_the_general_kind(capsys, tmp_path, trace):
    cell = spec.load_cell(spec.load_benchmark(), CELL)
    assert cell.traffic["kind"] == "train_rank"
    assert (cell.traffic["block"], cell.traffic["measure_steps"]) == (2, 12)
    result, info = _run(capsys, trace, tmp_path)
    assert result["correct"] is True, info
    assert result["attempted"] > 0 and result["failed"] == 0
    ref = info["check_reference"]
    assert ref["first_tree_compared_splits"] == ref["first_tree_splits"] > 0
    check = cell.config["check"]
    assert ref["grad_err"] <= check["grad_rtol"]
    assert ref["hess_err"] <= check["hess_rtol"]
    assert ref["gain_err_median"] <= check["gain_median_rtol"]
    path = info["check_path"]
    assert path["learner"] == "PartitionedTreeLearner"
    assert path["megakernel"] is True and path["objective"] == "lambdarank"
    assert path["compiles_in_window"] == 0
    assert path["fused_block_hits"] == result["attempted"]
    assert path["rank_docs"] == 6000
    assert path["rank_slots"] <= 1.6 * path["rank_docs"]
    assert path["rank_classes"] == 8
    # check (b) judged the window's model by NDCG@10
    full = info["check_full_size"]
    assert 0.2 <= full["ndcg10_warm"] <= full["ndcg10_end"] <= 1.0
    bench = spec.load_benchmark()
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in spec.metrics_for(bench, section, CELL)}
    got = set(result["metrics"])
    if not trace:
        assert got == declared == {"train_mrow_iters_per_s", "setup_s"}
        return
    assert RANK_METRICS <= declared
    assert not declared & {"split_loop_ms_per_split",
                           "split_body_ms_per_split"}
    # every metric listed for the cell is read but for those that need
    # a chip: its peak, and Mosaic calls in the trace (the CPU runs the
    # kernels' interpret twins)
    needs_a_chip = {"grow_kernels_roofline", "train_hbm_floor_share",
                    "rank_grad_roofline"}
    assert needs_a_chip <= declared - got \
        <= needs_a_chip | {"grad_ms_per_tree"}
    by = info["scopes"]
    assert all(by[name] > 0 for name in vocabulary.RANK_SCOPES)
    m = result["metrics"]
    assert m["rank_grad_ms_per_tree"]["value"] == pytest.approx(
        sum(m[k]["value"] for k in ("rank_pairs_ms_per_tree",
                                    "rank_sort_ms_per_tree",
                                    "rank_layout_ms_per_tree"))
        + 1e3 * by.get(vocabulary.GRADIENTS, 0.0) / 2)
    assert m["rank_slots_per_doc"]["value"] \
        == path["rank_slots"] / path["rank_docs"]
    assert 1.0 <= m["rank_pair_slots_per_doc_pair"]["value"] <= 4.0


def test_the_probe_refuses_a_padded_layout(monkeypatch):
    """A program whose layout pads every query to the longest, or that
    counts nothing, fails at the probe, before any data is made."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability.telemetry import get_telemetry

    from benchmarks.kinds import train_rank
    tel = get_telemetry()
    tel.ensure_ring()
    params = {"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
              "tree_learner": "partitioned"}
    # as the program is: it passes
    train_rank._require_ragged_layout(lgb, tel, params, 20,
                                      "PartitionedTreeLearner")
    plain = train_rank._rank_facts

    def padded(tel):
        facts = plain(tel)
        return dict(facts, slots=facts["queries"] * 1251)
    monkeypatch.setattr(train_rank, "_rank_facts", padded)
    with pytest.raises(spec.SpecError, match="does not follow"):
        train_rank._require_ragged_layout(lgb, tel, params, 20,
                                          "PartitionedTreeLearner")
    monkeypatch.setattr(train_rank, "_rank_facts", plain)
    silent = types.SimpleNamespace(counters={})
    with pytest.raises(spec.SpecError, match="counts no"):
        train_rank._rank_facts(silent)


def test_the_generator_draws_values_of_one_table():
    gen = spec.load_module("generators", "msltr_like")
    sizes = gen.query_sizes(2270296)
    assert len(sizes) == 18919 and sizes.sum() == 2270296
    assert sizes.min() >= 1 and sizes.max() == 1251
    assert (sizes == 1251).sum() == 1
    x, y, s = gen.make(2147483999, 30000, 137)
    assert x.shape == (30000, 137) and x.dtype == np.float32
    assert s.sum() == 30000 and s.max() == 1251
    assert set(np.unique(y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
    shares = np.bincount(y.astype(int), minlength=5) / len(y)
    assert np.abs(shares - gen.GRADE_SHARES).max() < 0.05
    # the same seed gives the same rows; another seed gives other
    # values in the same query groups and the same kinds of columns
    x2, y2, s2 = gen.make(2147483999, 30000, 137)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    x3, y3, s3 = gen.make(7, 30000, 137)
    assert np.array_equal(s, s2) and np.array_equal(s, s3)
    assert not np.array_equal(x, x3)
    counts = [j for j in range(137)
              if np.array_equal(x[:, j], np.floor(x[:, j]))]
    assert 30 < len(counts) < 80
    assert counts == [j for j in range(137)
                      if np.array_equal(x3[:, j], np.floor(x3[:, j]))]
    assert min(len(np.unique(x[:, j])) for j in counts) < 255


def test_the_cell_is_listed_as_the_issue_names_it():
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in RANK_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "gradients"
        assert by_name[name]["moves"] == "train_mrow_iters_per_s"
    assert CELL not in by_name["split_loop_ms_per_split"]["workloads"]
    assert CELL not in by_name["split_body_ms_per_split"]["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == "msltr-rank")
    assert len(entry["source"]) <= 200
    assert entry["reduced"] == ["trees"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("msltr-rank", "train-msltr-2m", 1)
    cfg = spec.load_cell(bench, CELL).config
    assert (cfg["features"], cfg["max_bin"], cfg["num_leaves"],
            cfg["train_rows"]) == (137, 255, 255, 2270296)
    assert cfg["params"]["objective"] == "lambdarank"


def _facts(monkeypatch, vocab):
    # one tree: windows [0, 2], sort [2, 3], pairs [3, 7], sort back
    # [7, 8], the gather back [8, 9], the weights' multiply [9, 10]
    names = ["%windows.1 = ...", "%sort.2 = ...", "%pairs.3 = ...",
             "%back.4 = ...", "%weights.5 = ..."]
    ops = tr.DeviceOps([names[i] for i in (0, 1, 2, 1, 3, 4)],
                       [0, 2, 3, 7, 8, 9], [2, 3, 7, 8, 9, 10])
    table = {"windows.1": getattr(vocab, "RANK_LAYOUT", None),
             "sort.2": getattr(vocab, "RANK_SORT", None),
             "pairs.3": getattr(vocab, "RANK_PAIRS", None),
             "back.4": getattr(vocab, "RANK_LAYOUT", None),
             "weights.5": vocabulary.GRADIENTS}
    table = {k: v or vocabulary.GRADIENTS for k, v in table.items()}
    monkeypatch.setattr(scopes, "_table",
                        lambda facts: (vocab, table, 0.01))
    monkeypatch.setattr(scopes, "_idle_by_span", lambda trace, names: {})
    return {"trace": tr.Trace({0: ops}, [], 10.0), "rows": 1000,
            "block": 1, "features": 137, "chips": 1,
            "device_kind": "TPU v5 lite",
            "rank": {"queries": 10, "docs": 1000, "slots": 1300,
                     "pair_slots": 300000, "doc_pairs": 150000,
                     "classes": 3},
            "traced_trees": [{"leaves": 3, "split_rows": [10.0, 6.0],
                              "smaller_child_rows": [4.0, 2.0]}]}


def test_readers_on_made_up_events(monkeypatch):
    facts = _facts(monkeypatch, vocabulary)
    assert _read("rank_layout_ms_per_tree", facts) == pytest.approx(3000.0)
    assert _read("rank_sort_ms_per_tree", facts) == pytest.approx(2000.0)
    assert _read("rank_pairs_ms_per_tree", facts) == pytest.approx(4000.0)
    assert _read("rank_grad_ms_per_tree", facts) == pytest.approx(10000.0)
    assert _read("grad_ms_per_tree", facts) == pytest.approx(1000.0)
    assert _read("rank_slots_per_doc", facts) == pytest.approx(1.3)
    assert _read("rank_pair_slots_per_doc_pair", facts) \
        == pytest.approx(2.0)
    # 1,000 documents x 16 B at 819 GB/s over 10 s
    want = 100.0 * 1000 * 16 / 819e9 / 10.0
    assert _read("rank_grad_roofline", facts) == pytest.approx(want)
    assert want < 100.0


def test_a_program_without_the_rank_scopes_reads_as_nothing(monkeypatch):
    """The parent of ISSUE 37 has the vocabulary and a table, but no
    ranking scope and no ranking counter: no metric, no error."""
    old = types.SimpleNamespace(**{
        k: getattr(vocabulary, k) for k in dir(vocabulary)
        if k.isupper() and not k.startswith("RANK_")})
    facts = _facts(monkeypatch, old)
    del facts["rank"]
    for name in RANK_METRICS:
        assert _read(name, facts) is None, name
        assert _read(name, {}) is None, name
    # an elementwise objective's program on the new vocabulary traces
    # none of the three
    facts = _facts(monkeypatch, vocabulary)
    monkeypatch.setattr(
        scopes, "_table", lambda facts: (
            vocabulary, {"weights.5": vocabulary.GRADIENTS}, 0.01))
    facts.pop("_by_scope", None)
    assert _read("rank_grad_ms_per_tree", facts) is None
    assert _read("rank_grad_roofline", facts) is None


@pytest.mark.parametrize("foil,fails_by,holds", [
    (None, (), ("grad", "hess", "gain", "ndcg")),
    ("bfloat16-scores", ("grad", "hess", "gain"), ("ndcg",)),
    ("no-normalisation", ("grad", "hess", "gain"), ()),
    ("half-shrinkage", ("ndcg",), ("grad", "hess", "gain"))])
def test_the_reference_check_holds_rule_and_precision(foil, fails_by,
                                                      holds):
    """Check (a) as the cell runs it, with the configuration's own
    limits. Against the plain reference it holds. Against the reference
    computed from scores rounded to bfloat16 (the nearest precision
    below the configuration's float32), or with lambdarank's
    normalisation left out, it reads ok false by the element-by-element
    comparison of the first iteration's gradients and hessians (and by
    the first tree's gains, which are sums of them), NDCG@10 inside its
    limit under bfloat16: not by each. Against a wrong rule (leaf
    values shrunk by half the learning rate) it reads ok false by
    NDCG@10 alone, the gradients and the first tree's gains
    untouched."""
    import functools

    import lightgbm_tpu as lgb
    import ml_dtypes

    from benchmarks.kinds import train_rank
    from benchmarks.reference import gbdt_rank_numpy
    cfg = spec.load_cell(spec.load_benchmark(), CELL).config
    check = dict(cfg["check"], queries=60, trees=3)
    params = dict(cfg["params"], num_leaves=31,
                  tree_learner="partitioned")
    gen = spec.load_module("generators", cfg["generator"]["name"])
    x, y, sizes = gen.make(6, 12000, 20, **cfg["generator"]["params"])
    ds = lgb.Dataset(x, label=y, group=sizes, params=params).construct()
    plain = gbdt_rank_numpy.lambdarank_gradients
    reference = gbdt_rank_numpy.train
    if foil == "bfloat16-scores":
        reference = functools.partial(
            reference, gradients=lambda score, *a: plain(
                np.asarray(score).astype(ml_dtypes.bfloat16).astype(
                    np.float64), *a))
    elif foil == "no-normalisation":
        reference = functools.partial(
            reference, gradients=lambda score, labels, sizes, p: plain(
                score, labels, sizes, dict(p, lambdarank_norm=False)))
    elif foil == "half-shrinkage":
        reference = functools.partial(
            lambda binned, num_bins, labels, sizes, p, trees, **kw:
            gbdt_rank_numpy.train(
                binned, num_bins, labels, sizes,
                dict(p, learning_rate=p["learning_rate"] / 2), trees, **kw))
    out = train_rank._check_against_reference(lgb, ds, params, check,
                                              reference=reference)
    assert out["learner"] == "PartitionedTreeLearner"
    over = {
        "grad": out["grad_err"] > check["grad_rtol"],
        "hess": out["hess_err"] > check["hess_rtol"],
        "gain": out["gain_err_median"] > check["gain_median_rtol"],
        "ndcg": abs(out["ndcg"] - out["ndcg_reference"])
        > check["ndcg_tol"]}
    print(foil, {k: out[k] for k in ("grad_err", "hess_err",
                                     "gain_err_median", "ndcg",
                                     "ndcg_reference")})
    assert all(over[k] for k in fails_by), out
    assert not any(over[k] for k in holds), out
    assert out["ok"] is (not fails_by)
