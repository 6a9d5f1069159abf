"""The sparse one-hot cell (``allstate-12m-train``: ``kinds/train.py``'s
run through ``kinds/train_sparse.py``) end to end on the CPU at a tiny
size, its generator, its plain reference against a lower precision, a
bundle plan that was granted conflicts, and its readers on made-up
events and recorded facts (ISSUE 33)."""

import functools
import json
import types

import numpy as np
import pytest
from lightgbm_tpu.observability import scopes as vocabulary

from benchmarks import run, scopes, spec
from benchmarks import trace_reduce as tr

CELL = "allstate-12m-train"
# sizes cut here and nowhere else: the seventeen categoricals keep
# their nesting and their small factors, with fewer makes, models and
# sub-models; 16 + 290 columns bundle into 33 byte columns
CARDS = [6, 30, 70, 2, 2, 3, 3, 4, 5, 5, 6, 7, 8, 9, 10, 8, 15]
TINY = {"config": {"features": 16 + sum(CARDS),
                   "params": {"num_leaves": 15},
                   "generator": {"params": {"cards": CARDS,
                                            "positive": 0.2}},
                   "check": {"rows": 2000, "trees": 2, "auc_rows": 4000,
                             "auc_tol": 1e-3, "logloss_tol": 1e-3}},
        "traffic": {"rows": 4000,
                    "params": {"tree_learner": "partitioned"}}}


def _run(capsys, trace, scratch, tiny=TINY):
    rc = run.main(["--workload", CELL, "--seed", "2147483999",
                   "--seconds", "2", "--trace", str(trace)],
                  tiny=dict(tiny, allow_cpu=True, scratch=str(scratch)))
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
    assert cell.traffic["kind"] == "train_sparse"
    assert cell.traffic["rows"] == cell.config["train_rows"] == 12184290
    result, info = _run(capsys, trace, tmp_path)
    assert result["correct"] is True, info
    ref = info["check_reference"]
    assert ref["first_tree_compared_splits"] > 0
    assert ref["gain_err_median"] <= cell.config["check"]["gain_median_rtol"]
    assert result["attempted"] > 0 and result["failed"] == 0
    path = info["check_path"]
    assert path["learner"] == "PartitionedTreeLearner"
    assert path["megakernel"] is False and path["bundled"] is True
    assert path["bundle_conflict_rows"] == 0
    assert path["multival_features"] == 0
    assert path["bundle_columns"] == 33 < path["logical_features"]
    assert path["compiles_in_window"] == 0
    assert path["fused_block_hits"] == result["attempted"]
    assert ref["learner"] == "PartitionedTreeLearner"
    bench = spec.load_benchmark()
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in spec.metrics_for(bench, section, CELL)}
    got = set(result["metrics"])
    if not trace:
        assert got == declared == {"train_mrow_iters_per_s", "setup_s"}
        return
    # every metric listed for the cell is read but for those that need
    # a chip: its peak, and Mosaic calls in the trace; the CPU's trace
    # does not always hold an event of the gradients' one fused pass
    needs_a_chip = {"grow_kernels_roofline", "train_hbm_floor_share"}
    assert needs_a_chip <= declared - got \
        <= needs_a_chip | {"grad_ms_per_tree"}
    assert {"debundle_ms_per_split", "bundle_s",
            "bundle_columns_per_feature", "partition_ms_per_split",
            "seg_hist_ms_per_split", "scan_ms_per_split"} <= got
    metrics = result["metrics"]
    assert metrics["bundle_columns_per_feature"]["value"] \
        == pytest.approx(33 / path["logical_features"])
    assert metrics["bundle_s"]["value"] > 0      # a cache miss: bundled
    by = info["scopes"]
    assert by[vocabulary.SPLITS_DEBUNDLE] > 0
    assert by[vocabulary.ROOT_DEBUNDLE] > 0
    assert sum(v for k, v in by.items() if k.startswith("lgbm.")) \
        + by["unattributed"] == pytest.approx(by["busy"], rel=0.02)
    # a second run of the seed loads the bundled table from the cache:
    # nothing was bundled in its set-up, and it says so
    again, info = _run(capsys, 1, tmp_path)
    assert info["dataset"]["cache"] == "hit"
    assert again["correct"] is True
    assert again["metrics"]["bundle_s"]["value"] == 0.0
    assert info["check_path"]["bundle_columns"] == 33


def test_the_generator_draws_one_hot_rows_of_one_table():
    import scipy.sparse as sp
    gen = spec.load_module("generators", "allstate_like")
    features = 16 + sum(CARDS)
    x, y = gen.make(2147483999, 70000, features, cards=CARDS)
    assert sp.isspmatrix_csr(x) and x.shape == (70000, features)
    assert x.dtype == np.float32 and x.indices.dtype == np.int32
    # 16 numerics and one indicator a categorical in every row
    assert np.all(np.diff(x.indptr) == 33) and x.nnz == 70000 * 33
    assert x.has_sorted_indices
    base = 16 + np.concatenate([[0], np.cumsum(CARDS)])
    per_row = x.indices.reshape(70000, 33)
    for k in range(len(CARDS)):
        assert np.all((per_row[:, 16 + k] >= base[k])
                      & (per_row[:, 16 + k] < base[k + 1]))
    assert np.all(x.data.reshape(70000, 33)[:, 16:] == 1.0)
    assert 0.005 < y.mean() < 0.02                # a rare label
    # the same seed gives the same rows, and the head of a table is
    # the table's head (two blocks of rows here, the second cut)
    x2, y2 = gen.make(2147483999, 70000, features, cards=CARDS,
                      head=66000)
    assert (x2 != x[:66000]).nnz == 0 and np.array_equal(y2, y[:66000])
    # another seed gives other rows of the same table: the nesting and
    # the frequencies come from table_seed
    x3, _ = gen.make(7, 70000, features, cards=CARDS)
    assert (x3 != x).nnz > 0
    share, share3 = (np.bincount(m.indices, minlength=features) / 70000
                     for m in (x, x3))
    np.testing.assert_allclose(share[16:], share3[16:], atol=0.01)
    # a sub-model belongs to one model, a model to one make
    model, sub = per_row[:, 17], per_row[:, 18]
    assert len(set(zip(sub, model))) == len(set(sub))
    assert len(set(zip(model, per_row[:, 16]))) == len(set(model))
    with pytest.raises(ValueError, match="columns are not"):
        gen.make(1, 100, features + 1, cards=CARDS)


def _check(monkeypatch, foil, leaves=63):
    import lightgbm_tpu as lgb
    import ml_dtypes

    from benchmarks.kinds import train_sparse
    from benchmarks.reference import gbdt_sparse_numpy
    cfg = spec.load_cell(spec.load_benchmark(), CELL).config
    check = dict(cfg["check"], rows=20000)
    params = dict(cfg["params"], num_leaves=leaves,
                  tree_learner="partitioned")
    gen = spec.load_module("generators", cfg["generator"]["name"])
    gen_params = dict(cfg["generator"]["params"], cards=CARDS,
                      positive=0.2)
    make = functools.partial(gen.make, 6, 20000, 16 + sum(CARDS),
                             **gen_params)
    x, y = make()
    ds = lgb.Dataset(x, label=y, params=params).construct()
    plain = gbdt_sparse_numpy.train
    if foil == "bfloat16":
        monkeypatch.setattr(gbdt_sparse_numpy, "train", functools.partial(
            plain, quantize=lambda a: a.astype(ml_dtypes.bfloat16).astype(
                np.float64)))
    elif foil == "half-shrinkage":
        monkeypatch.setattr(
            gbdt_sparse_numpy, "train",
            lambda *a, **kw: plain(
                *a[:6], dict(a[6], learning_rate=a[6]["learning_rate"] / 2),
                *a[7:], **kw))
    out = train_sparse._check_against_reference(
        lgb, ds, params, check, lambda rows: make(head=rows))
    over = {
        "auc": abs(out["auc"] - out["auc_reference"]) > check["auc_tol"],
        "logloss": abs(out["logloss"] - out["logloss_reference"])
        > check["logloss_tol"],
        "gain": out["gain_err_median"] > check["gain_median_rtol"]}
    return out, tuple(k for k in over if over[k])


@pytest.mark.parametrize("foil,fails_by", [
    (None, ()), ("bfloat16", ("gain",)),
    ("half-shrinkage", ("auc", "logloss"))])
def test_the_reference_check_holds_rule_and_precision(monkeypatch, foil,
                                                      fails_by):
    """Check (a) as the cell runs it, with the configuration's own
    limits, against the plain reference on per-column bins (it holds),
    against that reference with gradients and hessians rounded to
    bfloat16, the nearest precision below the configuration's (ok
    false by the first tree's median gain difference ALONE), and
    against a wrong rule (leaf values shrunk by half the learning
    rate: ok false by AUC and log-loss, the gains untouched)."""
    out, over = _check(monkeypatch, foil)
    assert out["learner"] == "PartitionedTreeLearner"
    assert out["first_tree_compared_splits"] >= 40
    assert over == fails_by, out
    assert out["ok"] is (not fails_by)


def _with_two_bundles_merged(plan_fn):
    """A planner that puts two of ``plan_fn``'s bundles into one byte
    column: what a conflict budget grants, taken to where it shows on
    any table (two factors' values share rows)."""
    def plan(*args, **kw):
        out = plan_fn(*args, **kw)
        group, offset = out.feature_group, out.feature_offset
        shared = [g for g in range(out.num_groups)
                  if (group == g).sum() >= 2]
        a, b = sorted(shared, key=lambda g: out.group_num_bins[g])[:2]
        assert out.group_num_bins[a] + out.group_num_bins[b] <= 257
        moved = group == b
        offset[moved] += out.group_num_bins[a] - 1
        out.group_num_bins[a] += out.group_num_bins[b] - 1
        group[moved] = a
        group[group > b] -= 1
        out.group_num_bins = np.delete(out.group_num_bins, b)
        out.num_groups -= 1
        out.mv_group_start -= 1
        return out
    return plan


def test_a_plan_with_a_conflict_budget_reads_not_correct(capsys, tmp_path,
                                                         monkeypatch):
    """A plan that lets columns which share rows share a byte column
    (what a conflict budget above 0 grants) on a table that has such
    rows: the second value overwrites the first, ``bundle_conflict_rows``
    says in how many rows, and the run is not correct by that number,
    whatever the trees look like."""
    from lightgbm_tpu.data import bundling

    from benchmarks.kinds import train_sparse
    monkeypatch.setattr(
        bundling, "plan_bundles_from_nonzeros",
        _with_two_bundles_merged(bundling.plan_bundles_from_nonzeros))
    # the width probe is the first to refuse such a program
    with pytest.raises(spec.SpecError, match="losslessly"):
        _run(capsys, 0, tmp_path)
    # past it, the window's own table is held to the same
    monkeypatch.setattr(train_sparse, "_require_bundled_width",
                        lambda *a: None)
    result, info = _run(capsys, 0, tmp_path)
    path = info["check_path"]
    assert path["bundle_conflict_rows"] > 0 and path["bundled"] is True
    assert path["bundle_columns"] == 32
    assert path["bundle_ok"] is False and path["ok"] is False
    assert path["fused_block_hits"] == result["attempted"]
    assert info["check_full_size"]["ok"] is True
    assert result["correct"] is False


# ---- the readers ----------------------------------------------------
def _facts(monkeypatch, vocab):
    # one tree of 3 leaves (2 splits): root histogram [0, 4], root
    # debundle [4, 5]; a split: histogram [5, 8], debundle [8, 10],
    # scan [10, 11]; the second split alike
    kernel = ' custom-call(...), custom_call_target="tpu_custom_call"'
    names = ["%hist_root.1 =" + kernel, "%root_debundle.2 = ...",
             "%hist_segment.3 =" + kernel, "%gather.4 = ...",
             "%scan.5 =" + kernel]
    ops = tr.DeviceOps(
        [names[i] for i in (0, 1, 2, 3, 4, 2, 3, 4)],
        [0, 4, 5, 8, 10, 11, 14, 16], [4, 5, 8, 10, 11, 14, 16, 17])
    table = {"hist_root.1": vocabulary.GROW_ROOT,
             "root_debundle.2": getattr(vocab, "ROOT_DEBUNDLE", None),
             "hist_segment.3": vocabulary.SPLITS_HIST,
             "gather.4": getattr(vocab, "SPLITS_DEBUNDLE", None),
             "scan.5": vocabulary.SPLITS_SCAN}
    table = {k: v for k, v in table.items() if v is not None}
    monkeypatch.setattr(scopes, "_table",
                        lambda facts: (vocab, table, 0.01))
    monkeypatch.setattr(scopes, "_idle_by_span", lambda trace, names: {})
    return {"trace": tr.Trace({0: ops}, [], 17.0), "rows": 10, "block": 1,
            "features": 47, "logical_features": 4228, "chips": 1,
            "device_kind": "TPU v5 lite", "bundle_s": 31.5,
            "traced_trees": [{"leaves": 3, "split_rows": [10.0, 6.0],
                              "smaller_child_rows": [4.0, 2.0]}]}


def test_readers_on_made_up_events(monkeypatch):
    facts = _facts(monkeypatch, vocabulary)
    # 4 s under the split body's debundle over 2 splits; the root's
    # one debundle has its own scope and is not in it
    assert _read("debundle_ms_per_split", facts) == pytest.approx(2000.0)
    assert _read("scan_ms_per_split", facts) == pytest.approx(1000.0)
    assert _read("bundle_s", facts) == 31.5
    assert _read("bundle_columns_per_feature", facts) \
        == pytest.approx(47 / 4228)


def test_a_program_without_bundle_scopes_reads_as_nothing(monkeypatch):
    """The parent of ISSUE 33 has the vocabulary and a table, but no
    ``SPLITS_DEBUNDLE``, and its kind hands no bundle facts: no metric,
    no error."""
    old = types.SimpleNamespace(**{
        k: getattr(vocabulary, k) for k in dir(vocabulary)
        if k.isupper() and "DEBUNDLE" not in k and "BUNDLE" not in k})
    facts = _facts(monkeypatch, old)
    assert _read("debundle_ms_per_split", facts) is None
    assert _read("scan_ms_per_split", facts) == pytest.approx(1000.0)
    for name in ("debundle_ms_per_split", "bundle_s",
                 "bundle_columns_per_feature"):
        assert _read(name, {}) is None
    assert _read("bundle_columns_per_feature",
                 {"features": 28}) is None


# recorded: the facts of the traced chip run of PR 33 (seed 2147483317)
# that the two byte-reckoning readers use (PERF.md section 5): the
# physical width, the rate of the untraced steps, the Mosaic kernels'
# time (77.63 % of 2.2550 s busy) and the traced tree's row sums (11.2
# N partitioned, 2.2 N histogrammed, as the two readings imply)
RECORDED = {"features": 47, "logical_features": 4228, "chips": 1,
            "device_kind": "TPU v5 lite",
            "rate_untraced_mrow_iters_per_s": 5.28,
            "kernel_s": 1.7506, "split_rows_sum": 11.2 * 12184290,
            "smaller_child_rows_sum": 2.2 * 12184290}


@pytest.mark.parametrize("features,under", [(47, True), (4228, False)])
def test_the_rooflines_reckon_the_physical_row(features, under):
    """``grow_kernels_roofline`` and ``train_hbm_floor_share`` reckon a
    row's bytes from ``facts["features"]``. With the PHYSICAL width the
    kind hands them (47 columns: a 75-byte row of a 128-byte one) both
    read under 5 %; with the 4,228 logical columns they would count
    4,240-byte rows that no kernel moves, about 70 times the bytes."""
    n = 12184290.0
    tree = {"leaves": 255,
            "split_rows": [n] + [(RECORDED["split_rows_sum"] - n) / 253]
            * 253,
            "smaller_child_rows":
            [RECORDED["smaller_child_rows_sum"] / 254] * 254}
    kernel = ' custom-call(...), custom_call_target="tpu_custom_call"'
    ops = tr.DeviceOps(["%k.1 =" + kernel], [0.0],
                       [RECORDED["kernel_s"]])
    facts = dict(RECORDED, features=features, traced_trees=[tree],
                 trace=tr.Trace({0: ops}, [], RECORDED["kernel_s"]))
    roofline = _read("grow_kernels_roofline", facts)
    floor = _read("train_hbm_floor_share", facts)
    assert (roofline < 5.0 and floor < 5.0) is under, (roofline, floor)
    if under:
        # what the traced run printed: 1.3562 % and 0.1193 %
        assert roofline == pytest.approx(1.356, rel=0.02)
        assert floor == pytest.approx(0.1193, rel=0.01)
    else:
        assert roofline > 50.0


def test_the_new_metrics_are_listed_where_they_read():
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("debundle_ms_per_split", "bundle_s",
                 "bundle_columns_per_feature"):
        assert by_name[name]["workloads"] == [CELL]
    assert by_name["bundle_s"]["moves"] == "setup_s"
    for name in ("partition_ms_per_split", "seg_hist_ms_per_split",
                 "scan_ms_per_split"):
        assert by_name[name]["workloads"][-1] == CELL
    # two lists the issue names are pinned by the accepted benchmark's
    # own tests (test_epsilon_cell.py, test_expo_cell.py), which this
    # PR may not edit: the cell is not on them (PERF.md section 7)
    for name in ("hist_cache_ms_per_split", "split_body_ms_per_split"):
        assert CELL not in by_name[name]["workloads"]
    entry = next(c for c in bench["configs"]
                 if c["name"] == "allstate-onehot")
    assert entry["reduced"] == ["trees"] and len(entry["source"]) == 184
    cell = spec.load_cell(bench, CELL)
    cfg = cell.config
    assert (cfg["features"], cfg["max_bin"], cfg["num_leaves"],
            cfg["rows"]) == (4228, 255, 255, 13184290)
    gen = spec.load_module("generators", cfg["generator"]["name"])
    assert 16 + sum(cfg["generator"]["params"]["cards"]) == 4228
    assert tuple(cfg["generator"]["params"]["cards"]) == gen.CARDS
    assert cell.chips == 1 and cell.traffic["block"] == 1
