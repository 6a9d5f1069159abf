#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of the Higgs model (28 dense numeric features,
binary objective, 255 leaves, 255 bins), on rows generated from a seed:

1. device: fail at once unless ``jax.devices()[0].platform == "tpu"``;
2. kernels: every Pallas kernel the chip path can select, compiled, at
   the Higgs width, against its oracle
   (``tools/check_kernels_on_chip.py``);
3. train: ``lgb.train`` for 17 rounds (the sync first iteration plus
   one fused block of 16), with a path report — which learner, which
   kernels, which iteration driver actually ran — and the train AUC
   against the same data and params on the XLA foil;
4. serve: the trained booster behind ``ServingEngine`` on the device
   route with the host fallback off, against the host route;
   then a second table of the same shape from another seed, trained
   the same way: its labels reach ``gbdt_grad`` and ``gbdt_fused_block``
   as arguments, so both programs come from the persistent compile
   cache (their ``compile`` records say ``hit``);
5. categorical: 200,000 rows shaped like the benchmark's Expo table
   (40 columns, twelve of them categories named in ``params``, four
   of those cut to 255 bins by the binning) for 9 rounds: the route
   the compiled megakernel refuses, which is the per-phase kernels
   with the bitset partition and the categorical XLA scan, still in
   fused blocks; its route counters, its trees' category
   sets on the host against the scores the device holds, and its AUC
   against the XLA foil;
6. wide: 2,000 rows shaped like the benchmark's Epsilon table (2,000
   dense numeric columns, rows of unit length), 31 leaves, for 5
   rounds: more columns than the megakernel's unrolled body takes, so
   the plan keeps the per-phase kernels, which cut their work by
   columns (the histogram a 128-column slice at a time, the Pallas
   scan a 128-feature block at a time), still in fused blocks; its
   route counters and its AUC against the XLA foil;
7. bundled: 300,000 rows of the benchmark's sparse one-hot table
   (Allstate: 4,228 columns, 33 stored values a row) handed over as a
   scipy CSR, 5 rounds: the dataset bundles the indicators (EFB) into
   a few tens of byte columns without a conflict row or a multi-val
   feature, and every split runs the bundled per-phase body (the
   256-entry table partition, the debundle before each scan, the
   Pallas scan over the logical features), still in fused blocks; its
   route counters and its AUC against the XLA foil on the same table;
8. rank: 200,000 rows of the benchmark's learning-to-rank table (MS
   LTR: 137 columns in 256-byte rows, about 1,700 ragged query groups
   of 10 to 1,251 documents, grades 0-4) with ``objective=lambdarank``
   for 5 rounds: the megakernel at its third width, the pairwise
   gradients on the query layout that follows the queries' lengths
   (``objective.rank_slots`` at most 1.6 x ``objective.rank_docs``),
   still in fused blocks; its route counters and its NDCG@10;
9. data parallel, where the host has four chips (skipped on fewer):
   500,000 rows at the benchmark's Criteo width (67 columns) for 5
   rounds with ``tree_learner=data`` over four chips, the table binned
   a row shard a worker: the mesh learner's per-phase kernels with the
   collectives between them, still in fused blocks; its path report
   and tree hash, the sharding, and the collectives' calls and bytes.

``--devices 4`` instead trains the same shape data-parallel over four
chips and checks the sharding and the AUC against the one-chip model.

Every stage asserts; nothing catches a failure to let the run go on.
The last line of stdout, printed only when every stage passed, is the
result object and nothing else:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The line before it, ``report: {...}``, carries everything observed
(versions, path report, compile cache, seconds); those seconds are
observations of one run, not measurements: it ends ``"claim": null``.
Off a TPU nothing goes to stdout at all.

Where the compile cache goes: ``lightgbm_tpu/utils/compile_cache.py``
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache_tpu`` next to
this file).
"""

import argparse
import hashlib
import json
import sys
import time

ROWS = 500_000
FEATURES = 28
ROUNDS = 17
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "learning_rate": 0.1, "metric": "", "verbosity": -1}
MIN_AUC = 0.75          # the bar bench.py's fixed baseline uses
FOIL_AUC_TOL = 1e-3
SERVE_SIZES = (1, 512, 4096)
CAT_ROWS = 200_000
CAT_ROUNDS = 9          # the sync first iteration plus a block of 8
CAT_SCORE_TOL = 1e-4    # host trees' raw scores against the device's
# a near-tie between two category sets falls the other way somewhere in
# nine 255-leaf trees, which moves in-sample AUC more than arithmetic
# does: 4.8e-4 at the first chip run, up to 1.4e-3 in the benchmark's
# check (PERF.md, PR 27); a learner that reads the categories as
# ordered parts by 2.8e-2 and more
CAT_FOIL_AUC_TOL = 5e-3
# small and shallow for the foil's sake: its scatter-add histogram
# over 2,000 columns took 427 s at 4,000 rows and 255 leaves on the
# chip (PR 31), the chip path 33 s
WIDE_ROWS = 2_000
WIDE_LEAVES = 31
WIDE_ROUNDS = 5         # the sync first iteration plus a block of 4
# 2,000 weak columns on a few thousand rows: near-ties between columns
# fall the other way under another summation order, as between category
# sets (0.998747 against the foil's 0.996756 at that first size)
WIDE_FOIL_AUC_TOL = 5e-3
# the benchmark's one-hot table (Allstate), handed over as a scipy CSR:
# at this many rows a good part of its 4,228 columns is too rare for a
# bin, the rest still bundles into a few tens of byte columns. Not the
# categorical stage's row count: both matrices have 128-byte rows, and
# a partition kernel of the same shapes would come from the process's
# cache untraced, its trace-time counter at 0
ONEHOT_ROWS = 300_000
ONEHOT_ROUNDS = 5       # the sync first iteration plus a block of 4
# a rare, weak label (one row in a hundred): five 255-leaf trees fit
# the sample, not the signal, so the bar only says "learnt something"
ONEHOT_MIN_AUC = 0.6
# exact ties are the rule on a one-hot table (equal columns, the two
# mirrored columns of a two-valued factor) and fall either way under
# another summation order; five 255-leaf trees on 3,000 positive rows
# then isolate other rows: 0.772839 against the foil's 0.780094 at the
# first chip run (PERF.md, PR 33), where the benchmark's check (a), on
# the plain reference, agrees to 8e-8 in the first tree's gains. A
# learner that reads a bundle wrong parts by a tenth and more
ONEHOT_FOIL_AUC_TOL = 2e-2
# the benchmark's learning-to-rank table (MS LTR): 137 columns in
# 256-byte rows, about 1,700 ragged query groups of 10 to 1,251
# documents, relevance grades 0-4, lambdarank. The third width through
# the megakernel and the one objective whose gradients are no
# elementwise pass (PR 37)
RANK_ROWS = 200_000
RANK_ROUNDS = 5         # the sync first iteration plus a block of 4
# in-sample NDCG@10 after five trees, from 0.36-0.38 at the seeded
# start (PERF.md, PR 37); and the AUC of "relevant at all" by the score
RANK_MIN_NDCG = 0.45
RANK_MIN_AUC = 0.6
# the benchmark's data-parallel deployment (criteo-dp4): 67 dense
# columns, rows sharded over the four chips of one host, the mesh
# learner's collectives between the phases of every split
DP_ROWS = 500_000
DP_FEATURES = 67
DP_CHIPS = 4
DP_ROUNDS = 5           # the sync first iteration plus a block of 4


def device_report() -> dict:
    """Platform, kind, count and versions as JAX reports them; exits
    non-zero, before anything is trained, unless the platform is a
    TPU."""
    import jax
    import jaxlib
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:    # hosts without a TPU
        libtpu = None
    line = (f"device: platform={info['platform']} "
            f"device_kind={info['kind']!r} count={info['count']} "
            f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
            f"libtpu={libtpu}")
    if info["platform"] != "tpu":
        sys.stderr.write(
            f"{line}\nchip_smoke: needs a TPU, JAX reports platform="
            f"{info['platform']!r}; no result\n")
        sys.exit(2)
    print(line, flush=True)
    info["versions"] = {"jax": jax.__version__,
                        "jaxlib": jaxlib.__version__, "libtpu": libtpu}
    return info


def higgs_like(n: int, f: int = FEATURES, seed: int = 42):
    """The generator of ``bench.py:measure``: dense standard-normal
    features, a label from a noisy interaction logit."""
    import numpy as np
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)

    def c(i):
        return x[:, i % f]

    logit = (2.0 * c(0) - 1.5 * c(1) + c(2) * c(3)
             + 0.8 * c(4) * c(5) - c(6))
    y = (logit + rng.randn(n).astype(np.float32) > 0).astype(np.float32)
    return x, y


def _benchmark_rows(config: str, generator: str, n: int, seed: int):
    """``(x, y, [query sizes,] the configuration)``: ``n`` rows of a
    benchmark table from its own generator and configuration file
    (``benchmarks/``)."""
    import importlib
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "benchmarks", "configs",
                           config + ".json")) as fh:
        cfg = json.load(fh)
    gen = importlib.import_module("benchmarks.generators." + generator)
    return (*gen.make(seed, n, cfg["features"],
                      **cfg["generator"]["params"]), cfg)


def expo_like(n: int, seed: int = 42):
    """``(x, y, params)``: rows of the benchmark's categorical table,
    and ``PARAMS`` with that file's categorical parameters."""
    x, y, cfg = _benchmark_rows("expo-categorical", "expo_like", n, seed)
    return x, y, dict(cfg["params"], **PARAMS)


def epsilon_like(n: int, seed: int = 42):
    """``(x, y)``: rows of the benchmark's dense-wide table."""
    return _benchmark_rows("epsilon-wide", "epsilon_like", n, seed)[:2]


def allstate_like(n: int, seed: int = 42):
    """``(x, y)``: rows of the benchmark's sparse one-hot table, ``x``
    a scipy CSR."""
    return _benchmark_rows("allstate-onehot", "allstate_like", n, seed)[:2]


def msltr_like(n: int, seed: int = 42):
    """``(x, grades, query sizes, params)``: rows of the benchmark's
    learning-to-rank table, and ``PARAMS`` with that file's objective."""
    x, y, sizes, cfg = _benchmark_rows("msltr-rank", "msltr_like", n, seed)
    return x, y, sizes, dict(PARAMS,
                             objective=cfg["params"]["objective"])


def train_auc(bst, x, y) -> float:
    """In-sample AUC by the repo's own metric, on raw scores."""
    from types import SimpleNamespace

    import numpy as np

    from lightgbm_tpu.metric.metrics import AUCMetric
    raw = np.asarray(bst.predict(x, raw_score=True), np.float64).ravel()
    assert raw.shape == (len(y),) and np.isfinite(raw).all()
    metric = AUCMetric(bst.config)
    metric.init(SimpleNamespace(label=y, weights=None), len(y))
    return float(metric.eval(raw, None)[0])


def stage_kernels(interpret: bool = False, **shapes) -> dict:
    """Each Pallas kernel against its oracle. ``shapes`` narrows a
    stage's cases (tests); the default is every case, Higgs width
    first."""
    from tools import check_kernels_on_chip as ck
    out = {}
    for stage in ck.STAGES:
        t0 = time.perf_counter()
        failures = ck.STAGE_FNS[stage](interpret=interpret,
                                       **shapes.get(stage, {}))
        assert failures == 0, f"kernel stage {stage}: {failures} " \
            "comparison(s) failed"
        out[stage] = {"ok": True,
                      "seconds": round(time.perf_counter() - t0, 1)}
    return out


def stage_train(x, y, params, rounds: int, *, learner: str,
                interpret: bool, megakernel: bool, shards: int = 1,
                categorical: bool = False, wide: bool = False,
                bundled: bool = False, min_auc: float = MIN_AUC,
                group=None):
    """``lgb.train`` + the path report. Asserts the run took the path
    it was meant to take; ``megakernel`` is what the caller expects of
    the config, the report's value is what the trace counted, as is
    ``leaf_of_pos`` (the block pass or the search, by num_leaves) and
    ``leaf_value_pass_traces`` (the pass painted the leaf's value).
    ``categorical``: ``params`` names categorical columns, so the
    bitset partition and the categorical scan must have been traced,
    the Pallas scan kernel not, and the trees must hold category
    splits that route rows on the host as the device did. ``wide``:
    the table has more columns than the megakernel takes, so the plan
    must have refused it for the width alone and every histogram call
    must have been cut into column slices. ``bundled``: ``x`` is a
    sparse one-hot table, so the dataset must have bundled it (EFB)
    into fewer physical columns without a conflict row or a multi-val
    feature, and the bundled split body (table partition, debundle
    before every scan) must have been traced. ``group``: the rows come
    in query groups of these sizes and ``y`` holds relevance grades, so
    the objective's counters must say that its query layout follows
    the documents, and the model is judged by NDCG@10 (the AUC is that
    of "relevant at all")."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability.telemetry import get_telemetry
    from lightgbm_tpu.ops.leaf_of_pos import uses_block_pass
    tel = get_telemetry()
    tel.ensure_ring()       # counters only, no sink
    before = {k: tel.counters.get(k, 0) for k in
              ("fused.block_hits", "learner.megakernel_traces",
               "learner.leaf_of_pos_dense_traces",
               "learner.leaf_value_pass_traces",
               "learner.lut_partition_traces",
               "learner.cat_scan_traces",
               "learner.wide_table_traces",
               "learner.bundled_traces",
               "kernels.partition_pipelined",
               "kernels.partition_one_compaction",
               "kernels.hist_child_stream",
               "kernels.hist_feature_slices")}
    t0 = time.perf_counter()
    bst = lgb.train(dict(params), lgb.Dataset(x, label=y, group=group),
                    num_boost_round=rounds)
    seconds = time.perf_counter() - t0
    delta = {k: int(tel.counters.get(k, 0) - v)
             for k, v in before.items()}
    gbdt = bst._gbdt
    ln = gbdt.learner
    leaves = [int(t.num_leaves) for t in gbdt.models]
    report = {
        "learner": type(ln).__name__,
        "interpret": bool(ln.interpret),
        "use_scan_kernel": bool(ln.params.use_scan_kernel),
        "megakernel": "on" if delta["learner.megakernel_traces"]
        else "off",
        "megakernel_reason": _megakernel_reason(ln),
        "leaf_of_pos": "dense"
        if delta["learner.leaf_of_pos_dense_traces"] else "search",
        # grow programs whose pass over the positions paints the
        # leaf's VALUE for the fused driver's score update (PR 36)
        "leaf_value_pass_traces":
        delta["learner.leaf_value_pass_traces"],
        "lut_partition": "on" if delta["learner.lut_partition_traces"]
        else "off",
        "cat_scan": "on" if delta["learner.cat_scan_traces"] else "off",
        # the plan refused the megakernel for the table's width alone
        "wide_table": "on" if delta["learner.wide_table_traces"]
        else "off",
        # the bundled (EFB) split body entered a grow program's trace
        "bundled": "on" if delta["learner.bundled_traces"] else "off",
        # column slices over the histogram calls traced (1 a call
        # where the stream takes whole rows: up to MAX_FUSED_F columns)
        "hist_feature_slices": delta["kernels.hist_feature_slices"],
        # kernel traces that took partition_pallas.partition_stream
        "partition_pipelined": delta["kernels.partition_pipelined"],
        # compactions (a one-hot and its permutation product) those
        # traces hold, counted where one enters a trace: one a stream
        # (PR 34; the block step before it held two and the back-copy
        # a third)
        "partition_one_compaction":
        delta["kernels.partition_one_compaction"],
        # kernel traces through hist_pallas.hist_child_stream, the
        # one histogram form: the root's and a leaf segment's program,
        # the sliced one, the megakernel's second stream
        "hist_child_stream": delta["kernels.hist_child_stream"],
        "fused_block_hits": delta["fused.block_hits"],
        "trees": len(leaves),
        "min_leaves": min(leaves),
        "max_leaves": max(leaves),
        "num_shards": int(getattr(ln, "num_shards", 1)),
        "auc": round(train_auc(bst, x, y if group is None else y > 0), 6),
        "train_seconds": round(seconds, 1),
        # the trees themselves: the tables are seeded, so a change
        # that moves the same bytes in the same order (a partition
        # kernel's) leaves every stage's hash as the last PR wrote it
        # down (PERF.md section 6)
        "model_sha256": hashlib.sha256(
            bst.model_to_string().encode()).hexdigest()[:16],
    }
    print(f"path[{learner}]: {json.dumps(report)}", flush=True)
    assert report["learner"] == learner, report
    assert report["interpret"] is interpret, report
    assert report["use_scan_kernel"] is (not interpret
                                         and not categorical), report
    assert report["megakernel"] == ("on" if megakernel else "off"), \
        report
    assert report["leaf_of_pos"] == (
        "dense" if uses_block_pass(ln.num_leaves) else "search"), report
    # the fused driver's score update reads no table by position: its
    # grow call painted the leaf values (a process that had traced the
    # same shapes before counts nothing, which interpret mode allows)
    assert report["leaf_value_pass_traces"] > 0 or interpret, report
    assert report["fused_block_hits"] > 0, \
        "_train_fused_blocks did not run"
    assert report["trees"] == rounds, report
    assert report["min_leaves"] > 1, report
    assert report["num_shards"] == shards, report
    assert report["auc"] >= min_auc, report
    assert report["cat_scan"] == ("on" if categorical else "off"), report
    assert report["lut_partition"] == (
        "on" if (categorical or bundled) and not megakernel
        else "off"), report
    assert report["bundled"] == ("on" if bundled else "off"), report
    # every compiled partition, the megakernel's phase 0 included, is
    # the pipelined stream (the megakernel's interpret twin has none)
    assert report["partition_pipelined"] > 0 or interpret, report
    # ... and holds one compaction: a second one in the block step or
    # one in the back-copy would count 2 or 3 a stream
    assert report["partition_one_compaction"] \
        == report["partition_pipelined"], report
    # every histogram of every compiled route is the one-hot stream:
    # the root's and the per-phase body's ``histogram_segment``, the
    # megakernel's phase 0 (in interpret mode the count is 0 where the
    # process had traced the same shapes before)
    assert report["hist_child_stream"] > 0 or interpret, report
    # a TPU's plan alone refuses for width: off one, ``auto`` never
    # picks the megakernel
    assert report["wide_table"] == (
        "on" if wide and not interpret else "off"), report
    if wide:
        from lightgbm_tpu.ops.hist_pallas import SLICE_F
        # the root's call and the split body's, each cut into slices
        assert report["hist_feature_slices"] \
            >= 2 * -(-ln.num_groups // SLICE_F) > 2, report
    if group is not None:
        import numpy as np

        from benchmarks.reference.gbdt_rank_numpy import ndcg_at
        seen = {"rank_" + k: int(tel.counters["objective.rank_" + k])
                for k in ("queries", "docs", "slots", "classes")}
        seen["ndcg10"] = round(ndcg_at(np.asarray(
            gbdt.train_score[:, 0], np.float64), y, group, 10), 6)
        print(f"path[{learner}]: rank {json.dumps(seen)}", flush=True)
        report.update(seen)
        assert seen["rank_docs"] == len(y), report
        assert seen["rank_slots"] <= 1.6 * seen["rank_docs"], report
        assert seen["ndcg10"] >= RANK_MIN_NDCG, report
    if bundled:
        inner = gbdt.train_data
        seen = {"logical_features": inner.num_features,
                "bundle_columns": inner.num_dense_groups,
                "bundle_conflict_rows": inner.bundle_conflict_rows,
                "multival": inner.has_multival}
        print(f"path[{learner}]: bundled {json.dumps(seen)}", flush=True)
        report.update(seen)
        assert ln.bundled and not seen["multival"], report
        assert seen["bundle_conflict_rows"] == 0, report
        assert seen["bundle_columns"] * 2 < seen["logical_features"], \
            report
    if categorical:
        import numpy as np
        cat_splits = sum(
            int((np.asarray(t.decision_type[:t.num_leaves - 1]) & 1).sum())
            for t in gbdt.models)
        # the host trees' category sets send every row where the
        # device's bitset partition sent it
        gap = float(np.abs(
            np.asarray(bst.predict(x, raw_score=True), np.float64)
            - np.asarray(gbdt.train_score[:, 0], np.float64)).max())
        seen = {"cat_splits": cat_splits, "host_vs_device_score_gap": gap}
        print(f"path[{learner}]: categorical {json.dumps(seen)}",
              flush=True)
        report.update(seen)
        assert cat_splits > 0, report
        assert gap <= CAT_SCORE_TOL, report
    return bst, report


def stage_second_table(rounds: int, rows: int = ROWS) -> dict:
    """A second Higgs-like table of the first's shape from another
    seed, trained as the first was. A new booster traces its programs
    anew, and since every array they read from the table is an
    argument, the two that hold the labels lower to the first table's
    programs and the persistent cache serves them: the ``cache`` field
    of their backend ``compile`` records, each ``hit`` on a TPU. The
    kernels' trace-time counters stay at 0 here (the process traced
    them for the first table), so the path is the first stage's."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability.telemetry import get_telemetry
    tel = get_telemetry()
    x, y = higgs_like(rows, seed=43)
    blocks = tel.counters.get("fused.block_hits", 0)
    t0 = time.perf_counter()
    bst = lgb.train(dict(PARAMS), lgb.Dataset(x, label=y),
                    num_boost_round=rounds)
    seconds = time.perf_counter() - t0
    leaves = [int(t.num_leaves) for t in bst._gbdt.models]
    report = {
        "cache": {p: [r["cache"] for r in tel.records
                      if r["kind"] == "compile"
                      and r["stage"] == "backend" and r["program"] == p
                      and r["t0"] >= t0]
                  for p in ("gbdt_grad", "gbdt_fused_block")},
        "fused_block_hits": int(tel.counters.get("fused.block_hits", 0)
                                - blocks),
        "trees": len(leaves), "min_leaves": min(leaves),
        "auc": round(train_auc(bst, x, y), 6),
        "train_seconds": round(seconds, 1),
        "model_sha256": hashlib.sha256(
            bst.model_to_string().encode()).hexdigest()[:16]}
    print(f"second_table: {json.dumps(report)}", flush=True)
    assert report["fused_block_hits"] > 0, report
    assert report["trees"] == rounds and report["min_leaves"] > 1, report
    assert report["auc"] >= MIN_AUC, report
    for p, seen in report["cache"].items():
        assert seen and set(seen) == {"hit"}, (p, seen)
    return report


def _megakernel_reason(ln) -> str:
    """What the plan says, next to what the trace counted. The mesh
    learners have no megakernel: their collectives sit between the
    per-phase kernels."""
    plan = ln.split_plan()
    return f"fused_split_kernel={ln.config.fused_split_kernel}: " \
        f"plan {plan.body}" \
        f"{'' if getattr(ln, 'has_megakernel', False) else ' (learner has no megakernel)'}" \
        " (learner/split_step.py plan_split_step)"


def stage_foil(x, y, params, rounds: int,
               hist_method: str = "onehot") -> dict:
    """The same data and params on the plain XLA path: the serial
    leaf-id learner with one-hot histograms (``scatter`` for a wide
    table, whose rows x columns x bins one-hot would not fit) and the
    XLA split scan, no Pallas kernel anywhere. An internal construction
    (the learner is swapped in before the first iteration), not an
    option."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    t0 = time.perf_counter()
    bst = lgb.Booster(dict(params, fused_split_kernel="off"),
                      lgb.Dataset(x, label=y))
    gbdt = bst._gbdt
    foil = SerialTreeLearner(gbdt.train_data, gbdt.config,
                             hist_method=hist_method)
    foil.params = foil.params._replace(use_scan_kernel=False)
    assert foil.split_plan().body == "per_phase"
    gbdt.learner = foil
    gbdt.train(rounds)
    report = {"learner": f"SerialTreeLearner(hist_method={hist_method!r})",
              "trees": len(gbdt.models),
              "auc": round(train_auc(bst, x, y), 6),
              "train_seconds": round(time.perf_counter() - t0, 1)}
    print(f"path[foil]: {json.dumps(report)}", flush=True)
    assert report["trees"] == rounds, report
    return report


def stage_serve(bst, x, sizes=SERVE_SIZES) -> dict:
    """Device route with the host fallback off, against the host
    route, one request per size. The device scan sums leaf values in
    f32 in tree order, the host loop in f64, so equality is exact only
    where no sum rounds; the report carries the largest difference and
    the gate is the f32 accumulation bound."""
    import numpy as np

    from lightgbm_tpu.serving import ServingConfig, ServingEngine
    trees = len(bst._gbdt.models)
    out = {"requests": []}
    with ServingEngine(bst, ServingConfig(
            device="always", fallback_to_host=False)) as eng, \
            ServingEngine(bst, ServingConfig(device="never")) as host:
        mv = eng.registry.current()
        assert mv.device_ready and mv.stacked is not None, \
            "registry declined to pin the model on the device"
        warm = eng.stats()
        for n in sizes:
            rows = x[:n]
            fut = eng.submit(rows, timeout_ms=0)
            got = np.asarray(fut.result(timeout=600))
            meta = fut.meta
            ref = np.asarray(host.predict(rows, timeout_ms=0))
            assert meta["route"] == "device", meta
            assert got.shape == ref.shape == (n,), (got.shape, n)
            assert np.isfinite(got).all()
            diff = float(np.abs(got - ref).max())
            # probabilities in (0, 1): |d sigmoid| <= |d raw| / 4, and
            # T f32 adds of values below max|raw| round by at most
            # T * eps * max|raw| in total
            raw_max = float(np.abs(np.asarray(
                host.predict(rows, kind="raw_score",
                             timeout_ms=0))).max())
            bound = trees * np.finfo(np.float32).eps * max(raw_max, 1.0)
            assert diff <= bound, (n, diff, bound)
            out["requests"].append({
                "rows": n, "route": meta["route"],
                "bit_identical": bool(np.array_equal(got, ref)),
                "max_abs_diff": diff,
                "latency_ms": meta["latency_ms"]})
        stats = eng.stats()
    assert stats["fallbacks"] == 0 and stats["errors"] == 0, stats
    assert stats["bucket_misses"] == warm["bucket_misses"], \
        ("a request compiled a bucket the warm-up had not", warm, stats)
    out.update(fallbacks=stats["fallbacks"],
               bucket_misses_warmup=warm["bucket_misses"],
               bucket_misses_serving=stats["bucket_misses"]
               - warm["bucket_misses"],
               bit_identical=all(r["bit_identical"]
                                 for r in out["requests"]))
    print(f"serve: {json.dumps(out)}", flush=True)
    return out


def stage_data_parallel(chips: int, rows: int = DP_ROWS,
                        params: dict = PARAMS,
                        interpret: bool = False) -> dict:
    """The data-parallel learner over ``DP_CHIPS`` chips at the
    benchmark's Criteo width, where the host has them (skipped, and
    said so, where it has fewer): the path report with its tree hash,
    the sharding, and the collectives' counters (calls, payload bytes
    and the bytes a chip sends of them, counted where each enters a
    grow program's trace)."""
    if chips < DP_CHIPS:
        return {"skipped": f"{chips} chip(s), the stage needs {DP_CHIPS}"}
    from lightgbm_tpu.observability.telemetry import get_telemetry
    tel = get_telemetry()
    before = {k: v for k, v in tel.counters.items()
              if k.startswith("comm.")}
    x, y = higgs_like(rows, DP_FEATURES, seed=44)
    params = dict(params, tree_learner="data", num_machines=DP_CHIPS)
    bst, report = stage_train(
        x, y, params, DP_ROUNDS, learner="MeshPartitionedTreeLearner",
        interpret=interpret, megakernel=False, shards=DP_CHIPS)
    report["shards"] = stage_shards(bst, rows, DP_CHIPS)
    report["comm"] = {k: v - before.get(k, 0)
                      for k, v in sorted(tel.counters.items())
                      if k.startswith("comm.")
                      and v != before.get(k, 0)}
    print(f"data_parallel: {json.dumps(report['comm'])}", flush=True)
    for op in ("psum", "psum_scatter", "all_gather"):
        assert report["comm"].get(f"comm.{op}_sent_bytes", 0) > 0, report
    return report


def stage_shards(bst, rows: int, devices: int) -> dict:
    """The mesh learner's training matrix: one shard per device, about
    rows/devices each, none holding the whole; device memory in use
    within 2x across the devices."""
    import jax
    ln = bst._gbdt.learner
    shards = ln.mat.addressable_shards
    devs = sorted({s.device.id for s in shards})
    shard_rows = [int(s.data.shape[0] * s.data.shape[1])
                  for s in shards]
    stats = [d.memory_stats() for d in jax.devices()[:devices]]
    # a TPU always reports; the CPU test mesh reports nothing
    assert all(stats) or jax.default_backend() != "tpu", stats
    in_use = [int(st["bytes_in_use"]) for st in stats if st]
    out = {"shard_devices": devs, "shard_rows": shard_rows,
           "bytes_in_use": in_use}
    print(f"shards: {json.dumps(out)}", flush=True)
    assert len(devs) == devices == len(shards), out
    # each shard: its rows/devices share padded to the kernels' block
    # layout, and less than the whole matrix would take
    from lightgbm_tpu.ops.hist_pallas import matrix_rows
    per = matrix_rows(-(-rows // devices))
    assert all(r == per < matrix_rows(rows) for r in shard_rows), out
    assert not in_use or max(in_use) <= 2 * min(in_use), out
    return out


def watch_persistent_cache() -> dict:
    """Count jax's own persistent-cache events from here on (the
    telemetry counter of the same name also counts the predictor's
    in-process signature hits)."""
    import jax.monitoring
    seen = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return seen


def cache_report(seen: dict) -> dict:
    from lightgbm_tpu.observability.telemetry import get_telemetry
    from lightgbm_tpu.utils.compile_cache import resolve_cache_dir
    c = get_telemetry().counters
    return {"dir": resolve_cache_dir(),
            "compiles": int(c.get("jit.compiles", 0)),
            "compile_seconds": round(c.get("jit.compile_s", 0.0), 1),
            "persistent_cache_hits": seen["hits"],
            "persistent_cache_misses": seen["misses"]}


def result_line(device: dict) -> str:
    """The last line of stdout: exactly ``ok`` and the device as JAX
    reports it. Everything else observed is on the ``report:`` line."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip smoke (default); 4: "
                    "data-parallel training over four chips")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device = device_report()
    assert device["count"] >= args.devices, device

    # everything below needs the repo: in a directory holding nothing
    # else of it, the import fails and so does the run
    from lightgbm_tpu.observability.telemetry import get_telemetry
    from lightgbm_tpu.utils.compile_cache import \
        maybe_enable_compile_cache
    from lightgbm_tpu.utils.roofline import device_peaks
    get_telemetry().ensure_ring()
    maybe_enable_compile_cache()    # before the first compile
    cache_events = watch_persistent_cache()
    peaks = device_peaks()
    print(f"peaks: {json.dumps(peaks)}", flush=True)

    x, y = higgs_like(ROWS)
    report = {"versions": device["versions"], "peaks": peaks,
              "rows": ROWS, "features": FEATURES, "rounds": ROUNDS,
              "params": PARAMS}
    if args.devices == 1:
        report["kernels"] = stage_kernels()
        # the static rule selects the megakernel for this config
        bst, report["train"] = stage_train(
            x, y, PARAMS, ROUNDS, learner="PartitionedTreeLearner",
            interpret=False, megakernel=True)
        report["foil"] = stage_foil(x, y, PARAMS, ROUNDS)
        gap = abs(report["train"]["auc"] - report["foil"]["auc"])
        assert gap <= FOIL_AUC_TOL, ("chip path vs XLA foil", gap)
        report["serve"] = stage_serve(bst, x)
        report["second_table"] = stage_second_table(ROUNDS)
        # the table the megakernel refuses: per-phase kernels, bitset
        # partition, categorical scan; no scan kernel, still fused
        cx, cy, cat_params = expo_like(CAT_ROWS)
        _, report["categorical"] = stage_train(
            cx, cy, cat_params, CAT_ROUNDS,
            learner="PartitionedTreeLearner", interpret=False,
            megakernel=False, categorical=True)
        report["categorical_foil"] = stage_foil(cx, cy, cat_params,
                                                CAT_ROUNDS)
        gap = abs(report["categorical"]["auc"]
                  - report["categorical_foil"]["auc"])
        assert gap <= CAT_FOIL_AUC_TOL, ("categorical chip path vs foil",
                                         gap)
        # the table too wide for the megakernel: per-phase kernels cut
        # by columns, the Pallas scan by feature blocks, still fused
        wx, wy = epsilon_like(WIDE_ROWS)
        wide_params = dict(PARAMS, num_leaves=WIDE_LEAVES)
        _, report["wide"] = stage_train(
            wx, wy, wide_params, WIDE_ROUNDS,
            learner="PartitionedTreeLearner", interpret=False,
            megakernel=False, wide=True)
        report["wide_foil"] = stage_foil(wx, wy, wide_params,
                                         WIDE_ROUNDS,
                                         hist_method="scatter")
        gap = abs(report["wide"]["auc"] - report["wide_foil"]["auc"])
        assert gap <= WIDE_FOIL_AUC_TOL, ("wide chip path vs foil", gap)
        # the sparse one-hot table from a CSR: bundled into one
        # 128-byte row, the table partition, the debundle before every
        # scan, the Pallas scan over the logical features, still fused
        ox, oy = allstate_like(ONEHOT_ROWS)
        _, report["bundled"] = stage_train(
            ox, oy, PARAMS, ONEHOT_ROUNDS,
            learner="PartitionedTreeLearner", interpret=False,
            megakernel=False, bundled=True, min_auc=ONEHOT_MIN_AUC)
        report["bundled_foil"] = stage_foil(ox, oy, PARAMS, ONEHOT_ROUNDS)
        gap = abs(report["bundled"]["auc"] - report["bundled_foil"]["auc"])
        assert gap <= ONEHOT_FOIL_AUC_TOL, ("bundled chip path vs foil",
                                            gap)
        # the learning-to-rank table: ragged query groups, lambdarank's
        # pairwise gradients on the query layout, the megakernel at 137
        # columns and 256-byte rows, still fused
        rx, ry, sizes, rank_params = msltr_like(RANK_ROWS)
        _, report["rank"] = stage_train(
            rx, ry, rank_params, RANK_ROUNDS,
            learner="PartitionedTreeLearner", interpret=False,
            megakernel=True, min_auc=RANK_MIN_AUC, group=sizes)
    else:
        mesh_params = dict(PARAMS, tree_learner="data",
                           num_machines=args.devices)
        bst, report["train"] = stage_train(
            x, y, mesh_params, ROUNDS,
            learner="MeshPartitionedTreeLearner", interpret=False,
            megakernel=False, shards=args.devices)
        report["shards"] = stage_shards(bst, ROWS, args.devices)
        _, report["one_chip"] = stage_train(
            x, y, PARAMS, ROUNDS, learner="PartitionedTreeLearner",
            interpret=False, megakernel=True)
        gap = abs(report["train"]["auc"] - report["one_chip"]["auc"])
        assert gap <= FOIL_AUC_TOL, ("four chips vs one chip", gap)
    report["data_parallel"] = stage_data_parallel(device["count"])
    report["compile_cache"] = cache_report(cache_events)
    report["seconds"] = round(time.perf_counter() - t_start, 1)
    report["claim"] = None
    print(f"report: {json.dumps(report)}", flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
