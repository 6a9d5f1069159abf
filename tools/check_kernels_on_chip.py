"""Compiled Pallas kernels against their oracles, one stage per kernel.

The reference's GPU_DEBUG_COMPARE (gpu_tree_learner.cpp) recomputes
device histograms on the host and compares; CI runs our Pallas kernels
only in interpret mode on CPU, which catches none of Mosaic's
hardware-compile failures. Each stage here runs one kernel the chip
path can select — the one-hot histogram stream (``histogram_segment``:
whole rows up to ``MAX_FUSED_F`` columns, column slices past it),
``partition_segment``, split-scan, and the split-step megakernel —
COMPILED against a NumPy/XLA oracle, and the one XLA function a bundled
table's split walks at a one-hot table's real width (``debundle``). The first shape of every stage is
the Higgs width (28 features, 256 bins).

``chip_smoke.py`` calls the stage functions in-process; standalone:

    python tools/check_kernels_on_chip.py [stage ...]

Stages: hist partition_v1 split_scan fused_split debundle (default:
all). Every
requested stage runs every time — no verdict is remembered between
runs. A stage returns its number of failed comparisons; a kernel the
compiler refuses raises. Exits non-zero unless every stage passed.

``--lowering`` runs only the megakernel's host-side Mosaic lowering
(no TPU needed). ``interpret=True`` (tests/test_chip_smoke.py) drives
the same comparisons through the interpret twins on the CPU.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# the kernel accumulates exact bf16 hi/lo pairs in f32; vs a NumPy
# oracle the summation ORDER differs, so absolute error grows with the
# magnitude of the sums (~3e-6 relative observed)
TOL = dict(rtol=1e-4, atol=1e-3)

# (rows, features, bins); Higgs width first
MATRIX_SHAPES = ((20000, 28, 256), (5000, 12, 64), (7333, 5, 16))
# the histogram's: both sides of MAX_FUSED_F as well (whole rows at
# 192 columns, two column slices at 193)
HIST_SHAPES = MATRIX_SHAPES + ((4000, 192, 256), (4000, 193, 256))


def _segments(n, unaligned):
    """Whole range, an unaligned start (shift > 0 hits the
    read-merge-write path at non-8-aligned boundaries), one block, and
    the tail."""
    return [(0, n), (unaligned, n - unaligned),
            (min(1234, n // 2), min(2048, n // 4)),
            (n - 517, 517)]


def _hist_inputs(rng, n, f, b):
    import jax.numpy as jnp

    from lightgbm_tpu.ops.hist_pallas import build_matrix, pack_gh
    binned = rng.randint(0, b, (n, f))
    g = rng.randn(n).astype("float32")
    h = (rng.rand(n) + 0.1).astype("float32")
    c = (rng.rand(n) > 0.1).astype("float32")
    mat = build_matrix(jnp.asarray(binned), 2048)
    mat = pack_gh(mat, f, jnp.asarray(g * c), jnp.asarray(h * c),
                  jnp.asarray(c))
    return binned, g, h, c, mat


def stage_hist(interpret: bool = False, shapes=HIST_SHAPES) -> int:
    """``histogram_segment`` against a NumPy oracle (the gate) and
    against ``ops/histogram.py``'s scatter on the same device (the
    difference is printed)."""
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops.hist_pallas import histogram_segment
    from lightgbm_tpu.ops.histogram import histogram_scatter
    rng = np.random.RandomState(0)
    failures = 0
    for n, f, b in shapes:
        binned, g, h, c, mat = _hist_inputs(rng, n, f, b)
        ghc = jnp.asarray(np.stack([g * c, h * c, c], 1))
        for begin, count in _segments(n, 8):
            hc = np.asarray(histogram_segment(
                mat, begin, count, b, f, interpret=interpret))
            ho = np.zeros((f, b, 3), np.float32)
            sl = slice(begin, begin + count)
            for j in range(f):
                np.add.at(ho[j], (binned[sl, j], 0), (g * c)[sl])
                np.add.at(ho[j], (binned[sl, j], 1), (h * c)[sl])
                np.add.at(ho[j], (binned[sl, j], 2), c[sl])
            hx = np.asarray(histogram_scatter(
                jnp.asarray(binned[sl]), ghc[sl], b))
            ok = np.allclose(hc, ho, **TOL) \
                and np.array_equal(hc[..., 2], ho[..., 2])
            print(f"hist [{n}x{f} b={b}] seg=({begin},{count}) "
                  f"kernel-vs-oracle: {'ok ' if ok else 'FAIL'} "
                  f"max|d|={np.abs(hc - ho).max():.2e} "
                  f"vs ops/histogram.py max|d|="
                  f"{np.abs(hc - hx).max():.2e}", flush=True)
            failures += 0 if ok else 1
    return failures


def traced_products(fn, *args) -> list:
    """The matrix products in the body of each loop of the one Pallas
    kernel ``fn(*args)`` traces: a list a loop, in program order
    (``partition_segment``: the forward stream, then the back-copy),
    of the products' output shapes. Read off the jaxpr the compiler is
    handed, so nothing runs; what a call executes is a loop's list
    times its trip count."""
    import jax

    def inner(eqn):
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) \
                    else (value,):
                item = getattr(item, "jaxpr", item)
                if hasattr(item, "eqns"):
                    yield item

    def find(jaxpr, name):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == name:
                yield eqn
            else:
                for sub in inner(eqn):
                    yield from find(sub, name)

    (call,) = find(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")
    return [[tuple(dot.outvars[0].aval.shape)
             for dot in find(loop.params["body_jaxpr"].jaxpr,
                             "dot_general")]
            for loop in call.params["jaxpr"].eqns
            if loop.primitive.name == "while"]


def stage_partition_v1(interpret: bool = False,
                       shapes=MATRIX_SHAPES) -> int:
    import functools

    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops.hist_pallas import extract_row_ids
    from lightgbm_tpu.ops.partition_pallas import (merge_windows,
                                                   partition_segment,
                                                   stream_compactions,
                                                   stream_windows)
    rng = np.random.RandomState(1)
    failures = 0
    for n, f, b in shapes:
        binned, _, _, _, mat = _hist_inputs(rng, n, f, b)
        col, thr = f // 2, b // 2
        lut = jnp.zeros((1, 256), jnp.float32)
        calls = {use_lut: functools.partial(
            partition_segment, blk=512, interpret=interpret,
            use_lut_path=use_lut) for use_lut in (True, False)}
        # compactions a block, as each program is traced: the products
        # over whole rows in the forward and the back-copy loop's body
        row_products = {use_lut: [
            sum(shape[-1] == mat.shape[1] for shape in loop)
            for loop in traced_products(
                call, mat, mat, *([jnp.int32(0)] * 9), lut)]
            for use_lut, call in calls.items()}
        for begin, count in _segments(n, 13):
            for use_lut in (True, False):
                args = (jnp.int32(begin), jnp.int32(count), col,
                        jnp.int32(thr), jnp.int32(0), jnp.int32(0),
                        jnp.int32(0), jnp.int32(b), jnp.int32(0), lut)
                m_c, _, nl_c = calls[use_lut](
                    mat, jnp.zeros_like(mat), *args)
                sl = slice(begin, begin + count)
                go_left = binned[sl, col] <= thr
                nl_o = int(go_left.sum())
                # exact STABLE order: segment row ids, lefts first
                rid_seg = np.asarray(
                    extract_row_ids(m_c, f, mat.shape[0]))[sl]
                rid_orig = np.arange(n)[sl]
                want = np.concatenate([rid_orig[go_left],
                                       rid_orig[~go_left]])
                # windows that read their destination back before
                # writing it: the kernel's count against the host rule
                merged = merge_windows(
                    begin, count, [int(go_left[k:k + 512].sum())
                                   for k in range(0, count, 512)], 512)
                windows = stream_windows(count, nl_o, 512)
                # compactions the call ran: the traced products a
                # block times each loop's trips, against the host
                # rule (one a forward block, not one a window)
                forward, back = row_products[use_lut]
                ran = forward * -(-count // 512) \
                    + back * -(-(count - nl_o) // 512)
                compactions = stream_compactions(count, 512)
                ok = (int(nl_c[0]) == nl_o
                      and np.array_equal(rid_seg[:count], want)
                      and int(nl_c[1]) == merged
                      and ran == compactions)
                print(f"partition [{n}x{f}] "
                      f"seg=({begin},{count}) lut={use_lut}: "
                      f"{'ok ' if ok else 'FAIL'} "
                      f"left={int(nl_c[0])}/{nl_o} "
                      f"merged={int(nl_c[1])}/{windows} windows "
                      f"({int(nl_c[1]) / max(windows, 1):.1%}; "
                      f"host rule {merged}) "
                      f"compactions={ran} (host rule {compactions}; "
                      f"{forward} a forward block and {back} a "
                      f"back-copy block, as traced)", flush=True)
                failures += 0 if ok else 1
    return failures + _partition_bundled_split(interpret)


def _partition_bundled_split(interpret: bool) -> int:
    """``partition_segment`` with the 256-entry table of a BUNDLED
    numeric split (``partition_decision_lut``: group value -> feature
    bin -> goes left), on a one-hot table as ``Dataset.from_scipy``
    bundles it: an indicator in the middle of a bundle, and a raw
    numeric column of the same matrix, which takes the threshold
    compare through the same compiled kernel."""
    import jax.numpy as jnp
    import numpy as np
    import scipy.sparse as sp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.data.bundling import decode_feature_bin
    from lightgbm_tpu.learner.serial import feature_meta_from_dataset
    from lightgbm_tpu.ops.hist_pallas import (build_matrix,
                                              extract_row_ids)
    from lightgbm_tpu.ops.partition_pallas import (partition_decision_lut,
                                                   partition_segment)
    rng = np.random.RandomState(7)
    n, cards = 6000, (40, 300, 5)
    dense = np.zeros((n, 2 + sum(cards)), np.float32)
    dense[:, :2] = rng.randn(n, 2)
    base = 2
    for c in cards:
        dense[np.arange(n), base + rng.randint(0, c, n)] = 1.0
        base += c
    cfg = Config.from_params({"objective": "binary", "verbosity": -1,
                              "min_data_in_bin": 1,
                              "feature_pre_filter": False})
    ds = Dataset.from_scipy(sp.csr_matrix(dense), cfg,
                            label=dense[:, 0] > 0)
    assert ds.feature_group is not None and not ds.has_multival \
        and ds.bundle_conflict_rows == 0, "the table did not bundle"
    meta = feature_meta_from_dataset(ds, cfg)
    g = ds.num_groups
    mat = build_matrix(jnp.asarray(ds.binned), 2048)
    offsets = np.asarray(ds.feature_offset)
    inside = int(np.flatnonzero(offsets > 100)[0])  # deep in a bundle
    raw = int(np.flatnonzero(offsets == 0)[0])
    failures = 0
    for feat, thr in ((inside, 0), (raw, 120)):
        grp_col, use_lut, lut = partition_decision_lut(
            meta, jnp.int32(feat), jnp.int32(thr), jnp.bool_(False),
            jnp.bool_(False), jnp.zeros((8,), jnp.uint32), True)
        for begin, count in _segments(n, 13):
            m_c, _, nl_c = partition_segment(
                mat, jnp.zeros_like(mat), jnp.int32(begin),
                jnp.int32(count), grp_col, jnp.int32(thr), jnp.int32(0),
                meta.missing[feat], meta.default_bin[feat],
                meta.num_bins[feat], use_lut.astype(jnp.int32), lut,
                blk=512, interpret=interpret, use_lut_path=True)
            sl = slice(begin, begin + count)
            fbin = decode_feature_bin(
                ds.binned[sl, ds.feature_group[feat]].astype(np.int64),
                int(offsets[feat]), int(ds.num_bin(feat)))
            go_left = fbin <= thr
            rid_seg = np.asarray(extract_row_ids(m_c, g, mat.shape[0]))[sl]
            rid_orig = np.arange(n)[sl]
            want = np.concatenate([rid_orig[go_left], rid_orig[~go_left]])
            ok = (int(nl_c[0]) == int(go_left.sum())
                  and np.array_equal(rid_seg[:count], want)
                  and bool(use_lut) == (offsets[feat] > 0))
            print(f"partition bundled [{n}x{ds.num_features} in {g}] "
                  f"feature={feat} offset={int(offsets[feat])} "
                  f"seg=({begin},{count}) table={bool(use_lut)}: "
                  f"{'ok ' if ok else 'FAIL'} "
                  f"left={int(nl_c[0])}/{int(go_left.sum())}", flush=True)
            failures += 0 if ok else 1
    return failures


def stage_split_scan(interpret: bool = False,
                     shapes=((28, 256, False), (11, 64, True))) -> int:
    """Fused split-scan kernel vs the XLA reference scan — validates
    the Mosaic lowering (cumsum lane-shift ladder, SMEM scalars,
    [F, 8] packed output) that CI only sees interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops.split import (FeatureMeta, SplitParams,
                                        per_feature_numerical)
    from lightgbm_tpu.ops.split_scan_pallas import \
        per_feature_numerical_pallas
    rng = np.random.RandomState(2)
    failures = 0
    for f, b, any_missing in shapes:
        meta = FeatureMeta(
            num_bins=jnp.asarray(rng.randint(3, b, f), jnp.int32),
            missing=jnp.asarray(
                rng.randint(0, 3 if any_missing else 1, f), jnp.int32),
            default_bin=jnp.asarray(rng.randint(0, 5, f), jnp.int32),
            most_freq_bin=jnp.zeros(f, jnp.int32),
            monotone=jnp.zeros(f, jnp.int32),
            penalty=jnp.ones(f, jnp.float32),
            is_categorical=jnp.zeros(f, bool),
            global_id=jnp.arange(f, dtype=jnp.int32))
        params = SplitParams(
            lambda_l1=0.0, lambda_l2=0.5, max_delta_step=0.0,
            min_data_in_leaf=5.0, min_sum_hessian_in_leaf=1e-3,
            min_gain_to_split=0.0, any_missing=any_missing,
            use_scan_kernel=True)
        hist = np.zeros((f, b, 3), np.float32)
        for j in range(f):
            nb = int(meta.num_bins[j])
            hist[j, :nb, 2] = rng.randint(0, 50, nb)
            hist[j, :nb, 0] = rng.randn(nb) * hist[j, :nb, 2]
            hist[j, :nb, 1] = np.abs(rng.randn(nb)) * hist[j, :nb, 2]
        pg, ph, pc = (float(hist[0, :, j].sum()) for j in range(3))
        args = (jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph),
                jnp.float32(pc), meta, params, jnp.float32(-np.inf),
                jnp.float32(np.inf), jnp.ones(f, bool))
        ref = per_feature_numerical(*args)
        got = per_feature_numerical_pallas(*args, interpret=interpret)
        # the production path always calls the kernel under jax.vmap
        # (scan_children) — check the BATCHED lowering too
        gotv = jax.vmap(lambda hh: per_feature_numerical_pallas(
            hh, *args[1:], interpret=interpret))(
            jnp.stack([args[0], args[0] * 0.5]))
        sc_r, sc_g = np.asarray(ref.score), np.asarray(got.score)
        sc_v = np.asarray(gotv.score)[0]
        fin = np.isfinite(sc_r)
        # a gain is a difference of G^2/H terms built from 256-term
        # f32 prefix sums taken in another order (lane-shift ladder vs
        # XLA cumsum), so its error scales with the largest term, not
        # with the gain: 1e-4 of the largest score
        tol = dict(rtol=1e-4,
                   atol=1e-4 * float(np.abs(sc_r[fin]).max(initial=1.0)))
        ok = (np.array_equal(fin, np.isfinite(sc_g))
              and np.allclose(sc_g[fin], sc_r[fin], **tol)
              and np.array_equal(fin, np.isfinite(sc_v))
              and np.allclose(sc_v[fin], sc_r[fin], **tol))
        thr_agree = float((np.asarray(ref.threshold)
                           == np.asarray(got.threshold))[fin].mean()) \
            if fin.any() else 1.0
        ok = ok and thr_agree > 0.9
        print(f"split-scan [F={f} B={b} missing={any_missing}] "
              f"kernel-vs-xla (+vmap): {'ok ' if ok else 'FAIL'} "
              f"thr_agree={thr_agree:.2f}", flush=True)
        failures += 0 if ok else 1
    return failures


def histogrammed_share(tree) -> float:
    """Rows histogrammed ÷ rows partitioned over one tree's splits,
    from the tree's own counts: each split partitions its parent and
    hands the histogram stream its smaller child's segment
    (``hist_pallas.hist_child_stream``; the megakernel has no
    spare output word to count the rows itself). 0.28-0.35 on the
    benchmark's tables, never above 0.5."""
    import numpy as np
    n = int(tree.num_leaves) - 1
    internal = np.asarray(tree.internal_count[:n], np.float64)
    leaf = np.asarray(tree.leaf_count, np.float64)

    def child_rows(child):
        child = np.asarray(child[:n])
        return np.where(child >= 0, internal[np.maximum(child, 0)],
                        leaf[np.maximum(~child, 0)])
    smaller = np.minimum(child_rows(tree.left_child),
                         child_rows(tree.right_child))
    return float(smaller.sum() / max(internal.sum(), 1.0))


def stage_fused_split(interpret: bool = False, rows: int = 20000,
                      features: int = 28, leaves: int = 31) -> int:
    """Split-step megakernel vs the per-phase foil: the same
    partitioned learner grows one tree from the same gradients with
    the kernel forced on and forced off. The kernel's histogram/scan
    roundings differ from the foil's at f32 level (like the
    reference's GPU learner), so the gate is identical leaf counts +
    close per-row outputs, not byte-equality (the interpret twin owns
    byte-equality in CI). ``max_bin=255`` gives the 256-bin width.
    Also prints the share of the partitioned rows that the kernel's
    histogram stream was handed, and fails above 0.5: the stream
    visits the smaller child, never the parent."""
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner

    rng = np.random.RandomState(7)
    x = rng.randn(rows, features).astype("float32")
    y = (x[:, 0] - 0.5 * x[:, 1] + 0.3 * rng.randn(rows) > 0) \
        .astype("float32")
    grad = jnp.asarray(0.5 - y)
    hess = jnp.full((rows,), 0.25, jnp.float32)

    def grow(mode):
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": leaves,
            "max_bin": 255, "fused_split_kernel": mode,
            "verbosity": -1})
        lrn = PartitionedTreeLearner(
            Dataset.from_numpy(x, cfg, label=y), cfg,
            interpret=interpret)
        assert (lrn.split_plan().body == "megakernel") \
            == (mode == "on"), mode
        res = lrn.train(grad, hess)
        tree = res.tree
        return (int(tree.num_leaves),
                np.asarray(tree.leaf_value)[np.asarray(res.leaf_id)],
                histogrammed_share(tree))

    nl_on, out_on, share = grow("on")
    nl_off, out_off, _ = grow("off")
    err = float(np.abs(out_on - out_off).max())
    ok = nl_on == nl_off and nl_on > 1 and share <= 0.5 and np.allclose(
        out_on, out_off, rtol=1e-3, atol=1e-3)
    print(f"fused_split [{rows}x{features}] "
          f"kernel-vs-foil tree: {'ok ' if ok else 'FAIL'} "
          f"leaves={nl_on}/{nl_off} max|dout|={err:.2e} "
          f"histogrammed/partitioned rows={share:.3f}",
          flush=True)
    return 0 if ok else 1


def stage_debundle(interpret: bool = False, columns: int = 47,
                   numeric: int = 16, indicators: int = 4212) -> int:
    """``debundle_hist`` (a row gather and one static roll a bit of the
    shift) against the form it replaced (PR 33), an index a bin
    (``take_along_axis``), compiled for the same device: equal bit for
    bit for both children of a split, at the one-hot table's width
    (``allstate-onehot``: 16 raw numeric columns and 4,212 two-bin
    indicators in 31 bundles, 4,228 features in 47 columns). Plain
    XLA, so ``interpret`` changes nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops.histogram import debundle_hist
    bins = 256
    bundles = columns - numeric
    sizes = [indicators // bundles + (k < indicators % bundles)
             for k in range(bundles)]
    assert max(sizes) < bins
    group = np.r_[np.arange(numeric),
                  np.repeat(numeric + np.arange(bundles), sizes)]
    offset = np.r_[np.zeros(numeric, np.int64),
                   np.concatenate([1 + np.arange(m) for m in sizes])]
    num_bins = np.r_[np.full(numeric, bins), np.full(indicators, 2)]
    group, offset, num_bins = (jnp.asarray(a, jnp.int32)
                               for a in (group, offset, num_bins))

    def index_a_bin(hist_g, leaf):
        hf = hist_g[group]
        at = jnp.arange(bins, dtype=jnp.int32)[None, :]
        picked = jnp.take_along_axis(
            hf, jnp.clip(offset[:, None] + at - 1, 0, bins - 1)[:, :, None],
            axis=1)
        x = jnp.where(((at >= 1) & (at < num_bins[:, None]))[:, :, None],
                      picked, 0.0)
        x = x.at[:, 0, :].set(leaf[None, :] - x.sum(axis=1))
        return jnp.where((offset > 0)[:, None, None], x, hf)

    def rolls(hist_g, leaf):
        return debundle_hist(hist_g, group, offset, num_bins,
                             leaf[0], leaf[1], leaf[2])

    rng = np.random.default_rng(11)
    hist_g = jnp.asarray(rng.random((2, columns, bins, 3),
                                    dtype=np.float32))
    leaf = hist_g[:, 0].sum(axis=1)                  # [2, 3]
    got = jax.jit(jax.vmap(rolls))(hist_g, leaf)
    want = jax.jit(jax.vmap(index_a_bin))(hist_g, leaf)
    apart = int(np.count_nonzero(np.asarray(got) != np.asarray(want)))
    ok = apart == 0 and got.shape == (2, len(group), bins, 3)
    print(f"debundle [{columns} columns -> {len(group)} features x "
          f"{bins} bins, both children]: {'ok ' if ok else 'FAIL'} "
          f"{apart} values apart", flush=True)
    return 0 if ok else 1


STAGE_FNS = {"hist": stage_hist, "partition_v1": stage_partition_v1,
             "split_scan": stage_split_scan,
             "fused_split": stage_fused_split,
             "debundle": stage_debundle}
STAGES = tuple(STAGE_FNS)


def main() -> int:
    argv = sys.argv[1:]
    if "--lowering" in argv:
        from lightgbm_tpu.learner.partitioned import segment_grow_pack
        from lightgbm_tpu.ops.split_step_pallas import lower_for_tpu
        lower_for_tpu(segment_grow_pack(15), big_l=15)
        print("fused_split mosaic-lowering: ok", flush=True)
        return 0
    import jax

    from lightgbm_tpu.utils.device import on_tpu
    if not on_tpu():
        print(f"needs a TPU (backend={jax.default_backend()}); use "
              "--lowering for the host-side Mosaic pass")
        return 2
    unknown = [a for a in argv if a not in STAGES]
    if unknown:
        print(f"unknown stage(s) {unknown}; valid: {list(STAGES)}")
        return 2
    total = 0
    for stage in argv or STAGES:
        print(f"== stage {stage}", flush=True)
        failures = STAGE_FNS[stage]()
        total += failures
        print(f"== stage {stage}: "
              f"{'PASS' if failures == 0 else f'{failures} FAILURES'}",
              flush=True)
    print("PASS" if total == 0 else f"{total} FAILURES")
    return 0 if total == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
