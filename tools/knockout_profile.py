"""Knock-out profile of the partitioned grow loop.

Compiles grow variants with individual components disabled and compares
wall time at 500k rows / 255 leaves — the difference isolates each
component's contribution to the ~1.2 ms/split device cost.

Variants (shapes/structure identical so compile effort is comparable):
  full        — production body
  no_part     — partition kernel skipped (nl = cnt // 2, rows unmoved)
  no_hist     — histogram kernel skipped (child hist = parent * 0.5)
  no_scan     — best-split scans skipped (children get -inf gain after
                a fixed number of splits... instead: reuse parent split
                with decayed gain)
  no_state    — kernels + scans run, but per-leaf state writes collapsed

Run: python tools/knockout_profile.py [rows]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 500_000
    f = 28

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.ops.split import best_split, leaf_output_no_constraint
    from lightgbm_tpu.ops.hist_pallas import (combine_planes,
                                              histogram_segment_raw)
    from lightgbm_tpu.ops.partition_pallas import partition_segment
    from lightgbm_tpu.utils.compile_cache import \
        maybe_enable_compile_cache
    maybe_enable_compile_cache()

    rng = np.random.RandomState(42)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + rng.randn(n) > 0).astype(np.float32)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 255,
                              "max_bin": 255, "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    grad = jnp.asarray(y - 0.5)
    hess = jnp.full((n,), 0.25, jnp.float32)

    def run(tag, knock):
        learner = PartitionedTreeLearner(ds, cfg)
        import functools
        grow = functools.partial(_grow_knock, knock=knock)
        # mirror learner.train but with the knocked body
        fn = jax.jit(
            functools.partial(
                grow, meta=learner.meta, params=learner.params,
                num_leaves=learner.num_leaves,
                max_depth=learner.max_depth,
                num_bins_max=learner.num_bins_max,
                num_features=learner.num_features, n=n,
                interpret=learner.interpret))
        from lightgbm_tpu.utils.sync import fetch_one as fetch

        mat, ws = learner.mat, learner.ws
        t_c0 = time.perf_counter()
        r = fn(mat, ws, grad, hess)
        fetch(r)
        compile_s = time.perf_counter() - t_c0
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            r = fn(mat, ws, grad, hess)
            fetch(r)
        dt = (time.perf_counter() - t0) / iters
        print(f"{tag:10s}: {dt*1e3:9.2f} ms/tree  (compile {compile_s:.0f}s)",
              flush=True)
        return dt

    def _grow_knock(mat, ws, grad, hess, *, knock, meta, params, num_leaves,
                    max_depth, num_bins_max, num_features, n, interpret):
        from lightgbm_tpu.ops.hist_pallas import extract_row_ids, pack_gh
        f_ = num_features
        b = num_bins_max
        big_l = num_leaves
        rids = extract_row_ids(mat, f_, mat.shape[0])
        gp = jnp.where(jnp.arange(mat.shape[0]) < n,
                       grad[jnp.clip(rids, 0, n - 1)], 0.0)
        hp = jnp.where(jnp.arange(mat.shape[0]) < n,
                       hess[jnp.clip(rids, 0, n - 1)], 0.0)
        cp = jnp.where(jnp.arange(mat.shape[0]) < n, 1.0, 0.0)
        mat = pack_gh(mat, f_, gp, hp, cp)

        def seg_hist(m, begin, count):
            raw = histogram_segment_raw(m, begin, count, num_features=f_,
                                        num_bins=b, blk=2048,
                                        interpret=interpret)
            return combine_planes(raw, f_)

        inf = jnp.float32(jnp.inf)
        fmask = jnp.ones((f_,), bool)

        def scan_leaf(hist, g, h, c):
            return best_split(hist, g, h, c, meta, params,
                              constraint_min=-inf, constraint_max=inf,
                              feature_mask=fmask)

        root_hist = seg_hist(mat, jnp.int32(0), jnp.int32(n))
        sums = root_hist[0].sum(axis=0)
        root_g, root_h, root_c = sums[0], sums[1], sums[2]
        root_split = scan_leaf(root_hist, root_g, root_h, root_c)
        root_out = leaf_output_no_constraint(
            root_g, root_h + 2e-15, params.lambda_l1, params.lambda_l2,
            params.max_delta_step)

        def at0(arr, val):
            return arr.at[0].set(val)

        state = dict(
            k=jnp.int32(1), mat=mat, ws=ws,
            leaf_begin=jnp.zeros((big_l,), jnp.int32),
            leaf_cnt=at0(jnp.zeros((big_l,), jnp.int32), jnp.int32(n)),
            hist=at0(jnp.zeros((big_l, f_, b, 3), jnp.float32), root_hist),
            leaf_g=at0(jnp.zeros((big_l,), jnp.float32), root_g),
            leaf_h=at0(jnp.zeros((big_l,), jnp.float32), root_h),
            leaf_c=at0(jnp.zeros((big_l,), jnp.float32), root_c),
            bs_gain=at0(jnp.full((big_l,), -jnp.inf), root_split.gain),
            bs_feat=at0(jnp.zeros((big_l,), jnp.int32), root_split.feature),
            bs_thr=at0(jnp.zeros((big_l,), jnp.int32), root_split.threshold),
            bs_lg=at0(jnp.zeros((big_l,), jnp.float32), root_split.left_g),
            bs_lh=at0(jnp.zeros((big_l,), jnp.float32), root_split.left_h),
            bs_lc=at0(jnp.zeros((big_l,), jnp.float32), root_split.left_c),
        )
        leaf_range = jnp.arange(big_l)

        def cond(st):
            og = jnp.where(leaf_range < st["k"], st["bs_gain"], -jnp.inf)
            return (st["k"] < big_l) & jnp.isfinite(og.max())

        def body(st):
            k = st["k"]
            og = jnp.where(leaf_range < k, st["bs_gain"], -jnp.inf)
            leaf = jnp.argmax(og).astype(jnp.int32)
            new = k
            feat = st["bs_feat"][leaf]
            thr = st["bs_thr"][leaf]
            lg, lh, lc = st["bs_lg"][leaf], st["bs_lh"][leaf], \
                st["bs_lc"][leaf]
            pg, ph, pc = st["leaf_g"][leaf], st["leaf_h"][leaf], \
                st["leaf_c"][leaf]
            rg, rh, rc = pg - lg, ph - lh, pc - lc
            begin = st["leaf_begin"][leaf]
            cnt = st["leaf_cnt"][leaf]

            if knock == "no_part":
                mat2, ws2 = st["mat"], st["ws"]
                nl = (cnt // 2).astype(jnp.int32)
            else:
                lut = jnp.zeros((1, 256), jnp.float32)
                mat2, ws2, nl1 = partition_segment(
                    st["mat"], st["ws"], begin, cnt, feat, thr,
                    jnp.int32(0), meta.missing[feat],
                    meta.default_bin[feat], meta.num_bins[feat],
                    jnp.int32(0), lut, blk=512, interpret=interpret)
                nl = nl1[0]
            nr = cnt - nl

            parent_hist = st["hist"][leaf]
            if knock == "no_hist":
                hist_small = parent_hist * 0.5
            else:
                left_small = nl <= nr
                sb = jnp.where(left_small, begin, begin + nl)
                sc = jnp.minimum(nl, nr)
                hist_small = seg_hist(mat2, sb, sc)
            hist_other = parent_hist - hist_small
            left_small = nl <= nr
            hist_left = jnp.where(left_small, hist_small, hist_other)
            hist_right = jnp.where(left_small, hist_other, hist_small)

            if knock == "no_scan":
                gl = st["bs_gain"][leaf] * 0.7 - 1e-3
                split_l = root_split._replace(gain=gl, left_g=lg * 0.5,
                                              left_h=lh * 0.5,
                                              left_c=lc * 0.5)
                split_r = root_split._replace(gain=gl, left_g=rg * 0.5,
                                              left_h=rh * 0.5,
                                              left_c=rc * 0.5)
            else:
                split_l = scan_leaf(hist_left, lg, lh, lc)
                split_r = scan_leaf(hist_right, rg, rh, rc)

            def set2(arr, va, vb):
                return arr.at[leaf].set(va).at[new].set(vb)

            st2 = dict(st)
            st2.update(
                k=k + 1, mat=mat2, ws=ws2,
                leaf_begin=set2(st["leaf_begin"], begin, begin + nl),
                leaf_cnt=set2(st["leaf_cnt"], nl, nr),
                hist=st["hist"].at[leaf].set(hist_left).at[new].set(
                    hist_right),
                leaf_g=set2(st["leaf_g"], lg, rg),
                leaf_h=set2(st["leaf_h"], lh, rh),
                leaf_c=set2(st["leaf_c"], lc, rc),
                bs_gain=set2(st["bs_gain"], split_l.gain, split_r.gain),
                bs_feat=set2(st["bs_feat"], split_l.feature,
                             split_r.feature),
                bs_thr=set2(st["bs_thr"], split_l.threshold,
                            split_r.threshold),
                bs_lg=set2(st["bs_lg"], split_l.left_g, split_r.left_g),
                bs_lh=set2(st["bs_lh"], split_l.left_h, split_r.left_h),
                bs_lc=set2(st["bs_lc"], split_l.left_c, split_r.left_c),
            )
            return st2

        st = jax.lax.while_loop(cond, body, state)
        return st["k"], st["bs_gain"].sum(), st["mat"][0, 0]

    import jax
    print(f"backend={jax.default_backend()} n={n}", flush=True)
    base = run("full", "none")
    for tag in ("no_part", "no_hist", "no_scan"):
        dt = run(tag, tag)
        print(f"   -> {tag} saves {(base-dt)*1e3:8.2f} ms/tree "
              f"({(base-dt)/254*1e6:7.1f} us/split)", flush=True)


if __name__ == "__main__":
    main()
