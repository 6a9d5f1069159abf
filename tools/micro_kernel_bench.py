"""In-program kernel microbenchmark (round-4 perf work).

Separates host-dispatch latency from the true in-program cost of each
kernel by chaining K calls inside ONE jitted lax.fori_loop and
dividing. Reports:

  - host dispatch floor (trivial jit)
  - histogram_segment: per-call cost vs segment size -> fixed overhead
    + streaming Mrow/s
  - partition_segment: same
  - best-split scan: per-call cost

Streaming rates are additionally normalized to the device's HBM peak
(lightgbm_tpu/utils/roofline.py: published per-chip GB/s + the
documented bytes-per-row model), so each number reads as a fraction of
physically-possible instead of a bare Mrow/s. CPU backends print
"n/a" — the host's effective bandwidth is not in the table.

Run: python tools/micro_kernel_bench.py [rows]
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")


def timeit(fn, *args, warmup=2, iters=5):
    from lightgbm_tpu.utils.sync import fetch_one
    for _ in range(warmup):
        r = fn(*args)
    fetch_one(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    fetch_one(r)
    return (time.perf_counter() - t0) / iters


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 500_000
    f = 28
    b = 256
    k_chain = 20

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import hist_pallas as hp
    from lightgbm_tpu.ops import partition_pallas as pp
    from lightgbm_tpu.utils.roofline import (device_peaks,
                                             hist_bytes_per_row,
                                             normalize,
                                             part_bytes_per_row)

    peaks = device_peaks()
    print(f"backend={jax.default_backend()} n={n} f={f}")
    print(f"device_kind={peaks['device_kind']} "
          f"hbm_peak={peaks['hbm_gbps']} GB/s "
          f"mxu_peak={peaks['mxu_tflops']} bf16 TFLOP/s")

    def roof(rows_per_s, bytes_per_row):
        rf = normalize(rows_per_s, bytes_per_row, peaks)
        return (f" {rf['achieved_gbps']:7.2f} GB/s"
                f" {100 * rf['hbm_frac']:5.1f}% HBM")

    rng = np.random.RandomState(0)
    binned = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32) + 0.1
    c = np.ones(n, np.float32)

    mat = hp.build_matrix(jnp.asarray(binned), 2048)
    mat = hp.pack_gh(mat, f, jnp.asarray(g), jnp.asarray(h),
                     jnp.asarray(c))
    mat = jax.block_until_ready(mat)
    ws = jnp.zeros_like(mat)

    # 1. dispatch floor
    @jax.jit
    def triv(x):
        return x + 1.0
    x0 = jnp.zeros((8,), jnp.float32)
    t = timeit(triv, x0, warmup=3, iters=10)
    print(f"dispatch floor (trivial jit): {t*1e3:8.3f} ms")

    # 2. chained histogram_segment (both nibble mask variants).
    # Round-4 lesson (PERF_RUN.log 03:59): single-chain timings came
    # out 0.001 ms/call at EVERY size (346 Grow/s, ~300x the VPU
    # ceiling) — non-physical, so per-call cost is now derived from the
    # DIFFERENCE of two chain lengths (subtracting whatever fixed
    # overhead or queueing artifact polluted the absolute number) and
    # a non-linear chain scaling prints a loud UNRELIABLE flag.
    k_short = max(2, k_chain // 4)

    def mk_chain_hist(variant, k):
        def chain_hist(m, count):
            def body(i, acc):
                # begin depends on the carry so XLA cannot hoist the
                # loop-invariant kernel call (i % 2 stays 8-aligned ->
                # same work per iteration, different operand)
                begin = (acc.astype(jnp.int32) % 2) * 8
                hh = hp.histogram_segment(m, begin, count, b, f,
                                          blk=2048, interpret=False,
                                          variant=variant)
                return acc + hh[0, 0, 0]
            return jax.lax.fori_loop(0, k, body, jnp.float32(0))
        return jax.jit(chain_hist)

    # "perbin" joins the comparison so the wide-dataset decision
    # (sliced nibble vs per-bin, ops/hist_pallas.py) is measured
    for variant in ("grouped", "perfeat", "perbin"):
        chain_long = mk_chain_hist(variant, k_chain)
        chain_short = mk_chain_hist(variant, k_short)
        print(f"histogram_segment[{variant}], {k_short}x-vs-{k_chain}x "
              "chained in one jit:")
        for count in (2048, 8192, 32768, 131072, min(n, 500_000)):
            t_l = timeit(chain_long, mat, jnp.int32(count))
            t_s = timeit(chain_short, mat, jnp.int32(count))
            per = (t_l - t_s) / (k_chain - k_short)
            # the round-4 pathology was IDENTICAL times at every chain
            # length; a near-1 ratio (or negative difference) means the
            # device did not actually run k-proportional work. In the
            # legitimate overhead-dominated regime (fixed dispatch ~10x
            # the per-call cost) the ratio still clears 1.1 and the
            # differenced estimate stays valid.
            flag = ""
            if t_l < 1.1 * t_s or per <= 0:
                flag = (f"  UNRELIABLE (t{k_short}={t_s*1e3:.2f}ms "
                        f"t{k_chain}={t_l*1e3:.2f}ms)")
            rate = count / max(per, 1e-9)
            print(f"  count={count:8d}: {per*1e3:8.3f} ms/call "
                  f"({rate/1e6:8.1f} Mrow/s)"
                  + roof(rate, hist_bytes_per_row(f)) + flag)

    # 3. chained partition_segment
    def mk_chain_part(fn, blk, k):
        def chain_part(m, w, count):
            lut = jnp.zeros((1, 256), jnp.float32)
            def body(i, carry):
                m2, w2, acc = carry
                # thr varies with the carry so no call can be folded
                thr = jnp.int32(120) + acc % 8
                m3, w3, nl = fn(
                    m2, w2, jnp.int32(0), count, jnp.int32(3), thr,
                    jnp.int32(1), jnp.int32(0), jnp.int32(0),
                    jnp.int32(b), jnp.int32(0), lut, blk=blk,
                    interpret=False)
                return m3, w3, acc + nl[0]
            _, _, acc = jax.lax.fori_loop(0, k, body,
                                          (m, w, jnp.int32(0)))
            return acc
        return jax.jit(chain_part, donate_argnums=(0, 1))

    from lightgbm_tpu.utils.sync import fetch_one

    def time_part(chain_j, count):
        m2 = jnp.array(mat)  # fresh donation each measure
        w2 = jnp.array(ws)
        r = chain_j(m2, w2, jnp.int32(count))
        fetch_one(r)
        m2 = jnp.array(mat)
        w2 = jnp.array(ws)
        fetch_one(w2)  # uploads must finish before the clock starts
        t0 = time.perf_counter()
        r = chain_j(m2, w2, jnp.int32(count))
        fetch_one(r)
        return time.perf_counter() - t0

    for tag, fn, blk in (("blk=512", pp.partition_segment, 512),):
        chain_long = mk_chain_part(fn, blk, k_chain)
        chain_short = mk_chain_part(fn, blk, k_short)
        print(f"partition_segment {tag} blk={blk}, "
              f"{k_short}x-vs-{k_chain}x chained in one jit:")
        for count in (2048, 8192, 32768, 131072, min(n, 500_000)):
            t_l = time_part(chain_long, count)
            t_s = time_part(chain_short, count)
            per = (t_l - t_s) / (k_chain - k_short)
            # the round-4 pathology was IDENTICAL times at every chain
            # length; a near-1 ratio (or negative difference) means the
            # device did not actually run k-proportional work. In the
            # legitimate overhead-dominated regime (fixed dispatch ~10x
            # the per-call cost) the ratio still clears 1.1 and the
            # differenced estimate stays valid.
            flag = ""
            if t_l < 1.1 * t_s or per <= 0:
                flag = (f"  UNRELIABLE (t{k_short}={t_s*1e3:.2f}ms "
                        f"t{k_chain}={t_l*1e3:.2f}ms)")
            rate = count / max(per, 1e-9)
            print(f"  count={count:8d}: {per*1e3:8.3f} ms/call "
                  f"({rate/1e6:8.1f} Mrow/s)"
                  + roof(rate, part_bytes_per_row(f)) + flag)

    # 4. chained best-split scan
    from lightgbm_tpu.learner.serial import (feature_meta_from_dataset,
                                             split_params_from_config)
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.ops.split import best_split

    cfg = Config.from_params({"objective": "binary", "num_leaves": 255,
                              "max_bin": 255, "verbosity": -1})
    Xs = rng.randn(4096, f).astype(np.float32)
    ds = Dataset.from_numpy(Xs, cfg, label=np.zeros(4096, np.float32))
    meta = feature_meta_from_dataset(ds, cfg)
    params = split_params_from_config(cfg)

    hist = jnp.asarray(rng.rand(f, b, 3).astype(np.float32))
    inf = jnp.float32(np.inf)
    fm = jnp.ones((f,), bool)

    def chain_scan(hh):
        def body(i, acc):
            res = best_split(hh + acc * 1e-9, jnp.float32(100.0),
                             jnp.float32(200.0), jnp.float32(4096.0),
                             meta, params, -inf, inf, fm)
            return acc + res.gain
        return jax.lax.fori_loop(0, k_chain, body, jnp.float32(0))
    chain_scan_j = jax.jit(chain_scan)
    t = timeit(chain_scan_j, hist)
    print(f"best_split scan (XLA) chained: {t/k_chain*1e3:8.3f} ms/call")

    # 5. fused Pallas scan kernel, same chaining
    from lightgbm_tpu.ops.split_scan_pallas import \
        per_feature_numerical_pallas
    pk = params._replace(use_scan_kernel=True)

    def chain_scan_pl(hh):
        def body(i, acc):
            pf = per_feature_numerical_pallas(
                hh + acc * 1e-9, jnp.float32(100.0), jnp.float32(200.0),
                jnp.float32(4096.0), meta, pk, -inf, inf, fm)
            return acc + pf.score.max()
        return jax.lax.fori_loop(0, k_chain, body, jnp.float32(0))
    chain_scan_pl_j = jax.jit(chain_scan_pl)
    t = timeit(chain_scan_pl_j, hist)
    print(f"best_split scan (Pallas) chained: {t/k_chain*1e3:8.3f} ms/call")

    # 6. both-children vmapped Pallas scan (the grow-loop shape)
    def chain_scan_pl2(hh2):
        def body(i, acc):
            pf = jax.vmap(lambda hh: per_feature_numerical_pallas(
                hh + acc * 1e-9, jnp.float32(100.0), jnp.float32(200.0),
                jnp.float32(4096.0), meta, pk, -inf, inf, fm))(hh2)
            return acc + pf.score.max()
        return jax.lax.fori_loop(0, k_chain, body, jnp.float32(0))
    chain_scan_pl2_j = jax.jit(chain_scan_pl2)
    hist2 = jnp.stack([hist, hist * 0.5])
    t = timeit(chain_scan_pl2_j, hist2)
    print(f"both-children scan (Pallas vmap) chained: "
          f"{t/k_chain*1e3:8.3f} ms/call-pair")

    # 7. fused split-step megakernel (ops/split_step_pallas.py): the
    # grow while-loop IS the chain (L-1 megakernel dispatches in one
    # compiled program); per-split cost is DIFFERENCED across two
    # leaf counts so the root histogram + fixed program overhead
    # cancel, and the stream rate reads against the roofline with the
    # fused bytes/row model (partition + histogram ride ONE pass)
    import os as _os

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset as _DS
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    from lightgbm_tpu.utils.roofline import fused_leaf_bytes_per_row

    n_f = min(n, 200_000)
    Xf = rng.randn(n_f, f).astype(np.float32)
    yf = (Xf[:, 0] > 0).astype(np.float32)
    gradf = jnp.asarray(yf - 0.5)
    hessf = jnp.full((n_f,), 0.25, jnp.float32)

    def tree_time(leaves, mode):
        _os.environ["LGBM_TPU_FUSED_SPLIT_KERNEL"] = mode
        try:
            cfgf = Config.from_params({
                "objective": "binary", "num_leaves": leaves,
                "min_data_in_leaf": 20, "verbosity": -1})
            lrn = SerialTreeLearner(_DS.from_numpy(Xf, cfgf, label=yf),
                                    cfgf)
            return timeit(lambda: lrn.train(gradf, hessf).tree
                          .num_leaves, warmup=1, iters=3)
        finally:
            _os.environ.pop("LGBM_TPU_FUSED_SPLIT_KERNEL", None)

    for tag, mode in (("fused megakernel", "1"),
                      ("per-phase foil ", "0")):
        t_hi = tree_time(63, mode)
        t_lo = tree_time(31, mode)
        per = (t_hi - t_lo) / 32
        flag = "" if per > 0 else "  UNRELIABLE"
        rate = n_f / max(per, 1e-9)
        print(f"fused_split_kernel [{tag}] 31-vs-63-leaf trees: "
              f"{per*1e3:8.3f} ms/split ({rate/1e6:8.1f} Mrow/s)"
              + roof(rate, fused_leaf_bytes_per_row(f)) + flag)


if __name__ == "__main__":
    main()
