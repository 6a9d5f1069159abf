"""On-chip probe: can Mosaic build one-hot masks at int8 throughput?

The nibble histogram kernel is VPU-mask-bound (~120 Mrow/s modeled at
f32: each vector op costs ~rows/8 cycles regardless of lane count).
Mosaic's int8 tile is (32, 128) — IF u8/i8 compares+selects process 4x
the sublanes per cycle, the mask ceiling rises ~4x. This probe measures
three block-shaped candidates COMPILED on the real chip (no full
kernel rewrite):

  f32   — today's route: i32 compare, f32 select, bf16 cast, bf16 MXU
  i8    — u8 compare/select, i8->i32->f32->bf16 convert, bf16 MXU
          (the convert cost is part of the route and of the answer)
  i8mm  — u8 compare/select, s8 x s8 -> s32 MXU directly

Every variant consumes the FULL [WIN, LANES] mask through a matmul
(the real kernel's consumer), and a per-call SMEM salt perturbs the
compare pattern so XLA cannot hoist the call out of the timing chain.
Failures print and skip — an unsupported lowering is a RESULT, not an
error.

Run on the chip (one process): python tools/probe_i8_masks.py
Off-chip pre-check: python tools/probe_i8_masks.py --lower-only
  runs only the Mosaic TPU lowering pass for each candidate (works on
  any host) — an UNSUPPORTED there answers the question without
  spending chip time; a LOWERS-OK still needs the on-chip timing.
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

WIN = 2048
C = 128
LANES = 120
K_CHAIN = 50
REPS = 20        # mask builds per kernel invocation


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from lightgbm_tpu.utils.device import on_tpu
    from lightgbm_tpu.utils.sync import fetch_one

    lower_only = "--lower-only" in sys.argv
    if not lower_only and not on_tpu():
        print(f"needs the real TPU (backend={jax.default_backend()}); "
              "use --lower-only for the off-chip lowering pre-check")
        return 2

    rng = np.random.RandomState(0)
    blk = jnp.asarray(rng.randint(0, 255, (WIN, C)), jnp.uint8)

    def mk(body):
        def kern(salt_ref, in_ref, out_ref):
            salt = salt_ref[0]
            acc = None
            for r in range(REPS):
                v = body(in_ref, salt, r)        # [8, LANES] f32
                acc = v if acc is None else acc + v
            out_ref[...] = acc

        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
        )

    import jax.lax as lax

    def consume_bf16(mask_bf):
        ones = jnp.ones((WIN, 8), jnp.bfloat16)
        return lax.dot_general(ones, mask_bf, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def body_f32(in_ref, salt, r):
        m = in_ref[...].astype(jnp.int32)             # [WIN, C]
        pat = (lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
               + salt) % 8
        col = m[:, r % C:r % C + 1]
        lo = col - (col // 8) * 8
        mask = jnp.where(lo == pat, jnp.float32(1),
                         jnp.float32(0)).astype(jnp.bfloat16)
        return consume_bf16(mask)

    def body_i8(in_ref, salt, r):
        m = in_ref[...]                               # [WIN, C] u8
        pat = ((lax.broadcasted_iota(jnp.uint8, (1, LANES), 1)
                + salt.astype(jnp.uint8)) & jnp.uint8(7))
        col = m[:, r % C:r % C + 1]
        lo = col & jnp.uint8(7)
        mask = jnp.where(lo == pat, jnp.uint8(1), jnp.uint8(0))
        mask_bf = mask.astype(jnp.int32).astype(
            jnp.float32).astype(jnp.bfloat16)
        return consume_bf16(mask_bf)

    def body_i8mm(in_ref, salt, r):
        m = in_ref[...]
        pat = ((lax.broadcasted_iota(jnp.uint8, (1, LANES), 1)
                + salt.astype(jnp.uint8)) & jnp.uint8(7))
        col = m[:, r % C:r % C + 1]
        lo = col & jnp.uint8(7)
        mask = jnp.where(lo == pat, jnp.int8(1), jnp.int8(0))
        ones = jnp.ones((WIN, 8), jnp.int8)
        res = lax.dot_general(ones, mask, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
        return res.astype(jnp.float32)                # [8, LANES]

    for name, body in (("f32", body_f32), ("i8", body_i8),
                       ("i8mm", body_i8mm)):
        try:
            call = mk(body)
            if lower_only:
                # one probe compile per variant, by design
                # graftlint: allow[GL301]
                jax.jit(lambda x, call=call: call(
                    jnp.stack([jnp.int32(3)]), x)).trace(blk).lower(
                        lowering_platforms=("tpu",))
                print(f"{name:5s}: LOWERS OK (timing still needs "
                      "the chip)")
                continue

            @jax.jit
            def chain(x, call=call):
                def step(i, acc):
                    # the salt depends on the carry: the call cannot
                    # be hoisted out of the loop
                    salt = jnp.int32(acc) % 8 + i * 0
                    out = call(jnp.stack([salt]), x)
                    return acc + out[0, 0]
                return jax.lax.fori_loop(0, K_CHAIN, step,
                                         jnp.float32(0))

            fetch_one(chain(blk))         # compile + first run
            t0 = time.perf_counter()
            fetch_one(chain(blk))
            dt = (time.perf_counter() - t0) / K_CHAIN / REPS
            rows_s = WIN / dt
            print(f"{name:5s}: {dt*1e6:8.2f} us/mask-build+consume "
                  f"({rows_s/1e6:8.1f} Mrow/s per {LANES}-lane mask)")
        except Exception as e:  # noqa: BLE001 — unsupported IS a result
            print(f"{name:5s}: UNSUPPORTED/FAILED: "
                  f"{type(e).__name__}: {str(e)[:200]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
