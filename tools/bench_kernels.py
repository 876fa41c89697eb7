"""Microbenchmarks for histogram-kernel design decisions on the real TPU.

Every measurement here chains `reps` dependent kernel executions inside ONE
jitted fori_loop (the device cannot skip or overlap them) and fetches a single
scalar at the end, so per-dispatch host overhead is paid once; the dispatch
round-trip latency is measured separately and subtracted.

Usage: python tools/bench_kernels.py [--rows N] [--reps R]
"""
import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

F = 28
B = 128


def fetch_scalar(x):
    return float(jax.device_get(jnp.ravel(x)[0]))


def measure_latency():
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.float32(0.0)
    fetch_scalar(f(x))
    t0 = time.perf_counter()
    for _ in range(10):
        fetch_scalar(f(x))
    return (time.perf_counter() - t0) / 10


def _kern_feat(bins_ref, vals_ref, out_ref, *, nf, nb, dt):
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
    b = bins_ref[...].astype(jnp.int32)
    v = vals_ref[...].astype(dt)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    for f in range(nf):
        oh = (b[:, f:f + 1] == iota).astype(dt)
        acc = jax.lax.dot_general(v, oh, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        out_ref[f, :, :] += acc


@functools.partial(jax.jit, static_argnames=("tile", "dt", "nch"))
def pallas_feat(bins, vals, tile=2048, dt=jnp.float32, nch=2):
    n, f = bins.shape
    kern = functools.partial(_kern_feat, nf=f, nb=B, dt=dt)
    return pl.pallas_call(
        kern, grid=(n // tile,),
        in_specs=[pl.BlockSpec((tile, f), lambda i: (i, 0)),
                  pl.BlockSpec((tile, nch), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((f, nch, B), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, nch, B), jnp.float32),
    )(bins, vals)


def main():
    ap = argparse.ArgumentParser(
        description="histogram-kernel + repartition-primitive "
                    "microbenchmarks (chained fori_loop timing)")
    ap.add_argument("--rows", type=int, default=4_194_304)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()
    n, reps = args.rows, args.reps

    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, 63, size=(n, F), dtype=np.uint8))
    vals = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    leaf = jnp.asarray(rng.randint(0, 64, size=(n,), dtype=np.int32))

    lat = measure_latency()
    print(f"dispatch round-trip latency: {lat*1e3:.2f} ms")

    def timeit_chain(step, init):
        @jax.jit
        def run(state):
            return jax.lax.fori_loop(0, reps, lambda i, s: step(s), state)

        out = run(init)
        fetch_scalar(jax.tree_util.tree_leaves(out)[0])  # warmup + compile
        t0 = time.perf_counter()
        out = run(init)
        fetch_scalar(jax.tree_util.tree_leaves(out)[0])
        return (time.perf_counter() - t0 - lat) / reps

    def report(name, secs, work_rows=n):
        print(f"{name:55s} {secs*1e3:9.2f} ms   "
              f"{work_rows/secs/1e6:10.1f} Mrows/s")

    # ---------------- calibration: known-cost ops ----------------
    big = jnp.zeros((4096, 4096), dtype=jnp.bfloat16)
    t = timeit_chain(lambda a: (a @ a) * 1e-8, big)
    print(f"calib dense matmul 4k^3 bf16: {t*1e3:.3f} ms = "
          f"{2*4096**3/t/1e12:.1f} TFLOP/s (peak v5e ~197)")
    t = timeit_chain(lambda b: b + jnp.uint8(1), bins)
    print(f"calib elementwise u8 [N,F] (112MB r+w): {t*1e3:.3f} ms = "
          f"{2*n*F/t/1e9:.0f} GB/s (peak v5e ~819)")

    # ---------------- histogram kernels ----------------
    def hist_step(maker):
        def step(state):
            v, acc = state
            h = maker(v)
            # dependency: fold a scalar of h back into v (cheap vs the
            # kernel)
            return v + h[0, 0, 0] * 1e-30, acc + h[0, 0, 0]
        return step

    def bench_hist(name, maker, v0):
        try:
            t = timeit_chain(hist_step(maker), (v0, jnp.float32(0.0)))
            report(name, t)
        except Exception as e:  # noqa: BLE001
            print(f"{name:55s} FAILED: {str(e)[:120]}")

    bench_hist("pallas per-feature f32 2ch tile=2048",
               lambda v: pallas_feat(bins, v, 2048, jnp.float32, 2), vals)
    bench_hist("pallas per-feature f32 2ch tile=4096",
               lambda v: pallas_feat(bins, v, 4096, jnp.float32, 2), vals)
    bench_hist("pallas per-feature bf16 2ch tile=2048",
               lambda v: pallas_feat(bins, v.astype(jnp.bfloat16), 2048,
                                     jnp.bfloat16, 2), vals)

    vals8 = jnp.tile(vals, (1, 4))
    vals32 = jnp.tile(vals, (1, 16))
    vals128 = jnp.tile(vals, (1, 64))
    bench_hist("pallas per-feature f32 8ch tile=2048",
               lambda v: pallas_feat(bins, v, 2048, jnp.float32, 8), vals8)
    bench_hist("pallas per-feature f32 32ch tile=2048",
               lambda v: pallas_feat(bins, v, 2048, jnp.float32, 32), vals32)
    bench_hist("pallas per-feature f32 128ch tile=2048",
               lambda v: pallas_feat(bins, v, 2048, jnp.float32, 128),
               vals128)
    bench_hist("pallas per-feature bf16 128ch tile=2048",
               lambda v: pallas_feat(bins, v.astype(jnp.bfloat16), 2048,
                                     jnp.bfloat16, 128), vals128)

    # ---------------- repartition primitives ----------------
    def bench_plain(name, step, init, work_rows=n):
        try:
            t = timeit_chain(step, init)
            report(name, t, work_rows)
        except Exception as e:  # noqa: BLE001
            print(f"{name:55s} FAILED: {str(e)[:120]}")

    bench_plain("argsort [N] i32",
                lambda s: (jnp.argsort(s[0] + s[1]), s[1]),
                (leaf, jnp.int32(0)))
    perm = jnp.argsort(leaf)
    bench_plain("row gather bins[perm] [N,F] u8",
                lambda s: (bins[s[1]] | s[0], s[1]), (bins, perm))
    bench_plain("gather vals[perm] [N,2] f32",
                lambda s: (vals[s[1]] + s[0] * 1e-30, s[1]),
                (vals, perm))
    bench_plain("cumsum [N] f32",
                lambda s: jnp.cumsum(s) * 1e-8, vals[:, 0])


if __name__ == "__main__":
    main()
