"""Reliable (chained fori_loop) benchmarks of the primitives the windowed
tree-build redesign depends on: row gather/scatter, argsort, cumsum, and
histogram kernel variants (bf16, 2-features-per-lane-group packing).

Usage: python tools/bench_primitives.py [--rows N] [--reps R]
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
NT = 1024


def kern_base(bins_ref, vals_ref, out_ref, *, nf, nb, dt):
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


def kern_pack2(bins_ref, vals_ref, out_ref, *, nf, nb, dt):
    """Two 64-bin features share one 128-lane one-hot (OR of two compares),
    halving the MXU streams — the TPU version of the reference GPU's
    4-features-per-DWORD packing (gpu_tree_learner.cpp:317-344)."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
    b = bins_ref[...].astype(jnp.int32)
    v = vals_ref[...].astype(dt)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    for p in range(nf // 2):
        c0 = b[:, 2 * p:2 * p + 1]
        c1 = b[:, 2 * p + 1:2 * p + 2] + 64
        oh = ((c0 == iota) | (c1 == iota)).astype(dt)
        acc = jax.lax.dot_general(v, oh, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        out_ref[p, :, :] += acc


@functools.partial(jax.jit, static_argnames=("kern", "dt", "fo"))
def hist(bins, vals, kern, dt, fo):
    n, f = bins.shape
    k = functools.partial(kern, nf=f, nb=B, dt=dt)
    return pl.pallas_call(
        k, grid=(n // NT,),
        in_specs=[pl.BlockSpec((NT, f), lambda i: (i, 0)),
                  pl.BlockSpec((NT, 2), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((fo, 2, 128), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((fo, 2, 128), jnp.float32),
    )(bins, vals)


def fetch(x):
    return float(jax.device_get(jnp.ravel(x)[0]))


def main():
    ap = argparse.ArgumentParser(
        description="chained-fori_loop primitive benchmarks (gather/"
                    "scatter/sort/cumsum + histogram kernel variants)")
    ap.add_argument("--rows", type=int, default=2_097_152)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    n, reps = args.rows, args.reps

    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, 63, size=(n, F), dtype=np.uint8))
    vals = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    leaf = jnp.asarray(rng.randint(0, 64, size=(n,), dtype=np.int32))

    f_lat = jax.jit(lambda x: x + 1.0)
    fetch(f_lat(jnp.float32(0)))
    t0 = time.perf_counter()
    for _ in range(5):
        fetch(f_lat(jnp.float32(0)))
    lat = (time.perf_counter() - t0) / 5
    print(f"dispatch latency ~{lat*1e3:.1f} ms", flush=True)

    def chain(step, init):
        @jax.jit
        def run(state):
            return jax.lax.fori_loop(0, reps, lambda i, s: step(s), state)
        out = run(init)
        fetch(jax.tree_util.tree_leaves(out)[0])
        t0 = time.perf_counter()
        out = run(init)
        fetch(jax.tree_util.tree_leaves(out)[0])
        return (time.perf_counter() - t0 - lat) / reps

    def report(name, secs):
        print(f"{name:58s} {secs*1e3:8.2f} ms {n/secs/1e6:9.1f} Mrows/s",
              flush=True)

    def guard(name, fn):
        try:
            report(name, fn())
        except Exception as e:  # noqa: BLE001
            print(f"{name:58s} FAILED: {str(e)[:140]}", flush=True)

    # ---- data movement ----
    guard("take rows bins[perm] [N,28]u8",
          lambda: chain(lambda s: (s[0][s[1]], s[1]), (bins, perm)))
    guard("take vals[perm] [N,2]f32",
          lambda: chain(lambda s: (s[0][s[1]] * 1.0000001, s[1]),
                        (vals, perm)))
    guard("take idx perm[perm] [N]i32",
          lambda: chain(lambda s: (s[0][s[1]], s[1]), (perm, perm)))
    guard("scatter rows zeros.at[perm].set(bins)",
          lambda: chain(
              lambda s: (jnp.zeros_like(s[0]).at[s[1]].set(s[0]) | s[0][0, 0],
                         s[1]), (bins, perm)))
    guard("scatter idx zeros.at[perm].set(iota)",
          lambda: chain(
              lambda s: (jnp.zeros_like(s[0]).at[s[0]].set(s[1])
                         + s[0][0] * 0, s[1]),
              (perm, jnp.arange(n, dtype=jnp.int32))))
    guard("argsort leaf [N]i32",
          lambda: chain(lambda s: (jnp.argsort(s[0] ^ s[1]), s[1] ^ 1),
                        (leaf, jnp.int32(0))))
    guard("sort u64 keys [N]",
          lambda: chain(lambda s: (jnp.sort(s[0]) + s[0][0] % 2, s[1]),
                        (leaf.astype(jnp.uint32), jnp.int32(0))))
    guard("cumsum i32 [N]",
          lambda: chain(lambda s: jnp.cumsum(s % 3, dtype=jnp.int32),
                        jnp.ones((n,), jnp.int32)))

    # ---- histogram kernel variants ----
    def bench_hist(name, kern, dt, fo):
        def step(s):
            v, acc = s
            h = hist(bins, v, kern, dt, fo)
            return v + h[0, 0, 0] * 1e-30, acc + h[0, 0, 0]
        guard(name, lambda: chain(step, (vals, jnp.float32(0))))

    bench_hist("hist f32 per-feature (baseline)", kern_base, jnp.float32, F)
    bench_hist("hist bf16 per-feature", kern_base, jnp.bfloat16, F)
    bench_hist("hist f32 packed-2 (64-bin pairs)", kern_pack2, jnp.float32,
               F // 2)
    bench_hist("hist bf16 packed-2 (64-bin pairs)", kern_pack2, jnp.bfloat16,
               F // 2)


if __name__ == "__main__":
    main()
