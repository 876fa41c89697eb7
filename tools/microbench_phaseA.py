"""Microbenchmark: phase A of the fused split pass.

Two measurements:

1. Stage-by-stage ISOLATED compute replica (the round-5 method): the exact
   phase-A computation on a VMEM-resident [CHUNK, W] u8 tile, one stage per
   variant; deltas attribute cost without the constant-folding traps of
   in-kernel knockouts (a zeroed input folds every downstream op away).
   This measures the floor — round 5 measured ~0.26 ns/row.

2. IN-KERNEL (``--in-kernel``): the REAL fused kernel
   (partition_hist_pallas, the ``c4096`` bucket) on one large window.
   First with phases B/C, flushes and the histogram knocked out
   (``dbg_skip="phaseB,phaseC,flush,hist"``): stream + convert + extract +
   route + prefix + the banked totals DMA under the software pipeline; the
   gap to the isolated replica is per-chunk scheduling.  Then the whole
   kernel less the histogram (``dbg_skip="hist"``), whose outputs are
   right.  ``--route`` says where the rows go: ``left`` (every row left:
   the right block and its copy-back are empty), ``right`` (every row
   right: every row is also copied back) or ``half``.  Whole-kernel
   ``right`` minus ``left`` is the copy-back's cost a right row, which no
   knockout can isolate.  With ``--hist`` the whole kernel runs once more
   with the histogram ON, its ``hist_left`` scalar naming the block that
   holds the rows (the left block, or the right one at ``--route right``),
   and the difference is the smaller child's histogram, ns a histogrammed
   row; the root pass (``histogram_pallas_rows`` over the same rows) is
   timed beside it.  ``--features`` sets the column count (28; 67 and 9 are
   the other cells').  Knockout deltas fold constants; trust whole-kernel
   A/B between two commits.  The static reading of the same phases is
   ``tools/kernel_bundles.py``.
"""
import contextlib
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.core.partition import CHUNK, T      # the kernel's own
from tools.profile_tree import aggregate_xplane

W = 128
LANE = 128
REPS = 16
GRID = 32
NSUB = CHUNK // T
NPK = CHUNK // LANE


@contextlib.contextmanager
def _trace_dir():
    """A profiler directory of this run's own (under TMPDIR), removed after
    its reading: two commits timed in one call never see each other's."""
    path = tempfile.mkdtemp(prefix="lgbm_tpu_pha_")
    try:
        yield path
    finally:
        shutil.rmtree(path, ignore_errors=True)


def _consume(o_ref, arrs):
    """Cheap LIVE consumption: add a tiny slice-sum of each array."""
    for a in arrs:
        af = a.astype(jnp.float32) if a.dtype != jnp.float32 else a
        r = min(8, af.shape[0])
        o_ref[0:r, 0:1] += jnp.sum(af[0:r, :], axis=1, keepdims=True)


def make_kernel(stage):
    def kernel(x_ref, o_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _z():
            o_ref[...] = jnp.zeros_like(o_ref)

        iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        for r in range(REPS):
            gcol = 3 + ((i + r) & 3)          # defeat CSE across reps
            live = []
            ti = x_ref[...].astype(jnp.int32)
            ti_bf = ti.astype(jnp.bfloat16)
            live += [ti_bf[:8]]
            if stage >= 1:                     # extraction dot + packed col
                colsel = (iota_w == gcol).astype(jnp.bfloat16)
                colsel2 = jnp.zeros((1, W), jnp.bfloat16)
                wmat = jnp.concatenate([colsel, colsel2], axis=0)
                extT = jax.lax.dot_general(
                    wmat, ti_bf, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                extTi = extT.astype(jnp.int32)
                col_p = extTi[0:1, :].reshape(NPK, LANE)
                live += [col_p]
            if stage >= 2:                     # routing + window masks
                thr = 31 + (r & 1)
                gl = (col_p <= thr).astype(jnp.int32)
                gl = jnp.where(col_p == 63, 1, gl)   # missing-ish branch
                pos = (jax.lax.broadcasted_iota(jnp.int32, (NPK, 1), 0)
                       * LANE
                       + jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1))
                inw = ((pos >= 100).astype(jnp.int32)
                       * (pos < CHUNK - 3).astype(jnp.int32))
                selL = gl * inw
                selR = (1 - gl) * inw
                live += [selL, selR]
            if stage >= 3:                     # S concat + prefix + totals
                ltri = (jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
                        <= jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                        ).astype(jnp.bfloat16)
                S = jnp.concatenate([selL, selR], axis=0).astype(jnp.bfloat16)
                pfxU = jax.lax.dot_general(
                    S, ltri, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                tot_col = pfxU[:, T - 1:T]
                iiB = jax.lax.broadcasted_iota(jnp.int32, (2 * NSUB, 1), 0)
                jjB = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * NSUB), 1)
                triB = ((iiB >= jjB).astype(jnp.int32)
                        * ((iiB < NSUB) == (jjB < NSUB)).astype(jnp.int32)
                        ).astype(jnp.bfloat16)
                incl_col = jax.lax.dot_general(
                    triB, tot_col.astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                live += [pfxU[:8], incl_col]
            _consume(o_ref, live)

    return kernel


def _bench(name, stage, x):
    fn = jax.jit(pl.pallas_call(
        make_kernel(stage),
        grid=(GRID,),
        in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
    ))
    r = fn(x)
    r.block_until_ready()
    with _trace_dir() as trace_dir:
        with jax.profiler.trace(trace_dir):
            r = fn(x)
            r.block_until_ready()
            float(jax.device_get(r[0, 0]))
        rows = aggregate_xplane(trace_dir, top=40)
    ms = max(rows, key=lambda q: q[1])[1]
    print("%-30s %9.3f ms   %.3f ns/row"
          % (name, ms, ms * 1e6 / (GRID * REPS * CHUNK)))


# split threshold on a column of uniform bins: every row left (the right
# block and its copy-back are empty), every row right, or half and half
ROUTES = {"left": lambda num_bins: num_bins,
          "right": lambda num_bins: -1,
          "half": lambda num_bins: num_bins // 2 - 1}


def _device_ms(fn, reps):
    """Device time of one call of ``fn`` (the trace's largest op, which is
    the kernel), after a warm-up call: (ms, the last call's result)."""
    out = fn()
    jax.block_until_ready(out)
    with _trace_dir() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(reps):
                out = fn()
                jax.block_until_ready(out)
            jax.device_get(jax.tree_util.tree_leaves(out)[-1])
        best = max(aggregate_xplane(trace_dir, top=40),
                   key=lambda q: q[1])[1] / reps
    return best, out


def bench_in_kernel(route="left", n_rows=2_097_152, num_bins=256, reps=3,
                    features=28, hist=False):
    """Whole-kernel timing of the real pipelined kernel on one window of
    ``n_rows`` rows at ``features`` columns (W = 128 up to 108 of them,
    values behind the bin bytes as ``build_tree_partitioned`` lays them
    out): phase A alone by knockout, then everything but the histogram and,
    with ``hist``, everything.  Prints ns a window row."""
    from lightgbm_tpu.core.histogram import histogram_pallas_rows
    from lightgbm_tpu.core.partition import fold_hist, partition_hist_pallas

    f = features
    voff = -(-f // 4) * 4
    WK = -(-(voff + 20) // 128) * 128
    n_pad = ((n_rows // CHUNK) + 2) * CHUNK     # the builder's spare chunk
    rng = np.random.RandomState(0)
    rows = np.zeros((n_pad, WK), np.uint8)
    rows[:, :f] = rng.randint(0, num_bins, size=(n_pad, f))
    # finite gradients: the histogram's sums are checked below
    rows[:, voff:voff + 8] = rng.uniform(
        -1.0, 1.0, size=(n_pad, 2)).astype(np.float32).view(np.uint8)
    scal = np.zeros(12 + num_bins // 32, np.int32)
    # hist_left (scal[9]) names the block that holds the rows
    scal[:12] = [0, n_rows, 2, ROUTES[route](num_bins), 1, 0, num_bins, 0, 0,
                 0 if route == "right" else 1, 0, 1]
    r = jnp.asarray(rows)
    s = jnp.asarray(scal)

    def run(skip):
        ms, out = _device_ms(lambda: partition_hist_pallas(
            r, s, num_features=f, num_bins=num_bins, voff=voff,
            dbg_skip=skip), reps)
        return ms, int(out[2][0, 0]), out[1]

    ms_a, _, _ = run("phaseB,phaseC,flush,hist")
    print("route=%s in-kernel phase A (pipelined, %.1fM-row window, F=%d, "
          "%d bins): %.3f ms = %.3f ns/row"
          % (route, n_rows / 1e6, f, num_bins, ms_a, ms_a * 1e6 / n_rows))
    ms_full, nl, _ = run("hist")
    print("route=%s whole kernel less the histogram, %d rows left of %d: "
          "%.3f ms = %.3f ns/row"
          % (route, nl, n_rows, ms_full, ms_full * 1e6 / n_rows))
    if hist:
        ms_h, nl, raw = run("")
        n_hist = n_rows - nl if route == "right" else nl
        print("route=%s whole kernel, %d rows histogrammed: %.3f ms = %.3f "
              "ns/row; the histogram: %.3f ns a histogrammed row"
              % (route, n_hist, ms_h, ms_h * 1e6 / n_rows,
                 (ms_h - ms_full) * 1e6 / n_hist))
        ms_r, root = _device_ms(lambda: histogram_pallas_rows(
            r, num_bins, jnp.int32(0), jnp.int32(n_rows), num_features=f,
            voff=voff), reps)
        print("root pass histogram_pallas_rows, %d rows: %.3f ms = %.3f "
              "ns/row" % (n_rows, ms_r, ms_r * 1e6 / n_rows))
        if n_hist == n_rows:
            # the same rows either way (the kernel only moves them)
            got = np.asarray(fold_hist(raw, f, num_bins))
            err = float(np.max(np.abs(got - np.asarray(root))))
            print("split kernel's histogram against the root pass's: max "
                  "|diff| %.3g of max |sum| %.3g"
                  % (err, float(np.max(np.abs(np.asarray(root))))))
    return ms_full * 1e6 / n_rows


def main():
    import argparse
    ap = argparse.ArgumentParser(
        description="phase-A microbenchmark: isolated compute replica by "
                    "default; with --in-kernel the REAL fused kernel, phase "
                    "A by knockout and whole less the histogram")
    ap.add_argument("--in-kernel", action="store_true",
                    help="time the REAL fused kernel on one large window")
    ap.add_argument("--route", nargs="+", choices=sorted(ROUTES),
                    default=["left"],
                    help="where the window's rows go; several routes run "
                         "in turn, and right minus left is the copy-back")
    ap.add_argument("--hist", action="store_true",
                    help="with --in-kernel: also the whole kernel with the "
                         "histogram on (ns a histogrammed row by "
                         "difference) and the root pass over the same rows")
    ap.add_argument("--features", type=int, default=28,
                    help="bin columns of the table (28; 67 and 9 are the "
                         "other cells')")
    args = ap.parse_args()
    if args.in_kernel:
        ns = {route: bench_in_kernel(route, features=args.features,
                                     hist=args.hist)
              for route in args.route}
        if "left" in ns and "right" in ns:
            print("copy-back (whole kernel, right minus left): %.3f ns a "
                  "right row" % (ns["right"] - ns["left"]))
        return
    x = jnp.asarray(np.random.RandomState(0).randint(0, 64, (CHUNK, W)),
                    jnp.uint8)
    print("phase-A stage attribution ([%d, %d] u8 chunk)" % (CHUNK, W))
    _bench("0: converts", 0, x)
    _bench("1: + extract/reshape", 1, x)
    _bench("2: + route/sel", 2, x)
    _bench("3: + S/prefix/totals", 3, x)
    print("run with --in-kernel [--route left right half] for the real "
          "kernel")


if __name__ == "__main__":
    main()
