"""Wide-F histogram benchmark: compile time + measured ns/row at Bosch
shape (F=968), factored vs classic layouts.

Round 5 could only offer a DERIVED ~2.5x factored-vs-classic claim at this
width because both unrolled kernel layouts hit multi-10-minute XLA/Mosaic
compiles; the round-6 grid-over-groups layout is the fix, and this tool
turns the claim into a measured number (PERF.md "Wide-F").

Per configuration it reports:
- compile_s: wall-clock of the first (compiling) call
- ns_row: device time per (row) from the xplane trace of warm calls
- ns_row_feature: the same per (row, feature) — the cross-width comparable

Configs: F=968 at B=64 (factored; the 63-bin Bosch setting) and the same
shape FORCED onto the classic packed-tile path, plus F=968 at B=256 where
the 4 MiB accumulator gate makes classic the only path.

Usage: python tools/bench_widef.py [--rows 262144] [--json]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=262_144)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import lightgbm_tpu.core.histogram as H
    from tools.profile_tree import aggregate_xplane

    F = 968
    n = args.rows
    rng = np.random.RandomState(0)
    results = {}

    def measure(tag, b, force_classic):
        voff = -(-F // 4) * 4
        W = -(-(voff + 8) // 128) * 128
        rows = np.zeros((n, W), np.uint8)
        rows[:, :F] = rng.randint(0, b, size=(n, F))
        rows[:, voff:voff + 8] = rng.randint(0, 255, size=(n, 8))
        r = jnp.asarray(rows)
        orig = H._use_factored
        if force_classic:
            H._use_factored = lambda f, bb, quantized=False: False
        H.histogram_pallas_rows.clear_cache()
        try:
            t0 = time.perf_counter()
            out = H.histogram_pallas_rows(
                r, b, jnp.int32(0), jnp.int32(n), num_features=F, voff=voff,
                row_tile=2048)
            jax.block_until_ready(out)
            compile_s = time.perf_counter() - t0
            reps = 3
            trace_dir = "/tmp/lgbm_tpu_widef/" + tag
            with jax.profiler.trace(trace_dir):
                for _ in range(reps):
                    out = H.histogram_pallas_rows(
                        r, b, jnp.int32(0), jnp.int32(n), num_features=F,
                        voff=voff, row_tile=2048)
                    jax.block_until_ready(out)
                float(jax.device_get(out[0, 0, 0]))
            ms = max(aggregate_xplane(trace_dir, top=40),
                     key=lambda q: q[1])[1] / reps
        finally:
            H._use_factored = orig
            H.histogram_pallas_rows.clear_cache()
        results[tag] = {
            "compile_s": round(compile_s, 1),
            "ns_row": round(ms * 1e6 / n, 3),
            "ns_row_feature": round(ms * 1e6 / (n * F), 5),
        }
        if not args.json:
            print("%-28s compile %6.1f s   %8.2f ns/row   %.4f ns/(row*feat)"
                  % (tag, compile_s, results[tag]["ns_row"],
                     results[tag]["ns_row_feature"]), flush=True)

    if not args.json:
        print("wide-F histogram (F=%d, %d rows, grid-over-groups layout)"
              % (F, n), flush=True)
    measure("F968_B64_factored", 64, force_classic=False)
    measure("F968_B64_classic", 64, force_classic=True)
    measure("F968_B256_classic", 256, force_classic=False)  # gate -> classic
    if args.json:
        print(json.dumps(results))


if __name__ == "__main__":
    main()
