#!/usr/bin/env python
"""Kernel-plan autotuning sweep -> persisted plan cache.

Runs the empirical planner (``lightgbm_tpu/plan/autotune.py``) over a
shape grid: for every (shape-class, device_kind) it races the candidate
tilings — bucket-ladder variants of the fused split dispatch and
tree-block VMEM budgets of the blocked predict — with walls ranked on
the compile-accounting steady-median machinery (warm loads and compiles
never pollute the ranking), then persists the winners into the atomic,
versioned JSON plan cache (``--cache-out``, default next to the XLA
compilation cache — exactly where the CLI / engine look for it).  With
``--json`` it also writes the full candidate table, winner and margin per
shape.

Off-TPU the fused kernels run in interpret mode (``--interpret`` is
implied): candidate walls are interpreter-priced and NON-EVIDENCE — a
mechanism proof.  No cell of the benchmark runs a tuned plan and the
planner has not been raced on the chip (ROADMAP C3).

Examples::

    python tools/bench_autotune.py --shape 65536:28:256 --reps 4 \
        --cache-out /tmp/plan_cache.json --json /tmp/autotune.json
    python tools/bench_autotune.py --grid default
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the round-18 grid: Higgs-like tall, wide-F factored, wide-F
# classic, multiclass — one row per workload-zoo shape family
DEFAULT_GRID = ("1048576:28:256", "65536:968:64", "65536:600:256",
                "262144:54:64:5")


def parse_shape(spec: str):
    """``n:f:bins[:classes]`` -> ShapeClass fields."""
    parts = spec.split(":")
    if len(parts) not in (3, 4):
        raise argparse.ArgumentTypeError(
            "shape must be n:f:bins[:classes], got %r" % spec)
    n, f, b = int(parts[0]), int(parts[1]), int(parts[2])
    k = int(parts[3]) if len(parts) == 4 else 1
    return (n, f, b, k)


def build_parser():
    ap = argparse.ArgumentParser(
        description="Kernel-plan autotuning sweep (writes the plan cache)")
    ap.add_argument("--shape", action="append", type=parse_shape,
                    metavar="N:F:BINS[:K]", default=None,
                    help="shape class to tune (repeatable); default: "
                         "one small smoke shape")
    ap.add_argument("--grid", choices=["default"], default=None,
                    help="use the round-18 shape grid")
    ap.add_argument("--reps", type=int, default=4,
                    help="steady-state repetitions per candidate "
                         "(first dispatch is the counted miss)")
    ap.add_argument("--trees", type=int, default=8,
                    help="trees of the predict-side fixture model")
    ap.add_argument("--interpret", action="store_true",
                    help="force Pallas interpret mode (implied off-TPU; "
                         "walls are then mechanism proof, not evidence)")
    ap.add_argument("--cache-out", default=None,
                    help="plan cache path (default: the location the "
                         "CLI/engine probe, next to the XLA cache)")
    ap.add_argument("--json", default=None,
                    help="also write the candidate tables here")
    ap.add_argument("--scale-rows", type=int, default=None,
                    help="cap synthetic fixture rows (tuning still keys "
                         "the cache by the REQUESTED shape class); use "
                         "for off-TPU smoke runs")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS",
                                                          ""))
    import jax

    from lightgbm_tpu.plan import autotune, cache as plan_cache, planner

    shapes = list(args.shape or [])
    if args.grid == "default":
        shapes += [parse_shape(s) for s in DEFAULT_GRID]
    if not shapes:
        shapes = [(8192, 8, 32, 1)]
    on_tpu = jax.default_backend() == "tpu"
    interpret = bool(args.interpret) or not on_tpu
    cache_path = args.cache_out or plan_cache.default_cache_path()

    def progress(sc, res):
        print("tuned %s (fixture rows %d, interpret=%s): winner %s "
              "margin %s"
              % (res["key"], res["fixture_rows"], interpret,
                 res["winner"]["name"],
                 {m: round(v, 3) for m, v in res["margin"].items()}))

    sweep = autotune.run_sweep(
        [planner.shape_class(n, f, b, num_class=k)
         for (n, f, b, k) in shapes],
        cache_path=cache_path, reps=args.reps, interpret=interpret,
        fixture_rows=args.scale_rows, trees=args.trees, progress=progress)
    device_kind = sweep["device_kind"]

    artifact = {
        "v": 1,
        "metric": "plan_autotune",
        "unit": "steady_p50_s",
        "device_kind": str(device_kind),
        "backend": jax.default_backend(),
        "interpret": interpret,
        "evidence": ("interpret-mode walls: mechanism proof only"
                     if interpret else "device walls"),
        "cache": cache_path,
        "shapes": sweep["shapes"],
    }
    print("plan cache -> %s" % cache_path)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=1)
        print("candidates -> %s" % args.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
