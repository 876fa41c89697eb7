"""On-device profiling of the tree build / fused training chunk.

The counterpart of the reference's ``Timer``/``FunctionTimer`` aggregation
(include/LightGBM/utils/common.h:1032-1093) for DEVICE time: host-side timers
only see dispatch on an async runtime, so this captures a ``jax.profiler``
trace and aggregates the XLA op durations from the xplane file with
``jax.profiler.ProfileData``.

Usage:
    python tools/profile_tree.py [rows] [leaves] [max_bin]   # tree build
    python tools/profile_tree.py --chunk [rows] [leaves]     # fused chunk

Captures through the ``lightgbm_tpu.obs.profiling`` layout (``--out``
root, default /tmp/lgbm_tpu_prof, one ``capture_<n>_profile_tree/`` dir
with a ``capture.json`` per invocation) — the SAME artifact shape the
triggered path (``/debug/profile``, the flight recorder) produces, so
this aggregation works on either.  Prints the top ops by total device
time, grouped by op name with counts.  The record of where a tree's time
goes is the benchmark's traced run (``benchmarks/run.py --trace 1``).
"""
import collections
import glob
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def aggregate_xplane(trace_dir: str, top: int = 25):
    """[(name, total_ms, count)] by device time from the newest xplane.pb,
    read with ``jax.profiler.ProfileData`` (nothing but jax).  An op event's
    name is its whole HLO line, ``%<op>.<n> = ...``; events are grouped by
    ``%<op>``, so a kernel is found by the ``name`` its ``pallas_call``
    gives.  Durations are summed as they are: a ``%while`` holds its body's
    events (``benchmarks/trace_reduce.py`` is the reducer that takes own
    times and the busy union)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise SystemExit("no xplane.pb under %s — did the profiler run?"
                         % trace_dir)
    planes = list(ProfileData.from_file(paths[-1]).planes)
    plane = next((p for p in planes if "TPU" in p.name), None)
    if plane is None:
        raise SystemExit("no TPU device plane in the trace (planes: %s) — "
                         "this tool needs a TPU backend"
                         % [p.name for p in planes])
    agg = collections.Counter()
    cnt = collections.Counter()
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            key = re.sub(r"[.\d]+$", "", ev.name.split(" = ", 1)[0])
            agg[key] += ev.duration_ns
            cnt[key] += 1
    return [(name, t / 1e6, cnt[name]) for name, t in agg.most_common(top)]


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="profile one tree build (or --chunk: a fused boosting "
                    "chunk) and aggregate device time from xplane")
    ap.add_argument("rows", nargs="?", type=int, default=1_000_000)
    ap.add_argument("leaves", nargs="?", type=int, default=255)
    ap.add_argument("max_bin", nargs="?", type=int, default=63)
    ap.add_argument("--chunk", action="store_true",
                    help="profile the fused train_chunk path instead")
    ap.add_argument("--nsrow", action="store_true",
                    help="also print per-op device time per logical "
                         "row-visit")
    ap.add_argument("--out", default="/tmp/lgbm_tpu_prof",
                    help="capture root (obs/profiling layout: one "
                         "capture_<n>_profile_tree/ dir per invocation)")
    cli = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.utils.log import Log

    Log.reset_level(30)
    chunk = cli.chunk
    n = cli.rows
    leaves = cli.leaves
    max_bin = cli.max_bin

    rng = np.random.RandomState(0)
    X = rng.normal(size=(n, 28)).astype(np.float32)
    y = ((X[:, 0] * 2 + X[:, 1] ** 2 - X[:, 2] * X[:, 3]) > 0).astype(np.float64)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=max_bin)
    cfg = Config(objective="binary", num_leaves=leaves, max_bin=max_bin,
                 num_iterations=100)
    # the shared capture layout (obs/profiling.py): the standalone tool and
    # the triggered /debug/profile path produce identically-shaped
    # artifacts, so aggregate_xplane works on both
    from lightgbm_tpu.obs import profiling
    root = cli.out
    seq = len(glob.glob(os.path.join(root, "capture_*"))) + 1
    trace_dir = profiling.open_capture(root, seq, "profile_tree")

    if chunk:
        from lightgbm_tpu.boosting.gbdt import GBDT
        from lightgbm_tpu.objective import create_objective
        b = GBDT(cfg, ds, create_objective("binary", cfg))

        def sync():
            b.train_score.block_until_ready()
            float(jax.device_get(b.train_score[0, 0]))

        b.train_chunk(3)
        sync()
        t0 = time.perf_counter()
        b.train_chunk(3)
        sync()
        print("fused chunk: %.1f ms/iter" % ((time.perf_counter() - t0) / 3 * 1e3))
        with profiling.trace_block(trace_dir):
            b.train_chunk(3)
            sync()
        profiling.write_meta(trace_dir, reason="profile_tree",
                             mode="chunk", rows=n, leaves=leaves,
                             max_bin=max_bin)
    else:
        from lightgbm_tpu.core.tree_learner import SerialTreeLearner
        lrn = SerialTreeLearner(ds, cfg)
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        h = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
        arr = lrn.train(g, h, n)
        int(arr.num_leaves)
        t0 = time.perf_counter()
        for _ in range(3):
            arr = lrn.train(g, h, n)
        int(arr.num_leaves)
        print("tree build: %.1f ms" % ((time.perf_counter() - t0) / 3 * 1e3))
        with profiling.trace_block(trace_dir):
            arr = lrn.train(g, h, n)
            int(arr.num_leaves)
        profiling.write_meta(trace_dir, reason="profile_tree",
                             mode="tree", rows=n, leaves=leaves,
                             max_bin=max_bin)

    # --nsrow: also print each op's device time per LOGICAL row-visit.
    # Row-visits are exact from the trained tree (every row passes one
    # window per level).
    visits = None
    if cli.nsrow:
        if chunk:
            trees = b.models[-3:]
            visits = 0.0
            for t in trees:
                nl = t.num_leaves
                visits += float(np.sum(t.leaf_count[:nl] * t.leaf_depth[:nl]))
        else:
            nl = int(arr.num_leaves)
            visits = float(np.sum(np.asarray(arr.leaf_count)[:nl]
                                  * np.asarray(arr.leaf_depth)[:nl]))
    for name, ms, c in aggregate_xplane(trace_dir):
        if visits:
            print("%-74s %9.3f ms x%5d %8.3f ns/row-visit"
                  % (name[:72], ms, c, ms * 1e6 / visits))
        else:
            print("%-88s %9.3f ms x%5d" % (name[:86], ms, c))


if __name__ == "__main__":
    main()
