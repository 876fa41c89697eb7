#!/usr/bin/env python
"""Time INSIDE the Pallas kernels of a benchmark cell's traced trees, by the
regions the kernels open (``lightgbm_tpu.obs.scopes.KERNEL_REGIONS``).

    python tools/kernel_regions.py --workload higgs_train --seed 2147800001

runs the cell's own traced run in a child process (``benchmarks/run.py
--workload .. --trace 1 --trace-dir D``, unedited) whose ``LIBTPU_INIT_ARGS``
carries ``obs.scopes.KERNEL_TRACE_FLAGS``: with them the TPU's library turns
a kernel's ``tpu.trace_start`` / ``tpu.trace_stop`` into events of the device
plane's ``XLA TraceMe`` line, under ``jax.profiler.start_trace``'s default
options (PERF.md §6, PR 39: the spike).  Then it reads the kept
``*.xplane.pb`` with ``jax.profiler.ProfileData`` and prints, per traced tree:

- ms in each region, by kernel (the split kernel's buckets ``c4096``,
  ``c1024``, ``small``; the rows histogram), their sum beside the kernel
  events' own time on ``XLA Ops`` (they must agree: what is left is the
  launch and what the compiler put outside every region);
- ns a window row / a right row / a histogrammed row, from the rows the
  run's own readers print (``roofline.tree_rows`` of the traced trees);
- every line of the device plane with its event count;
- for the ``%cond`` events around the split kernel, what any line holds
  between the branch's last op and the conditional's end.

With the flag on the TPU's library also writes a ``Tensor Core`` line, an
event for every instrumented bundle of every custom call (12.3M a chunk of
``higgs_train``), and a whole traced chunk outgrows the profiler's buffer:
what comes after is dropped from EVERY line (PERF.md §6, PR 39).  So at a
cell's real size ``--slice SECONDS`` (0.3: about a tree) drives the cell's
kind itself in the child, through the kind's own ``run`` with a tracer of
this tool's that starts the profile where the traced unit starts and stops
it ``SECONDS`` later, ``obs.profiling``'s ``detail="kernel"`` options on
(the ``Tensor Core Sync Flag`` line).  The launches it caught whole are the
chunk's first, in the trees' own order, so their rows are the trees' nodes'
(``launch_rows.json``) and "a tree" is 254 launches' worth.  A trace that
lost events says so (launches a tree are not the trees').

``--read D`` reads a directory an earlier call kept; ``--no-flags`` runs the
child without the flags (the control: what the regions cost when they are
on).  This process never touches the chip: the child owns it.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import subprocess
import sys
import tempfile
import threading
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

REGION_LINE = "XLA TraceMe"
OPS_LINE = "XLA Ops"
FLOOD_LINE = "Tensor Core"
DEVICE_PLANE_PREFIX = "/device:TPU:"
SPLIT_PREFIX = "%partition_hist_pallas_"
KERNEL_CALL = " custom-call("      # a Pallas kernel's event is its HLO line
SPLIT_BUCKETS = ("c4096", "c1024", "small")
WHOLE = 0.95          # a launch is whole when its regions are this much of it


def run_child(args, trace_dir):
    """The cell's traced run, its output kept beside the trace."""
    from lightgbm_tpu.obs.scopes import KERNEL_TRACE_FLAGS
    env = dict(os.environ)
    # this process reads the trace on the CPU; the child takes the chip
    env.pop("JAX_PLATFORMS", None)
    if args.jax_platforms is not None:
        env["JAX_PLATFORMS"] = args.jax_platforms
    if not args.no_flags:
        env["LIBTPU_INIT_ARGS"] = " ".join(
            [env.get("LIBTPU_INIT_ARGS", "")] + list(KERNEL_TRACE_FLAGS)
        ).strip()
    if args.slice:
        cmd = [sys.executable, os.path.abspath(__file__), "--slice-child",
               "--slice", str(args.slice)]
    else:
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
               "--trace", "1"]
    cmd += ["--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace-dir", trace_dir]
    if args.cells_dir:
        cmd += ["--cells-dir", args.cells_dir]
    if args.rehearse_rows is not None:
        cmd += ["--rehearse-rows", str(args.rehearse_rows)]
    print("child: LIBTPU_INIT_ARGS=%r %s"
          % (env.get("LIBTPU_INIT_ARGS", ""), " ".join(cmd)), flush=True)
    os.makedirs(trace_dir, exist_ok=True)
    with open(os.path.join(trace_dir, "run.out"), "w") as out:
        done = subprocess.run(cmd, env=env, cwd=ROOT, stdout=out,
                              stderr=subprocess.STDOUT)
    if done.returncode:
        with open(os.path.join(trace_dir, "run.out")) as fh:
            sys.stdout.write(fh.read()[-6000:])
        sys.exit("the traced run failed: exit code %d" % done.returncode)


class SliceTracer:
    """What a kind's ``run`` takes for its tracer: the profile starts where
    the traced units start and a timer stops it ``seconds`` later, while
    the device is still in the first of them."""

    def __init__(self, trace_dir, seconds):
        self.dir, self.seconds = trace_dir, seconds
        self.lock = threading.Lock()
        self.on = False

    def _stop(self):
        import jax
        with self.lock:
            if self.on:
                jax.profiler.stop_trace()
                self.on = False

    def __enter__(self):
        import jax
        from lightgbm_tpu.obs import profiling
        jax.profiler.start_trace(
            self.dir, profiler_options=profiling.profile_options("kernel"))
        self.on = True
        self.timer = threading.Timer(self.seconds, self._stop)
        self.timer.start()

    def __exit__(self, *exc):
        self.timer.cancel()
        self._stop()


def slice_child(args):
    """The cell's kind, set up and run as ``benchmarks/run.py`` runs it, with
    a :class:`SliceTracer`; leaves ``launch_rows.json``: [window rows,
    smaller-child rows, right rows] of every split of the traced trees, in
    the order the chunk launches them."""
    import importlib

    import run as bench_run
    rehearsal = args.rehearse_rows is not None
    if rehearsal:                  # as benchmarks/run.py rehearses
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["LIGHTGBM_TPU_PALLAS_INTERPRET"] = "1"
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, wl, cfg = bench_run.find_cell(bench, args.workload, args.cells_dir)
    import jax
    from lightgbm_tpu.utils.compile_cache import enable_compilation_cache
    devices = jax.devices()
    if not rehearsal and (devices[0].platform != "tpu"
                          or len(devices) != int(cell["chips"])):
        sys.exit("needs %d TPU chip(s), found %r" % (cell["chips"], devices))
    enable_compilation_cache()
    kind = importlib.import_module("kinds." + wl["kind"])
    job = kind.Job(cfg, wl, args.seed, rehearse_rows=args.rehearse_rows)
    job.setup()
    job.run(args.seconds, SliceTracer(args.trace_dir, args.slice))
    rows = []
    for t in job.traced_trees:
        for node in range(int(t.num_leaves) - 1):
            kids = [int(t.internal_count[c]) if c >= 0
                    else int(t.leaf_count[~c])
                    for c in (int(t.left_child[node]),
                              int(t.right_child[node]))]
            rows.append([int(t.internal_count[node]), min(kids), kids[1]])
    with open(os.path.join(args.trace_dir, "launch_rows.json"), "w") as fh:
        json.dump({"rows": rows, "trees": len(job.traced_trees),
                   "table_rows": int(job.gbdt.num_data)}, fh)
    print("slice of %.2f s kept in %s; %d launches' rows written"
          % (args.slice, args.trace_dir, len(rows)), flush=True)


def parse_run(text):
    """What the run's own readers printed: {"trees", "window_rows",
    "small_rows" (both of all traced trees), "buckets": {name: window rows a
    tree}, "result": the result line}."""
    found = {"buckets": {}}
    for line in text.splitlines():
        m = re.search(r"over (\d+) traced trees at \d+ device columns of \d+ "
                      r"bins: (\d+) window rows .*?, (\d+) smaller-child "
                      r"rows", line)
        if m:
            found.update(trees=int(m.group(1)), window_rows=int(m.group(2)),
                         small_rows=int(m.group(3)))
        m = re.match(r"bucket (\w+), per traced tree: ([\d.]+) launches "
                     r"\(.*?\), ([\d.]+) window rows", line)
        if m:
            found["buckets"][m.group(1)] = float(m.group(3))
        m = re.search(r"traced trees (\d+)-(\d+)", line)
        if m:
            found["traced"] = (int(m.group(1)), int(m.group(2)))
            found.setdefault("trees", int(m.group(2)) - int(m.group(1)) + 1)
        if line.startswith('{"correct"'):
            found["result"] = json.loads(line)
    return found


def load_planes(path):
    """{plane: {line: [(name, start_ns, dur_ns)] by start}} of the device
    planes of an ``.xplane.pb``(``.gz``)."""
    import gzip
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        planes[plane.name] = {}
        for line in plane.lines:
            if line.name == FLOOD_LINE:     # millions: counted, not kept
                planes[plane.name][line.name] = range(
                    sum(1 for _ in line.events))
                continue
            planes[plane.name][line.name] = sorted(
                ((e.name, e.start_ns, e.duration_ns) for e in line.events),
                key=lambda e: e[1])
    return planes


def op_name(event_name):
    """``%fusion.59`` of ``%fusion.59 = f32[..] fusion(...)``."""
    return event_name.split(" ", 1)[0]


def kernel_of(name):
    """``c4096`` / ``c1024`` / ``small`` for a split kernel's op,
    ``rows_hist`` for the rows histogram, else the op's name less its
    number."""
    base = name.rsplit(".", 1)[0]
    if base.startswith(SPLIT_PREFIX):
        return base[len(SPLIT_PREFIX):]
    if base.startswith("%histogram_pallas_rows"):
        return "rows_hist"
    return base.lstrip("%")


def regions_by_kernel(lines, drop_last=False):
    """[(kernel, ns on XLA Ops, {region: ns})] of every kernel launch of one
    device plane, in time order, and the ns of region events inside no
    kernel event; ``drop_last`` leaves the plane's last kernel event out,
    and every region event that starts after the one before it ends."""
    kernels = [(s, s + d, op_name(n)) for n, s, d in lines.get(OPS_LINE, ())
               if KERNEL_CALL in n]
    regions = lines.get(REGION_LINE, ())
    if drop_last and kernels:
        kernels.pop()
        end = kernels[-1][1] if kernels else 0
        regions = [r for r in regions if r[1] < end]
    starts = [k[0] for k in kernels]
    launches = [(kernel_of(name), e - s, defaultdict(float))
                for s, e, name in kernels]
    stray = 0.0
    for name, s, d in regions:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= kernels[i][1] + 1:
            launches[i][2][name] += d
        else:
            stray += d
    return launches, stray


def whole_launches(launches):
    """The launches whose regions the trace holds whole: their sum is the
    kernel's own time but for the launch and what the compiler put outside
    (2% on a trace that lost nothing).  The ``Tensor Core`` line's flood
    makes the profiler drop events inside a long launch, and a region
    whose start or stop went is not in the trace."""
    return [l for l in launches if sum(l[2].values()) >= WHOLE * l[1]]


def cond_tails(lines):
    """For the ``%cond`` events of ``XLA Ops`` that hold a split kernel:
    (conditionals, ns from the branch's last op to the conditional's end,
    {line: [events, ns overlapping that tail, {name: ns}]})."""
    ops = lines.get(OPS_LINE, ())
    starts = [s for _, s, _ in ops]
    count, tail_ns = 0, 0.0
    others = {ln: (ev, [s for _, s, _ in ev]) for ln, ev in lines.items()
              if ln not in (OPS_LINE, FLOOD_LINE, "XLA Modules", "Steps")}
    seen = defaultdict(lambda: [0, 0.0, defaultdict(float)])
    for name, s, d in ops:
        if not op_name(name).startswith("%cond"):
            continue
        lo = bisect.bisect_right(starts, s)
        hi = bisect.bisect_left(starts, s + d)
        kids = [ops[i] for i in range(lo, hi) if ops[i][1] + ops[i][2]
                <= s + d]
        if not any(op_name(k[0]).startswith(SPLIT_PREFIX) for k in kids):
            continue
        last_end = max(k[1] + k[2] for k in kids)
        count += 1
        tail_ns += s + d - last_end
        for ln, (events, ev_starts) in others.items():
            j = bisect.bisect_left(ev_starts, last_end - 1_000_000)
            while j < len(events) and events[j][1] < s + d:
                en, es, ed = events[j]
                over = min(es + ed, s + d) - max(es, last_end)
                if over > 0 or (ed == 0 and last_end <= es < s + d):
                    seen[ln][0] += 1
                    seen[ln][1] += max(over, 0)
                    seen[ln][2][en[:48]] += max(over, 0)
                j += 1
    return count, tail_ns, seen


def report_whole_launches(by_plane, rows, a_tree):
    """A slice's split launches are the chunk's first, in split order, so
    launch i moved ``rows[i]``; over the launches the trace holds whole, by
    bucket: each region's share of the kernel's time and its ns a row."""
    tally = defaultdict(lambda: {"n": 0, "of": 0, "ns": 0.0, "rows": [0, 0, 0],
                                 "parts": defaultdict(float)})
    for launches in by_plane:
        splits = [l for l in launches if l[0] in SPLIT_BUCKETS]
        kept = set(map(id, whole_launches(splits)))
        for i, launch in enumerate(splits[:len(rows)]):
            t = tally[launch[0]]
            t["of"] += 1
            if id(launch) not in kept:
                continue
            t["n"] += 1
            t["ns"] += launch[1]
            for j in range(3):
                t["rows"][j] += rows[i][j]
            for region, v in launch[2].items():
                t["parts"][region] += v
    for launches in by_plane:
        # the slice's first tree, launch for launch the same on every run
        # of one seed: what a flag costs is read off two such lines
        first = [l for l in launches if l[0] in SPLIT_BUCKETS][:a_tree]
        if len(first) == a_tree:
            print("\nthe slice's first %d split launches (one tree): %.4f "
                  "ms of kernel (%s)" % (a_tree, sum(l[1] for l in first)
                                         / 1e6, ", ".join(
                      "%s %.4f in %d" % (b, sum(l[1] for l in first
                                                if l[0] == b) / 1e6,
                                         sum(l[0] == b for l in first))
                      for b in SPLIT_BUCKETS)))
    print("\nover the launches the trace holds whole (regions at least "
          "%d%% of the launch):" % (100 * WHOLE))
    for bucket, t in sorted(tally.items()):
        if not t["n"]:
            print("  %-6s none of %d" % (bucket, t["of"]))
            continue
        window, small, right = t["rows"]
        p = t["parts"]
        print("  %-6s %d of %d launches, %d window rows (%.0f a launch), "
              "%.4f ns of kernel a window row: %s"
              % (bucket, t["n"], t["of"], window, window / t["n"],
                 t["ns"] / window, "  ".join(
                     "%s %.1f%% %.4f" % (r, 100 * v / t["ns"], v / window)
                     for r, v in sorted(p.items()))))
        if small and right:
            print("         k.hist %.4f ns a smaller-child row (%d), "
                  "k.copy_back %.4f ns a right row (%d), k.prologue + "
                  "k.drain %.2f us a launch"
                  % (p["k.hist"] / small, small, p["k.copy_back"] / right,
                     right, (p["k.prologue"] + p["k.drain"]) / t["n"] / 1e3))


def report(trace_dir, trees=None):
    import trace_reduce
    is_file = os.path.isfile(trace_dir)
    path = trace_dir if is_file else trace_reduce.find_xplane(trace_dir)
    beside = os.path.dirname(path) if is_file else trace_dir
    run, sliced = {"buckets": {}}, None
    if os.path.exists(os.path.join(beside, "run.out")):
        with open(os.path.join(beside, "run.out")) as fh:
            run = parse_run(fh.read())
    if os.path.exists(os.path.join(beside, "launch_rows.json")):
        with open(os.path.join(beside, "launch_rows.json")) as fh:
            sliced = json.load(fh)
    trees = trees or run.get("trees") or (sliced and sliced["trees"])
    if not trees:
        sys.exit("how many trees the trace holds is in no run.out: --trees")
    planes = load_planes(path)
    chips = len(planes)
    if not chips:
        sys.exit("no device plane in %s: not a chip's trace" % path)
    print("trace %s: %d device plane(s), %d traced trees%s"
          % (path, chips, trees,
             " (%d-%d)" % run["traced"] if "traced" in run else ""))
    for plane, lines in sorted(planes.items()):
        print("plane %s:" % plane)
        for ln, events in sorted(lines.items()):
            print("  line %-24r %8d events" % (ln, len(events)))
    inside = defaultdict(lambda: defaultdict(float))
    whole = defaultdict(lambda: [0, 0.0])
    stray = 0.0
    by_plane = []
    for lines in planes.values():
        # a slice ends inside a launch: that one is left out
        launches, loose = regions_by_kernel(lines, drop_last=bool(sliced))
        by_plane.append(launches)
        stray += loose
        for k, ns, parts in launches:
            whole[k][0] += 1
            whole[k][1] += ns
            for region, v in parts.items():
                inside[k][region] += v
    if not inside:
        print("NO REGION EVENTS: line %r is missing or empty. The process "
              "must START with LIBTPU_INIT_ARGS holding %s"
              % (REGION_LINE, "obs.scopes.KERNEL_TRACE_FLAGS"))
    result = run.get("result", {}).get("metrics", {})
    splits = sum(whole[k][0] for k in SPLIT_BUCKETS if k in whole) / chips
    a_tree = (len(sliced["rows"]) / sliced["trees"] if sliced else next(
        (v["value"] for k, v in result.items()
         if k.startswith("launches_per_tree")), None))
    if sliced:
        # the launches caught whole are the chunk's first, in split order
        caught = sliced["rows"][:int(splits)]
        trees = splits / a_tree
        rows = {"window": sum(r[0] for r in caught),
                "small": sum(r[1] for r in caught),
                "right": sum(r[2] for r in caught)}
        print("a slice: %d split launches caught whole = %.3f trees of %d "
              "launches" % (splits, trees, a_tree))
    else:
        right = next((v["value"] for k, v in result.items()
                      if k.startswith("split_right_rows_per_tree")), None)
        rows = {"window": run.get("window_rows"),
                "small": run.get("small_rows"),
                "right": right and right * trees}
        if a_tree and abs(splits / trees - a_tree) > 0.01 * a_tree:
            print("THE TRACE LOST EVENTS: %.2f split launches a tree on %r "
                  "where the trees made %.2f (the profiler's buffer was "
                  "full: take a --slice)" % (splits / trees, OPS_LINE,
                                             a_tree))
    per = 1e6 * trees * chips          # ns of all chips -> ms a tree a chip
    print("\nper traced tree%s, ms (regions | their sum | the kernel on %r "
          "| launches a tree):" % (" and chip" if chips > 1 else "",
                                   OPS_LINE))
    table = {}
    for k in sorted(whole):
        n, ns = whole[k]
        parts = inside.get(k, {})
        total = sum(parts.values())
        table[k] = dict({r: v / per for r, v in parts.items()},
                        regions_sum=total / per, kernel=ns / per,
                        launches=n / trees / chips)
        print("  %-10s %s | %.3f | %.3f (%.2f%% outside every region) | "
              "%.2f" % (k, "  ".join("%s %.3f" % (r, v / per) for r, v in
                                     sorted(parts.items())) or "-",
                        total / per, ns / per,
                        100.0 * (ns - total) / ns if ns else 0.0,
                        n / trees / chips))
    if stray:
        print("  region events inside no kernel event: %.3f ms a tree"
              % (stray / per))
    split = defaultdict(float)
    for k, parts in inside.items():
        if k in SPLIT_BUCKETS:
            for region, ns in parts.items():
                split[region] += ns
    if split and rows["window"]:
        # the rows are the whole table's: a chip's kernel over its share
        line = ["ns a window row: " + "  ".join(
            "%s %.4f" % (r, split[r] / rows["window"])
            for r in sorted(split))]
        if rows["small"] and "k.hist" in split:
            line.append("k.hist %.4f ns a histogrammed (smaller-child) row"
                        % (split["k.hist"] / rows["small"]))
        if rows["right"] and "k.copy_back" in split:
            line.append("k.copy_back %.4f ns a right row"
                        % (split["k.copy_back"] / rows["right"]))
        print("\nthe split kernel over %d window rows, %s smaller-child "
              "rows, %s right rows a tree:\n  %s"
              % (rows["window"] / trees,
                 rows["small"] and int(rows["small"] / trees),
                 rows["right"] and int(rows["right"] / trees),
                 "\n  ".join(line)))
    if sliced and inside:
        report_whole_launches(by_plane, sliced["rows"], int(a_tree))
    conds = tails = 0
    seen_all = defaultdict(lambda: [0, 0.0, defaultdict(float)])
    for lines in planes.values():
        n, ns, seen = cond_tails(lines)
        conds += n
        tails += ns
        for ln, (c, t, names) in seen.items():
            seen_all[ln][0] += c
            seen_all[ln][1] += t
            for name, v in names.items():
                seen_all[ln][2][name] += v
    if conds:
        print("\n%d conditionals around a split kernel: %.3f us each from "
              "the branch's last op to the conditional's end (%.3f ms a "
              "tree); in that tail:" % (conds, tails / conds / 1e3,
                                        tails / per))
        for ln, (c, t, names) in sorted(seen_all.items()):
            top = sorted(names.items(), key=lambda kv: -kv[1])[:4]
            print("  line %-24r %7d events, %.3f us a conditional: %s"
                  % (ln, c, t / conds / 1e3,
                     ", ".join("%s %.2f" % (n, v / conds / 1e3)
                               for n, v in top)))
        if not seen_all:
            print("  no line holds an event there")
    print(json.dumps({"kernel_regions": table, "trees": trees,
                      "chips": chips, "cond_tail_us": tails / conds / 1e3
                      if conds else None}))
    return table


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--no-flags", action="store_true",
                    help="the control: the child starts without the flags")
    ap.add_argument("--cells-dir", default=None,
                    help="passed on to benchmarks/run.py (a probe's cell)")
    ap.add_argument("--slice", type=float, default=None,
                    help="profile only this many seconds from the start of "
                         "the traced unit (a cell at its real size: 0.3)")
    ap.add_argument("--rehearse-rows", type=int, default=None,
                    help="passed on: a CPU rehearsal of the control flow")
    ap.add_argument("--slice-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--read", default=None,
                    help="read a trace directory an earlier call kept")
    ap.add_argument("--trees", type=int, default=None,
                    help="traced trees, when the directory has no run.out")
    args = ap.parse_args()
    if args.slice_child:
        return slice_child(args)
    # the reader needs jax.profiler only; the chip is the child's
    args.jax_platforms = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.read:
        report(args.read, args.trees)
        return
    if not args.workload or args.seed is None:
        ap.error("--workload and --seed, or --read")
    # a chunk's trace with the regions on runs to hundreds of MB: kept
    # where TMPDIR says unless asked for, and read here
    trace_dir = args.trace_dir or tempfile.mkdtemp(
        prefix="kernel_regions_%s_%d_" % (args.workload, args.seed))
    run_child(args, trace_dir)
    with open(os.path.join(trace_dir, "run.out")) as fh:
        tail = fh.read().splitlines()
    print("\n".join(l for l in tail if l.startswith(
        ("traced trees", "bucket ", "roofline of", "scope find", "scope chunk",
         "scope rest", "ok ", "NOT "))))
    print(tail[-1][:6000])
    report(trace_dir, args.trees)


if __name__ == "__main__":
    main()
