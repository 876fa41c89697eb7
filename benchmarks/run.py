"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, job kind, per-layer metric
or reader is a file of its own, found by the name ``BENCHMARK.json`` or the
cell's file gives (``README.md`` in this directory).  This file holds the
order of a run and the contract's last line, nothing else.

A run: set-up (data from the seed, the program's own ingest, warm-up of the
cell's shapes; ``setup_s`` ends at the first timed dispatch), then with
``--trace 0`` the measured window (up to the traffic file's
``window_end_tree``, or of ``--seconds`` where it states none), with
``--trace 1`` the units up to the traffic file's ``trace_first_tree`` and a
few traced units from that tree on (the same trees on every commit, however
fast), then the checks that decide ``correct``.  The last line of standard
output is the result; everything before it says what was found.
"""
import time
T0 = time.perf_counter()     # process start, before every other import

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def find_cell(bench, name, cells_dir):
    """(cell, its traffic mix, its configuration), each a dict.  The cell is
    its entry in ``BENCHMARK.json``; the traffic mix is ``traffic/<name>.json``
    and the configuration the file its entry names.  A probe or a test brings
    a cell that is not listed as ``<cells_dir>/<name>.json``, and may keep its
    traffic and configuration files in directories beside ``cells_dir``."""
    beside = os.path.dirname(os.path.abspath(cells_dir)) if cells_dir else None
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        if not cells_dir:
            sys.exit("no cell %r in BENCHMARK.json" % name)
        cell = load_json(os.path.join(cells_dir, name + ".json"))

    def first(*paths):
        return next(p for p in paths if p and os.path.exists(p))
    listed = {c["name"]: os.path.join(ROOT, c["file"])
              for c in bench["configs"]}
    cfg = first(listed.get(cell["config"]), beside and os.path.join(
        beside, "configs", cell["config"] + ".json"))
    traffic = first(os.path.join(HERE, "traffic", cell["traffic"] + ".json"),
                    beside and os.path.join(beside, "traffic",
                                            cell["traffic"] + ".json"))
    return cell, load_json(traffic), load_json(cfg)


class Tracer:
    """``with tracer:`` profiles what runs inside it into ``self.dir``; with
    ``profile`` false (a rehearsal) it only marks where the traced units
    are."""

    def __init__(self, keep_dir, profile=True):
        self.keep = keep_dir is not None
        self.profile = profile
        if self.keep:
            os.makedirs(keep_dir, exist_ok=True)
        self.dir = keep_dir or (tempfile.mkdtemp(prefix="bench_trace_")
                                if profile else None)
        self.ran = False

    def __enter__(self):
        if self.profile:
            import jax
            jax.profiler.start_trace(self.dir)
            self.ran = True

    def __exit__(self, *exc):
        if self.profile:
            import jax
            jax.profiler.stop_trace()

    def reduced(self, program_spans=()):
        """What ``trace_reduce.reduce`` makes of the trace, or None when
        nothing was profiled; the trace itself goes unless it is to be
        kept."""
        import trace_reduce
        try:
            if not self.ran:
                return None
            return trace_reduce.reduce(trace_reduce.find_xplane(self.dir),
                                       trace_reduce.UNIT_ANNOTATION,
                                       program_spans)
        finally:
            if self.dir and not self.keep:
                shutil.rmtree(self.dir, ignore_errors=True)


def program_span_names():
    """The names of the spans the program has opened so far (its own span
    API, which also annotates the profiler's host line): what an idle gap is
    labelled with.  Empty for a program without the API."""
    try:
        from lightgbm_tpu.obs import spans
        return set(spans.totals())
    except (ImportError, AttributeError):
        return set()


def layer_metrics(bench, cell, kind, ctx):
    """{name: value} of the per-layer metrics that apply to this cell and kind
    and whose reader found something to read."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        spec = load_json(os.path.join(HERE, "layer_metrics",
                                      m["name"] + ".json"))
        if kind not in spec["kinds"]:
            continue
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(spec["args"], ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(trace, top=10):
    def top_s(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": top_s(trace["own"]), "idle_gaps": top_s(trace["idle"])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cells-dir", default=None,
                    help="where to find a cell that BENCHMARK.json does not "
                         "list (a probe, a test)")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace here (default: a "
                         "temporary directory, removed after the reduction)")
    ap.add_argument("--rehearse-rows", type=int, default=None,
                    help="CPU rehearsal of the control flow at this many "
                         "rows: interpret-mode kernels, no metric printed")
    args = ap.parse_args()
    rehearsal = args.rehearse_rows is not None
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["LIGHTGBM_TPU_PALLAS_INTERPRET"] = "1"

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, wl, cfg = find_cell(bench, args.workload, args.cells_dir)

    import jax
    from lightgbm_tpu.utils.compile_cache import enable_compilation_cache
    devices = jax.devices()
    if not rehearsal and (devices[0].platform != "tpu"
                          or len(devices) != int(cell["chips"])):
        sys.exit("benchmark needs %d TPU chip(s): jax found %d device(s) of "
                 "platform %r" % (cell["chips"], len(devices),
                                  devices[0].platform))
    cache = enable_compilation_cache()
    print("cell %s seed %d seconds %g trace %d; compile cache %s"
          % (args.workload, args.seed, args.seconds, args.trace, cache),
          flush=True)

    kind = importlib.import_module("kinds." + wl["kind"])
    job = kind.Job(cfg, wl, args.seed, rehearse_rows=args.rehearse_rows)
    job.setup()
    tracer = Tracer(args.trace_dir, not rehearsal) if args.trace else None
    job.run(args.seconds, tracer)
    setup_s = job.t_start - T0

    if args.trace:
        trace = tracer.reduced(program_span_names())
        ctx = {"job": job, "trace": trace, "cfg": cfg, "wl": wl,
               "device_kind": devices[0].device_kind}
        metrics = layer_metrics(bench, args.workload, wl["kind"], ctx)
    else:
        trace = None
        values = dict(job.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in bench["end_to_end"] if m["name"] in values}

    import gbdt_job
    correct = not job.failed
    for name, holds, found in job.check() + gbdt_job.window_check(job):
        print("%s %s: %s" % ("ok " if holds else "NOT", name, found),
              flush=True)
        correct = correct and bool(holds)

    # the allocator's peak on the fullest chip (arguments, results, arrays
    # kept), plus what the kind says its largest program needs beside them
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    print("allocator statistics of device 0: %r" % stats[0], flush=True)
    temp = job.program_temp_bytes() if hasattr(job, "program_temp_bytes") else 0
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": temp + max(
                  int(s.get("peak_bytes_in_use", 0)) for s in stats)}
    result = {"correct": correct, "attempted": job.attempted,
              "failed": job.failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_ns"] / 1e9
        device["window_s"] = trace["window_ns"] / 1e9
        result["breakdown"] = breakdown(trace)
    if rehearsal:
        # a CPU number never appears under a metric's name
        print("rehearsal on %s: would report %s" % (devices[0].platform,
                                                    sorted(metrics)))
        result.update(correct=False, metrics={})
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
