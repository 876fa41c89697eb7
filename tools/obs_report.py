#!/usr/bin/env python
"""Render a telemetry JSONL (lightgbm_tpu/obs) into human/trace artifacts.

Any run with ``telemetry_out=<path>`` set (engine.train, the CLI)
writes a schema-versioned JSONL event stream plus
``<path>.summary.json``; a pod run writes one ``<path>.rank<k>.jsonl``
shard per host.  This tool turns those into things people read:

- the end-of-run human table (``obs.report.human_table``) — from the
  written summary when present, else rebuilt from the events (serving,
  resilience AND quality blocks: ``kind="drift"`` breadcrumbs rebuild the
  per-model, per-generation drift table a died run never summarized);
- a Chrome-trace/Perfetto JSON (``--trace out.json``): ``kind="span"``
  events (obs/spans.py) become nested lifelines — one lane per trace id,
  so a single serving request shows its queue-wait / coalesce / dispatch
  children inside the request slice — and every other event carrying a
  duration (``dt_s``) becomes a complete ("X") slice, the rest instants.
  Load in ``chrome://tracing`` / https://ui.perfetto.dev;
- ``--merge``: treat the positional path as the pod BASE path, glob its
  ``.rank<k>.jsonl`` shards, and reassemble the pod view of a (possibly
  died) run: a per-host breakdown table plus, with ``--trace``, one
  skew-aligned merged trace (each rank its own pid; per-rank timestamps
  shifted so every rank's ``run_start`` coincides, removing host clock
  skew from the picture).

Events stream through ``obs.iter_events`` (O(1) memory), so a multi-GB
died-run artifact never needs artifact-sized RAM.

No device work, no import-time allocation: heavy imports happen inside
``main`` after argparse has answered ``--help``.
"""
import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# span bookkeeping fields that should not repeat into trace args
_SPAN_KEYS = ("v", "ts", "kind", "dt_s", "t0", "dur_s", "name",
              "trace_id", "span_id", "parent_id")


def build_parser():
    ap = argparse.ArgumentParser(
        description="render a lightgbm_tpu telemetry JSONL into the human "
                    "summary table and/or a Chrome-trace file; --merge "
                    "reassembles a pod run's .rank<k>.jsonl shards")
    ap.add_argument("jsonl", help="telemetry JSONL path (telemetry_out=...);"
                                  " with --merge, the pod BASE path the "
                                  ".rank<k>.jsonl shards were derived from")
    ap.add_argument("--summary", default=None,
                    help="summary JSON to render (default: <jsonl>"
                         ".summary.json when present, else rebuilt from "
                         "the events)")
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="write a Chrome-trace/Perfetto JSON built from "
                         "the event timestamps to OUT")
    ap.add_argument("--merge", action="store_true",
                    help="pod mode: glob <jsonl>.rank*.jsonl shards, print "
                         "a per-host breakdown and merge the trace "
                         "(per-rank pids, run_start skew-aligned)")
    ap.add_argument("--no-table", action="store_true",
                    help="skip printing the human summary table")
    return ap


class _SpanLanes:
    """Stable trace_id -> small-int lane assignment.  Lane 0 is reserved
    for non-span events; each trace gets its own tid so its spans nest as
    one lifeline in the viewer."""

    def __init__(self):
        self._lanes = {}

    def tid(self, trace_id) -> int:
        lane = self._lanes.get(trace_id)
        if lane is None:
            lane = self._lanes[trace_id] = len(self._lanes) + 1
        return lane


def event_to_trace(e, lanes: _SpanLanes, shift: float = 0.0, pid: int = 0):
    """One telemetry event -> one Chrome trace-event dict (ts/dur in
    microseconds).  ``shift`` is added to every timestamp (skew
    alignment); ``pid`` separates pod ranks."""
    args = {k: v for k, v in e.items()
            if k not in _SPAN_KEYS and isinstance(v, (int, float, str, bool))}
    if e["kind"] == "span":
        t0 = e.get("t0")
        if not isinstance(t0, (int, float)):
            t0 = e["ts"] - float(e.get("dur_s", 0.0))
        return {"name": str(e.get("name", "span")), "ph": "X",
                "ts": (t0 + shift) * 1e6,
                "dur": float(e.get("dur_s", 0.0)) * 1e6,
                "pid": pid, "tid": lanes.tid(e.get("trace_id")),
                "args": args}
    dt = e.get("dt_s")
    if isinstance(dt, (int, float)) and dt >= 0:
        t0 = e.get("t0")
        if not isinstance(t0, (int, float)):
            t0 = e["ts"] - dt
        return {"name": e["kind"], "ph": "X", "ts": (t0 + shift) * 1e6,
                "dur": dt * 1e6, "pid": pid, "tid": 0, "args": args}
    return {"name": e["kind"], "ph": "i", "s": "g",
            "ts": (e["ts"] + shift) * 1e6, "pid": pid, "tid": 0,
            "args": args}


def write_chrome_trace(out_path: str, shards) -> int:
    """Stream shards of (pid, shift, event-iterable, label) into ONE
    Chrome-trace JSON without materializing the events; returns the trace
    event count.  Ordering is irrelevant to the format, so merging is a
    plain concatenation."""
    n = 0
    with open(out_path, "w") as fh:
        fh.write('{"displayTimeUnit": "ms", "traceEvents": [\n')
        first = True
        for pid, shift, events, label in shards:
            if label is not None:
                meta = {"name": "process_name", "ph": "M", "pid": pid,
                        "args": {"name": label}}
                fh.write(("" if first else ",\n") + json.dumps(meta))
                first = False
            lanes = _SpanLanes()
            for e in events:
                fh.write(("" if first else ",\n")
                         + json.dumps(event_to_trace(e, lanes, shift, pid)))
                first = False
                n += 1
        fh.write("\n]}\n")
    return n


def summary_from_events(events):
    """Rebuild a renderable summary dict from raw events (for JSONL files
    whose run died before finalize_run wrote the summary).  ``events`` may
    be any iterable — one streaming pass."""
    from lightgbm_tpu.obs.registry import Histogram
    hists = {}
    counters = {}
    recompiles = {}
    # serving rollup from serve_* events: the per-request latency histogram
    # is gone with the process, but batch latency/occupancy/queue depth and
    # the per-model request counts reconstruct from the stream
    srv_counters = {}
    srv_hists = {}
    # resilience event kind -> summary-counter name (the faults a died run
    # absorbed are exactly what its post-mortem reader wants first)
    # quality-plane recovery: the monitor emits a kind="drift" breadcrumb
    # every few observations; the LATEST one per (model, generation)
    # reconstructs the drift table a died run never wrote to its summary
    drift = {}
    res_kinds = {"preempt_checkpoint": "preemptions",
                 "io_retry": "io_retries",
                 "predict_fallback": "predict_fallbacks",
                 "checkpoint_skipped": "checkpoint_skipped",
                 "watchdog_stall": "watchdog_stalls",
                 "elastic_resume": "elastic_resumes"}
    resilience = {}
    # forensics recovery (round 16): kind="compile" breadcrumbs rebuild the
    # compile section (recovered compile_s is the raw miss-bearing dispatch
    # wall — an upper bound; the steady subtraction died with the process),
    # kind="alert" transitions rebuild the fired tally per rule
    compile_keys = {}
    alert_rules = {}
    alerts_fired = 0
    captures = []
    # kernel-plan recovery (round 18): kind="plan" stamps rebuild the
    # provenance-per-site table, kind="plan_fallback" the cache
    # degradation count a died run never summarized
    plan_sites = {}
    plan_fallbacks = 0
    # online-learning recovery: kind="online_cycle" events rebuild the
    # cycles-by-trigger table and the last generation/rows_behind gauges
    # a died train-while-serve run never summarized
    onl_counters = {}
    onl_gauges = {}
    onl_hists = {}
    # explanations recovery (round 19): kind="contrib" dispatch events +
    # contrib-tagged serve batches rebuild the contrib block a died run
    # never summarized
    ctb_counters = {}
    ctb_hists = {}
    # streaming-ingest recovery (round 21): kind="ingest" chunk events
    # rebuild the ingest block.  In --merge pod mode this folds per-rank
    # shards: chunks/rows/stall SUM across ranks, the RSS high-water is
    # the MAX (each rank's reading describes its own host; the pod's
    # headline number is the worst host)
    ing_counters = {}
    ing_gauges = {}
    ing_hists = {}
    # quantized-training recovery (round 22): kind="quant" chunk events
    # rebuild the quant block — how many chunks/iterations rode the
    # integer-histogram path and its static geometry — for runs that died
    # before the summary writer ran
    qnt_counters = {}
    qnt_gauges = {}
    n_events = 0
    for e in events:
        n_events += 1
        counters[e["kind"]] = counters.get(e["kind"], 0) + 1
        dt = e.get("dt_s")
        if isinstance(dt, (int, float)):
            hists.setdefault(e["kind"] + "_s", Histogram()).observe(dt)
        if e["kind"] == "span" and isinstance(e.get("dur_s"), (int, float)):
            # spans histogram under their own name so a died run still
            # shows queue_wait/dispatch quantiles per span kind
            hists.setdefault("span_%s_s" % e.get("name", "?"),
                             Histogram()).observe(e["dur_s"])
        if e["kind"] in res_kinds:
            key = res_kinds[e["kind"]]
            resilience[key] = resilience.get(key, 0) + 1
            if e["kind"] == "watchdog_stall":
                resilience["watchdog_stall_s"] = e.get("stall_s")
        if e["kind"] == "drift":
            # keyed per RANK too: drift breadcrumbs are cumulative
            # per-process counters, so in --merge pod mode one shard's
            # latest must not overwrite another's (they aggregate below)
            drift[(str(e.get("model", "?")), int(e.get("generation", 1)),
                   e.get("rank"))] = e
        if e["kind"] == "recompile":
            # one event can carry n>1 compiles (a cache that grew by
            # several programs in one dispatch)
            key = "%s|%s" % (e.get("fn", "?"), e.get("bucket", "?"))
            recompiles[key] = recompiles.get(key, 0) + int(e.get("n", 1))
        if e["kind"] == "compile":
            key = "%s|%s" % (e.get("fn", "?"), e.get("bucket", "?"))
            agg = compile_keys.setdefault(key, {"compiles": 0,
                                                "compile_s": 0.0})
            agg["compiles"] += int(e.get("n", 1))
            agg["compile_s"] += float(e.get("dispatch_s", 0.0) or 0.0)
        if e["kind"] == "alert":
            rule = str(e.get("rule", "?"))
            agg = alert_rules.setdefault(rule, {"fired": 0,
                                                "last_state": None})
            if e.get("state") == "firing":
                agg["fired"] += 1
                alerts_fired += 1
            agg["last_state"] = e.get("state")
            agg["series"] = e.get("series")
            if e.get("severity") is not None:
                agg["severity"] = e.get("severity")
        if e["kind"] == "profile_capture":
            captures.append({k: e.get(k) for k in
                             ("n", "reason", "dir", "seconds", "error")
                             if e.get(k) is not None})
        if e["kind"] == "plan":
            plan_sites[str(e.get("site", "?"))] = {
                "provenance": e.get("provenance"),
                "key": e.get("key") or None}
        if e["kind"] == "plan_fallback":
            plan_fallbacks += 1
        if e["kind"] == "online_cycle":
            onl_counters["online_cycles"] = \
                onl_counters.get("online_cycles", 0) + 1
            trig = "online_trigger_%s" % e.get("trigger", "?")
            onl_counters[trig] = onl_counters.get(trig, 0) + 1
            if e.get("generation") is not None:
                onl_gauges["online_generation"] = e["generation"]
            if e.get("rows_behind") is not None:
                onl_gauges["online_rows_behind"] = e["rows_behind"]
            for field, hname in (("train_s", "online_train_s"),
                                 ("publish_s", "online_publish_s")):
                if isinstance(e.get(field), (int, float)):
                    onl_hists.setdefault(hname,
                                         Histogram()).observe(e[field])
        if e["kind"] == "contrib":
            ctb_counters["contrib_calls"] = \
                ctb_counters.get("contrib_calls", 0) + 1
            ctb_counters["contrib_rows"] = \
                ctb_counters.get("contrib_rows", 0) + int(e.get("rows", 0))
            if isinstance(e.get("dt_s"), (int, float)) \
                    and e.get("bucket") is not None:
                ctb_hists.setdefault(
                    "contrib_latency_s_bucket_%d" % int(e["bucket"]),
                    Histogram()).observe(e["dt_s"])
        if e["kind"] == "predict_fallback" \
                and "contrib" in str(e.get("site", "")):
            ctb_counters["contrib_fallbacks"] = \
                ctb_counters.get("contrib_fallbacks", 0) + 1
        if e["kind"] == "ingest":
            phase = e.get("phase")
            if phase == "bin":
                ing_counters["ingest_chunks"] = \
                    ing_counters.get("ingest_chunks", 0) + 1
                rows = int(e.get("rows", 0))
                ing_counters["ingest_rows"] = \
                    ing_counters.get("ingest_rows", 0) + rows
                if isinstance(e.get("dt_s"), (int, float)) and e["dt_s"] > 0:
                    ing_hists.setdefault("ingest_chunk_rows_per_s",
                                         Histogram()).observe(
                        rows / e["dt_s"])
                if isinstance(e.get("stall_s"), (int, float)):
                    # per-chunk deltas, so summing never double-counts the
                    # cumulative total the phase="done" event also carries
                    ing_gauges["ingest_stall_ms"] = (
                        ing_gauges.get("ingest_stall_ms", 0.0)
                        + e["stall_s"] * 1000.0)
                if isinstance(e.get("rss_bytes"), (int, float)):
                    ing_gauges["host_rss_high_water_bytes"] = max(
                        int(ing_gauges.get("host_rss_high_water_bytes", 0)),
                        int(e["rss_bytes"]))
            elif phase == "done" \
                    and isinstance(e.get("rss_high_water"), (int, float)):
                ing_gauges["host_rss_high_water_bytes"] = max(
                    int(ing_gauges.get("host_rss_high_water_bytes", 0)),
                    int(e["rss_high_water"]))
        if e["kind"] == "quant":
            qnt_counters["quant_chunks"] = \
                qnt_counters.get("quant_chunks", 0) + 1
            qnt_counters["quant_iters"] = \
                qnt_counters.get("quant_iters", 0) + int(e.get("iters", 0))
            for field, gname in (("grad_levels", "quant_grad_levels"),
                                 ("hess_levels", "quant_hess_levels"),
                                 ("hist_channels", "quant_hist_channels")):
                if e.get(field) is not None:
                    qnt_gauges[gname] = e[field]
        if e["kind"] == "serve_batch" and e.get("contrib"):
            ctb_counters["serve_contrib_requests"] = \
                ctb_counters.get("serve_contrib_requests", 0) \
                + int(e.get("requests", 1))
        if e["kind"] == "serve_batch":
            m = str(e.get("model", "?"))
            # precision tier (round 20): pre-r20 event streams carry no
            # precision field — those batches were all exact by
            # construction, so the default reconstructs them faithfully
            p = str(e.get("precision", "exact"))
            for ck, n in (("serve_batches", 1),
                          ("serve_requests_model_%s" % m,
                           int(e.get("requests", 1))),
                          ("serve_rows_model_%s" % m, int(e.get("rows", 0))),
                          ("serve_requests_precision_%s" % p,
                           int(e.get("requests", 1))),
                          ("serve_rows_precision_%s" % p,
                           int(e.get("rows", 0))),
                          ("serve_single_row_fast",
                           1 if e.get("fast") else 0)):
                if n:
                    srv_counters[ck] = srv_counters.get(ck, 0) + n
            # lat_max_s (submit→complete of the batch's oldest request,
            # queue wait included) approximates request latency from
            # above; dispatch-only dt_s would understate it exactly when
            # queueing delay is the failure being investigated
            lat = e.get("lat_max_s", e.get("dt_s"))
            if isinstance(lat, (int, float)):
                h = srv_hists.setdefault("serve_latency_s_model_%s" % m,
                                         Histogram())
                for _ in range(max(int(e.get("requests", 1)), 1)):
                    h.observe(lat)
            if isinstance(e.get("queue_depth"), (int, float)):
                srv_hists.setdefault("serve_queue_depth",
                                     Histogram()).observe(e["queue_depth"])
            if isinstance(e.get("rows"), (int, float)) \
                    and isinstance(e.get("bucket"), (int, float)) \
                    and e["bucket"]:
                srv_hists.setdefault("serve_occupancy_model_%s" % m,
                                     Histogram()).observe(
                    e["rows"] / float(e["bucket"]))
        elif e["kind"] in ("serve_evict", "serve_swap", "serve_readmit",
                           "serve_reject"):
            ck = {"serve_evict": "serve_evictions",
                  "serve_swap": "serve_swaps",
                  "serve_readmit": "serve_readmits",
                  "serve_reject": "serve_rejected"}[e["kind"]]
            srv_counters[ck] = srv_counters.get(ck, 0) + 1
        elif e["kind"] == "serve_fail":
            srv_counters["serve_failed"] = (
                srv_counters.get("serve_failed", 0)
                + max(int(e.get("requests", 1)), 1))
        elif e["kind"] == "predict_fallback" and e.get("model"):
            # degraded dispatches carry the owning model: the post-mortem
            # reader needs the per-model fallback signal most of all
            ck = "predict_fallbacks_model_%s" % e["model"]
            srv_counters[ck] = srv_counters.get(ck, 0) + 1
    from lightgbm_tpu.obs.report import serving_block
    serving = serving_block(
        srv_counters, {},
        {k: h.summary() for k, h in srv_hists.items()})
    q_models = {}
    q_gens = {}
    # fold ranks: rows SUM across shards; the PSI/feature view comes from
    # the dominant (most-rows) shard — per-rank cumulative counters
    # cannot be exactly re-merged from breadcrumbs, and the dominant
    # shard is the honest approximation for a post-mortem
    by_gen = {}
    for (m, g, rank), e in sorted(drift.items(),
                                  key=lambda kv: str(kv[0])):
        agg = by_gen.setdefault((m, g), {"rows": 0, "ranks": 0,
                                         "best": None})
        agg["rows"] += int(e.get("rows", 0))
        agg["ranks"] += 1
        if agg["best"] is None \
                or int(e.get("rows", 0)) > int(agg["best"].get("rows", 0)):
            agg["best"] = e
    for (m, g), agg in sorted(by_gen.items()):
        e = agg["best"]
        try:
            feats = json.loads(e.get("top") or "[]")
        except ValueError:
            feats = []
        entry = {"generation": g, "rows": agg["rows"],
                 "psi_max": e.get("psi_max"),
                 "feature_max": e.get("feature_max"),
                 "score_psi": e.get("score_psi"),
                 "level": e.get("level"),
                 "rows_behind": e.get("rows_behind"),
                 "features": feats}
        if agg["ranks"] > 1:
            entry["ranks"] = agg["ranks"]
        q_gens.setdefault(m, {})[str(g)] = entry
        cur = q_models.get(m)
        if cur is None or g >= cur["generation"]:
            q_models[m] = entry
    quality = ({"models": q_models, "generations": q_gens}
               if q_models else None)
    from lightgbm_tpu.obs.report import (contrib_block, ingest_block,
                                         online_block)
    online = online_block(onl_counters, onl_gauges,
                          {k: h.summary() for k, h in onl_hists.items()})
    contrib = contrib_block(ctb_counters, {},
                            {k: h.summary() for k, h in ctb_hists.items()})
    if contrib is not None:
        contrib["recovered"] = True
    ingest = ingest_block(ing_counters, ing_gauges,
                          {k: h.summary() for k, h in ing_hists.items()})
    if ingest is not None:
        ingest["recovered"] = True
    from lightgbm_tpu.obs.report import quant_block
    quant = quant_block(qnt_counters, qnt_gauges, {})
    if quant is not None:
        quant["recovered"] = True
    compile_block = None
    if compile_keys:
        compile_block = {
            # the raw miss-bearing dispatch walls: an UPPER bound on the
            # compile seconds (no steady baseline survives a dead process)
            "compile_seconds_total": round(
                sum(v["compile_s"] for v in compile_keys.values()), 6),
            "compiles": sum(v["compiles"] for v in compile_keys.values()),
            "recovered": True,
            "keys": {k: {"compiles": v["compiles"],
                         "compile_s": round(v["compile_s"], 6)}
                     for k, v in sorted(compile_keys.items())},
        }
    alerts_block = None
    if alert_rules or alerts_fired:
        alerts_block = {
            "enabled": True, "recovered": True,
            "fired_total": alerts_fired,
            "series": [{"rule": r, "state": info.get("last_state"), **info}
                       for r, info in sorted(alert_rules.items())],
        }
    plan_block = None
    if plan_sites or plan_fallbacks:
        provs = {i.get("provenance") for i in plan_sites.values()}
        plan_block = {
            "provenance": ("pinned" if "pinned" in provs
                           else "tuned" if "tuned" in provs
                           else "analytic"),
            "sites": plan_sites,
            "cache_fallbacks": plan_fallbacks,
            "recovered": True,
        }
    return {
        **({"serving": serving} if serving else {}),
        **({"quality": quality} if quality else {}),
        **({"online": online} if online else {}),
        **({"contrib": contrib} if contrib else {}),
        **({"ingest": ingest} if ingest else {}),
        **({"quant": quant} if quant else {}),
        **({"compile": compile_block} if compile_block else {}),
        **({"alerts": alerts_block} if alerts_block else {}),
        **({"plan": plan_block} if plan_block else {}),
        **({"profiling": {"captures": captures, "recovered": True}}
           if captures else {}),
        "resilience": resilience,
        "metric": "telemetry_run", "unit": "row-trees/s", "value": None,
        "iterations": None, "wall_s": None,
        "recompiles": recompiles,
        "recompile_total": sum(recompiles.values()),
        "histograms": {k: h.summary() for k, h in sorted(hists.items())},
        "counters": {"events_" + k: v for k, v in sorted(counters.items())},
        "host_phases": {}, "gauges": {},
        "events": n_events,
    }


# ---- pod merge (--merge) ----

def find_shards(base: str):
    """[(rank, path)] for every ``<base>.rank<k>.jsonl`` shard, plus the
    unsharded base file itself (rank 0) when present — a run that started
    single-host and was resumed as a pod keeps both readable."""
    shards = []
    if os.path.exists(base):
        shards.append((0, base))
    for p in glob.glob(glob.escape(base) + ".rank*.jsonl"):
        tail = p[len(base) + len(".rank"):-len(".jsonl")]
        try:
            shards.append((int(tail), p))
        except ValueError:
            continue
    return sorted(shards)


def _shard_scan(path: str):
    """One streaming pass over a shard: (run_start ts or first ts, last
    ts, event count, span count, per-kind counts)."""
    from lightgbm_tpu.obs.registry import iter_events
    start = last = None
    n = spans = 0
    kinds = {}
    for e in iter_events(path):
        if start is None or e["kind"] == "run_start":
            start = e["ts"]
        last = e["ts"]
        n += 1
        if e["kind"] == "span":
            spans += 1
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
    return start, last, n, spans, kinds


def merge_report(base: str, trace_out=None, table=True) -> int:
    """The pod view: per-host breakdown (+ merged summary table) and the
    skew-aligned merged trace.  Returns 0, or 2 when no shards exist.

    Scans and trace pids are keyed by FILE, not rank: the unsharded base
    and a ``.rank0.jsonl`` shard can coexist (a run that started
    single-host and resumed as a pod), and they must not collide into one
    row/pid."""
    from lightgbm_tpu.obs.registry import iter_events
    from lightgbm_tpu.obs.report import human_table
    shards = find_shards(base)
    if not shards:
        print("no shards found for base %r (expected %s.rank<k>.jsonl)"
              % (base, base), file=sys.stderr)
        return 2
    # one entry per file: (pid, label, rank, path, scan)
    entries = []
    for pid, (rank, path) in enumerate(shards):
        label = ("base (unsharded)" if path == base else "rank %d" % rank)
        entries.append((pid, label, rank, path, _shard_scan(path)))
    starts = [e[4][0] for e in entries if e[4][0] is not None]
    t0 = min(starts) if starts else 0.0
    print("pod view: %d shard(s) for %s" % (len(entries), base))
    print("  %-16s %-8s %-7s %-10s %-10s %s"
          % ("shard", "events", "spans", "start+s", "wall_s", "file"))
    for pid, label, rank, path, (start, last, n, spans, _) in entries:
        print("  %-16s %-8d %-7d %-10s %-10s %s"
              % (label, n, spans,
                 "-" if start is None else "%.3f" % (start - t0),
                 "-" if start is None or last is None
                 else "%.3f" % (last - start),
                 os.path.basename(path)))
    if table:
        def all_events():
            for _, _, _, path, _ in entries:
                for e in iter_events(path):
                    yield e
        print(human_table(summary_from_events(all_events())))
    if trace_out:
        n = write_chrome_trace(trace_out, (
            (pid, (t0 - scan[0]) if scan[0] else 0.0,
             iter_events(path), label)
            for pid, label, _, path, scan in entries))
        print("wrote %s (%d trace events, %d shards, run_start "
              "skew-aligned)" % (trace_out, n, len(entries)),
              file=sys.stderr)
    return 0


def main(argv=None):
    args = build_parser().parse_args(argv)
    from lightgbm_tpu.obs.registry import iter_events
    from lightgbm_tpu.obs.report import human_table
    if args.merge:
        return merge_report(args.jsonl, trace_out=args.trace,
                            table=not args.no_table)
    if args.trace:
        n = write_chrome_trace(
            args.trace, [(0, 0.0, iter_events(args.jsonl), None)])
        print("wrote %s (%d trace events)" % (args.trace, n),
              file=sys.stderr)
    if not args.no_table:
        summary_path = args.summary
        if summary_path is None:
            cand = args.jsonl + ".summary.json"
            summary_path = cand if os.path.exists(cand) else None
        if summary_path:
            with open(summary_path) as fh:
                summary = json.load(fh)
        else:
            summary = summary_from_events(iter_events(args.jsonl))
        print(human_table(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
