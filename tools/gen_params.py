#!/usr/bin/env python
"""Generate lightgbm_tpu/_params_meta.py from the reference config.h doc comments.

The reference generates its parameter parser + docs from structured doc comments in
``include/LightGBM/config.h`` (see reference ``helpers/parameter_generator.py`` and the
generated ``src/io/config_auto.cpp``).  We follow the same single-source-of-truth pattern:
this script extracts the *data* (names, types, defaults, aliases, check constraints, enum
options) and emits a Python module consumed by ``lightgbm_tpu.config``.

Run:  python tools/gen_params.py [config.h path] > lightgbm_tpu/_params_meta.py

``--check`` verifies the committed ``lightgbm_tpu/_params_meta.py`` is in
sync with this generator (and with config.h when the reference tree is
available), exiting nonzero on drift — wired into the tools smoke test so
a hand-edited or stale params module is un-shippable.
"""
import os
import re
import sys

CTYPE_MAP = {
    "int": "int",
    "double": "float",
    "bool": "bool",
    "std::string": "str",
    "std::vector<int>": "list_int",
    "std::vector<int8_t>": "list_int",
    "std::vector<int32_t>": "list_int",
    "std::vector<double>": "list_float",
    "std::vector<std::string>": "list_str",
    "std::vector<std::vector<double>>": "matrix_float",
}

DECL_RE = re.compile(
    r"^\s*(int|double|bool|std::string|std::vector<int>|std::vector<int8_t>|"
    r"std::vector<int32_t>|std::vector<double>|"
    r"std::vector<std::string>|std::vector<std::vector<double>>)\s+(\w+)\s*(?:=\s*(.*?))?;"
)


def parse_default(pytype, raw, consts):
    if raw is None or raw == "":
        return {"int": 0, "float": 0.0, "bool": False, "str": "",
                "list_int": [], "list_float": [], "list_str": [], "matrix_float": []}[pytype]
    raw = raw.strip()
    if raw in consts:
        raw = consts[raw]
    if pytype == "int":
        return int(raw)
    if pytype == "float":
        return float(raw.rstrip("f"))
    if pytype == "bool":
        return raw == "true"
    if pytype == "str":
        return raw.strip('"')
    return []


def main(path):
    text = open(path).read()
    consts = {}
    for m in re.finditer(r"const\s+int\s+(\w+)\s*=\s*(\d+);", text):
        consts[m.group(1)] = m.group(2)

    # parameters live inside nested `#pragma region X Parameters` blocks
    # (config.h:81-899); declarations outside any named region are internal
    # members / helper-function locals, not user parameters
    section = "Core"
    region_stack = []
    params = []
    pending = {"aliases": [], "checks": [], "options": [], "section": section,
               "doc_only": False, "default_doc": None}

    def reset():
        return {"aliases": [], "checks": [], "options": [], "section": section,
                "doc_only": False, "default_doc": None}

    for line in text.splitlines():
        sm = re.match(r"\s*#pragma region (.*)", line)
        if sm:
            region_stack.append(sm.group(1))
            section = sm.group(1).replace(" Parameters", "")
            pending = reset()
            continue
        if re.match(r"\s*#pragma endregion", line):
            if region_stack:
                region_stack.pop()
            pending = reset()
            continue
        cm = re.match(r"\s*//\s*(\[doc-only\]|\w+)\s*(?:=\s*(.*))?$", line)
        if cm:
            key, val = cm.group(1), (cm.group(2) or "").strip()
            if key == "[doc-only]":
                pending["doc_only"] = True
            elif key == "alias":
                pending["aliases"] += [a.strip() for a in val.split(",") if a.strip()]
            elif key == "check":
                pending["checks"].append(val)
            elif key == "options":
                pending["options"] = [o.strip() for o in val.split(",")]
            elif key == "default":
                pending["default_doc"] = val
            continue
        dm = DECL_RE.match(line)
        if dm:
            if not region_stack or region_stack[-1] == "Parameters":
                pending = reset()
                continue
            ctype, name, rawdef = dm.groups()
            pytype = CTYPE_MAP[ctype]
            params.append({
                "name": name,
                "type": pytype,
                "default": parse_default(pytype, rawdef, consts),
                "aliases": pending["aliases"],
                "checks": pending["checks"],
                "options": pending["options"],
                "section": pending["section"],
            })
            pending = reset()
        elif line.strip() and not line.strip().startswith("//"):
            pending = reset()

    out = sys.stdout
    out.write('"""Parameter metadata extracted from the reference config '
              "(include/LightGBM/config.h doc comments).\n\n"
              "AUTO-GENERATED by tools/gen_params.py -- do not edit by hand.\n"
              '"""\n\n')
    out.write("PARAMS = [\n")
    for p in params:
        out.write("    %r,\n" % (p,))
    out.write("]\n")
    out.write(TAIL)
    return 0


# TPU-runtime extension tail, appended VERBATIM to the generated module
# (and compared verbatim by --check)
TAIL = """
# TPU-runtime extensions (not in the reference config.h; kept separate so a
# regeneration of the extracted block above preserves them — tools/gen_params.py
# appends this tail verbatim):
# - snapshot_keep: bounded retention for snapshot_freq checkpoint/model files
#   (newest N kept; <= 0 keeps everything)
# - nan_policy: non-finite gradient/hessian/score guard policy
# - telemetry_out: JSONL event-sink path for the obs subsystem; empty (the
#   default) keeps telemetry fully off — the hot loops make zero telemetry
#   calls.  A run with it set also writes <path>.summary.json at the end.
# - telemetry_freq: per-iteration telemetry event cadence (record every Nth
#   iteration); only consulted when telemetry_out is set
# - preemption_checkpoint: install the SIGTERM/SIGINT handler (resilience.py)
#   that makes task=train exit via an emergency checkpoint + EXIT_PREEMPTED
# - watchdog_timeout_s: abort a dispatch/collective that makes no progress
#   for this many seconds, dumping a diagnostic artifact first (0 = off)
# - io_retry_attempts / io_retry_backoff_s: bounded jittered retry policy
#   for transient checkpoint/model/telemetry file-I/O faults
# - tree_grow_mode: leaf (reference leaf-wise growth, one fused split-kernel
#   launch per grown leaf) or level (max_depth-driven BFS: all frontier
#   leaves of a level split by at most one multi-window launch per kernel
#   size class — <= depth * classes launches per tree; needs the fused TPU
#   path and falls back to leaf with a warning otherwise)
# - trees_per_chunk: boosting iterations grouped per fused-scan step so
#   several small trees share one scan step's dispatch cost; results are
#   bit-exact vs 1
# - max_batch_wait_us: serving-tier coalescing window — how long the
#   continuous-batching scheduler holds an open batch for compatible
#   requests before padding to the shape-bucket ladder and dispatching
# - serve_residency_budget_mb: multi-model residency budget (stacked device
#   ensembles, LRU-evicted past it; <= 0 = unlimited)
# - serve_single_row_fast: route batch-size-1 requests through the compiled
#   in-process if/else chain (model_codegen.compile_single_row) instead of
#   a padded device dispatch; bit-exact vs predict_blocked
# - predict_precision: serving/predict tier — exact (the default: bit-exact
#   f32 scores) or bf16 (lossy: bf16 leaf values + accumulate; leaf routing
#   stays bit-exact).  The bf16 tier's measured score error is budgeted
#   (PERF_BUDGETS.json bf16_max_score_delta, tests/test_precision.py); contrib
#   requests have no lossy tier
# - metrics_port: serve the live observability plane (obs/exporter.py:
#   /metrics Prometheus text, /healthz, /summary.json) from an HTTP
#   listener on this port for the duration of the run; 0 (the default)
#   starts no listener thread at all
# - metrics_addr: bind address for the metrics_port listener (default
#   loopback; set 0.0.0.0 to scrape from another host)
# - quality_monitor: model-quality plane (obs/quality.py) — per-feature
#   drift PSI/JS, score-distribution PSI and generation/freshness gauges
#   over served/scored traffic; only active while a telemetry run is
#   (telemetry_out / metrics_port / obs.configure), costs nothing
#   otherwise
# - quality_top_k: how many per-feature drift series each model exposes
#   on /metrics and in the summary (ranked by importance x PSI; bounds
#   exposition size on wide-F models)
# - alert_rules: JSON file of live SLO alert rules (obs/alerts.py; the
#   PERF_BUDGETS.json "alerts" list shape) evaluated periodically over
#   the run's metrics with fast/slow burn-rate windows, served at
#   /alerts and emitted as kind="alert" events; empty (the default)
#   installs no engine
# - alert_interval_s: evaluation period of the alert engine
# - flight_recorder: arm ONE automatic jax.profiler capture per run
#   (obs/profiling.py), fired by the first watchdog stall or the first
#   live SLO alert; artifacts land under <telemetry_out>.profiles/
# - online_* (lightgbm_tpu/online, task=online / lgb.serve_and_train):
#   online_rounds = boosting iterations per retrain cycle (extend mode);
#   online_update = extend (warm-start continuation: more trees per
#   generation) or refit (re-fit leaf values on the window — structure
#   unchanged, republish is a pure jit-cache hit);
#   online_min_rows / online_interval_s = cadence triggers (fire every N
#   fresh rows / every T seconds while fresh rows exist; 0 = off);
#   online_drift_trigger = fire when the quality plane's per-model drift
#   level reads "alert" (the round-15 snapshot()["models"][m]["level"]
#   hook); online_max_rows_behind / online_max_seconds_behind = freshness
#   SLO caps (0 = off); online_buffer_rows = bounded ingest buffer
#   (drop-oldest past it, counted); online_window_rows = max rows per
#   training window (0 = the whole buffer); online_poll_s = trigger poll
#   period of the trainer thread; online_feed = CLI task=online labeled
#   feed file replayed as ingest + requests
# - plan_cache (lightgbm_tpu/plan, round 18): path to the persisted
#   tuned-plan cache the kernel planner consults (written by
#   tools/bench_autotune.py).  Empty = probe the default location next to
#   the XLA compilation cache; missing/corrupt/stale caches degrade to
#   analytic plans with one warning + the plan_cache_fallbacks counter
# - hist_precision (core/quant.py round 22): exact keeps the byte-identical
#   f32 hi/lo histogram default; quantized stochastically rounds grad/hess
#   to low-bit integers per boosting iteration (stateless hash keyed like
#   the bagging mask) so histogram kernels run a 2-row integer operand and
#   the parallel learners' hist collective rides a half-width payload —
#   lossy, budgeted by PERF_BUDGETS.json quant_* lines
# - data_chunk_rows (io/loader.py round 21): streaming two-pass ingestion —
#   text data is read, sampled and binned in chunks of this many rows, so
#   peak host RSS is bounded by the chunk (plus the bin sample and the
#   packed store) instead of the raw f64 matrix; bit-identical to the
#   in-memory loader.  0 (the default) keeps the one-shot parse;
#   incompatible with pre_partition=true (the file would be double-sharded)
# - ingest_pipeline_depth: prefetch depth of the streaming loader's
#   producer thread (parse of chunk k+depth overlaps binning of chunk k;
#   1 disables overlap, the PipelineReader role)
PARAMS += [
    {'name': 'snapshot_keep', 'type': 'int', 'default': 3, 'aliases': ['checkpoint_keep', 'save_keep'], 'checks': [], 'options': [], 'section': 'IO'},
    {'name': 'nan_policy', 'type': 'str', 'default': 'raise', 'aliases': ['non_finite_policy'], 'checks': [], 'options': ['raise', 'skip_iter', 'clip'], 'section': 'Learning Control'},
    {'name': 'telemetry_out', 'type': 'str', 'default': '', 'aliases': ['telemetry_output', 'telemetry_file'], 'checks': [], 'options': [], 'section': 'IO'},
    {'name': 'telemetry_freq', 'type': 'int', 'default': 1, 'aliases': ['telemetry_frequency'], 'checks': ['>0'], 'options': [], 'section': 'IO'},
    {'name': 'preemption_checkpoint', 'type': 'bool', 'default': False, 'aliases': ['preempt_checkpoint'], 'checks': [], 'options': [], 'section': 'IO'},
    {'name': 'watchdog_timeout_s', 'type': 'float', 'default': 0.0, 'aliases': ['watchdog_timeout'], 'checks': ['>=0.0'], 'options': [], 'section': 'IO'},
    {'name': 'io_retry_attempts', 'type': 'int', 'default': 3, 'aliases': [], 'checks': ['>=1'], 'options': [], 'section': 'IO'},
    {'name': 'io_retry_backoff_s', 'type': 'float', 'default': 0.05, 'aliases': [], 'checks': ['>=0.0'], 'options': [], 'section': 'IO'},
    {'name': 'tree_grow_mode', 'type': 'str', 'default': 'leaf', 'aliases': ['grow_mode', 'grow_policy'], 'checks': [], 'options': ['leaf', 'level'], 'section': 'Learning Control'},
    {'name': 'trees_per_chunk', 'type': 'int', 'default': 1, 'aliases': [], 'checks': ['>=1'], 'options': [], 'section': 'Learning Control'},
    {'name': 'hist_precision', 'type': 'str', 'default': 'exact', 'aliases': ['quantized_grad'], 'checks': [], 'options': ['exact', 'quantized'], 'section': 'Learning Control'},
    {'name': 'max_batch_wait_us', 'type': 'int', 'default': 200, 'aliases': ['serve_batch_wait_us'], 'checks': ['>=0'], 'options': [], 'section': 'Serving'},
    {'name': 'serve_residency_budget_mb', 'type': 'float', 'default': 1024.0, 'aliases': ['residency_budget_mb'], 'checks': [], 'options': [], 'section': 'Serving'},
    {'name': 'serve_single_row_fast', 'type': 'bool', 'default': False, 'aliases': ['single_row_fast'], 'checks': [], 'options': [], 'section': 'Serving'},
    {'name': 'predict_precision', 'type': 'str', 'default': 'exact', 'aliases': ['serve_precision'], 'checks': [], 'options': ['exact', 'bf16'], 'section': 'Serving'},
    {'name': 'metrics_port', 'type': 'int', 'default': 0, 'aliases': ['telemetry_port'], 'checks': ['>=0', '<=65535'], 'options': [], 'section': 'IO'},
    {'name': 'metrics_addr', 'type': 'str', 'default': '127.0.0.1', 'aliases': ['telemetry_addr'], 'checks': [], 'options': [], 'section': 'IO'},
    {'name': 'quality_monitor', 'type': 'bool', 'default': True, 'aliases': ['drift_monitor'], 'checks': [], 'options': [], 'section': 'IO'},
    {'name': 'quality_top_k', 'type': 'int', 'default': 20, 'aliases': ['drift_top_k'], 'checks': ['>0'], 'options': [], 'section': 'IO'},
    {'name': 'alert_rules', 'type': 'str', 'default': '', 'aliases': ['alert_rules_file', 'slo_rules'], 'checks': [], 'options': [], 'section': 'IO'},
    {'name': 'alert_interval_s', 'type': 'float', 'default': 1.0, 'aliases': ['alert_eval_s'], 'checks': ['>0.0'], 'options': [], 'section': 'IO'},
    {'name': 'flight_recorder', 'type': 'bool', 'default': False, 'aliases': ['profile_on_incident'], 'checks': [], 'options': [], 'section': 'IO'},
    {'name': 'online_rounds', 'type': 'int', 'default': 10, 'aliases': ['online_trees_per_cycle'], 'checks': ['>=1'], 'options': [], 'section': 'Online'},
    {'name': 'online_update', 'type': 'str', 'default': 'extend', 'aliases': ['online_mode'], 'checks': [], 'options': ['extend', 'refit'], 'section': 'Online'},
    {'name': 'online_min_rows', 'type': 'int', 'default': 4096, 'aliases': ['online_cadence_rows'], 'checks': ['>=0'], 'options': [], 'section': 'Online'},
    {'name': 'online_interval_s', 'type': 'float', 'default': 0.0, 'aliases': ['online_cadence_s'], 'checks': ['>=0.0'], 'options': [], 'section': 'Online'},
    {'name': 'online_drift_trigger', 'type': 'bool', 'default': True, 'aliases': ['online_drift_refit'], 'checks': [], 'options': [], 'section': 'Online'},
    {'name': 'online_max_rows_behind', 'type': 'int', 'default': 0, 'aliases': [], 'checks': ['>=0'], 'options': [], 'section': 'Online'},
    {'name': 'online_max_seconds_behind', 'type': 'float', 'default': 0.0, 'aliases': [], 'checks': ['>=0.0'], 'options': [], 'section': 'Online'},
    {'name': 'online_buffer_rows', 'type': 'int', 'default': 1048576, 'aliases': [], 'checks': ['>=1'], 'options': [], 'section': 'Online'},
    {'name': 'online_window_rows', 'type': 'int', 'default': 0, 'aliases': [], 'checks': ['>=0'], 'options': [], 'section': 'Online'},
    {'name': 'online_poll_s', 'type': 'float', 'default': 0.25, 'aliases': [], 'checks': ['>0.0'], 'options': [], 'section': 'Online'},
    {'name': 'online_feed', 'type': 'str', 'default': '', 'aliases': ['online_feed_file'], 'checks': [], 'options': [], 'section': 'Online'},
    {'name': 'plan_cache', 'type': 'str', 'default': '', 'aliases': ['kernel_plan_cache', 'plan_cache_file'], 'checks': [], 'options': [], 'section': 'IO'},
    {'name': 'data_chunk_rows', 'type': 'int', 'default': 0, 'aliases': ['ingest_chunk_rows', 'stream_chunk_rows'], 'checks': ['>=0'], 'options': [], 'section': 'IO'},
    {'name': 'ingest_pipeline_depth', 'type': 'int', 'default': 2, 'aliases': ['ingest_prefetch_depth'], 'checks': ['>=1'], 'options': [], 'section': 'IO'},
]
"""


def check(meta_path, config_h):
    """--check: 0 when ``meta_path`` is in sync with this generator (and
    with config.h when the reference tree is available), nonzero naming
    the drift."""
    try:
        current = open(meta_path).read()
    except OSError as exc:
        print("gen_params --check: cannot read %s (%s)" % (meta_path, exc))
        return 2
    if not current.endswith(TAIL):
        print("gen_params --check: %s is STALE — its TPU-extension tail "
              "does not match tools/gen_params.py; regenerate with "
              "`python tools/gen_params.py > %s`" % (meta_path, meta_path))
        return 1
    names = re.findall(r"'name': '(\w+)'", current)
    dupes = sorted({n for n in names if names.count(n) > 1})
    if dupes:
        print("gen_params --check: %s declares duplicate params: %s"
              % (meta_path, ", ".join(dupes)))
        return 1
    if os.path.exists(config_h):
        import io as _io
        buf = _io.StringIO()
        old_stdout = sys.stdout
        sys.stdout = buf
        try:
            main(config_h)
        finally:
            sys.stdout = old_stdout
        if buf.getvalue() != current:
            print("gen_params --check: %s is STALE vs %s; regenerate with "
                  "`python tools/gen_params.py %s > %s`"
                  % (meta_path, config_h, config_h, meta_path))
            return 1
    print("gen_params --check: %s is in sync" % meta_path)
    return 0


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(
        description="generate _params_meta.py from the reference config.h")
    ap.add_argument("config_h", nargs="?",
                    default="/root/reference/include/LightGBM/config.h")
    ap.add_argument("--check", action="store_true",
                    help="verify lightgbm_tpu/_params_meta.py matches this "
                         "generator; exit nonzero on drift")
    ap.add_argument("--meta", default=os.path.normpath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "lightgbm_tpu",
        "_params_meta.py")))
    args = ap.parse_args()
    if args.check:
        sys.exit(check(args.meta, args.config_h))
    sys.exit(main(args.config_h))
