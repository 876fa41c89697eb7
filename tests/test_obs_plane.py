"""Live observability plane (round 14): HTTP exporter (/metrics /healthz
/summary.json), request-scoped spans, rank-aware pod shard sinks +
obs_report --merge, the streaming event reader, histogram reservoir
semantics, and the perf gate."""
import json
import os
import random
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from lightgbm_tpu import obs, resilience
from lightgbm_tpu.obs import spans
from lightgbm_tpu.obs.exporter import (MetricsExporter, health_snapshot,
                                       render_prometheus, start_exporter)
from lightgbm_tpu.obs.registry import (Histogram, Telemetry, iter_events,
                                       read_events, validate_event)
from lightgbm_tpu.obs.report import finalize_run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tools():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
        import perf_gate
    finally:
        sys.path.pop(0)
    return obs_report, perf_gate


@pytest.fixture(autouse=True)
def _clean_slate():
    obs.disable()
    resilience.clear_preemption()
    resilience.clear_stall()
    yield
    obs.disable()
    resilience.clear_preemption()
    resilience.clear_stall()


def _toy_booster(n=2048, num_iterations=8, seed=0, **params):
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, 8))
    y = X[:, 0] * 1.5 + np.sin(X[:, 1]) + rng.normal(scale=0.1, size=n)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=16)
    cfg = Config(objective="regression", num_leaves=15, min_data_in_leaf=5,
                 num_iterations=num_iterations, **params)
    return GBDT(cfg, ds, create_objective("regression", cfg)), X, y


def _get(exp, path, timeout=10):
    return urllib.request.urlopen(
        "http://127.0.0.1:%d%s" % (exp.port, path), timeout=timeout).read(
    ).decode()


# ---- exporter: /metrics ----

def test_metrics_prometheus_from_live_serving(tmp_path):
    """The acceptance pin's /metrics half: a serving process under load
    exposes well-formed Prometheus text with per-model serve counters and
    the run-scoped recompile gauge at 0 (warmup compiled, steady state
    did not)."""
    from lightgbm_tpu.serving import Server
    booster, X, _ = _toy_booster(num_iterations=4)
    booster.train_chunk(4)
    with Server(max_batch_wait_us=0) as srv:
        srv.register("prod", booster)
        srv.predict("prod", X[:64])  # warmup compiles OUTSIDE the run
        tele = obs.configure(out=str(tmp_path / "srv.jsonl"), freq=1)
        exp = start_exporter(tele, port=0)
        futs = [srv.submit("prod", X[i:i + 16]) for i in range(0, 320, 16)]
        for f in futs:
            f.result()
        text = _get(exp, "/metrics")
        obs.disable()
    assert "# TYPE lgbm_tpu_serve_requests_model_prod_total counter" in text
    assert "lgbm_tpu_serve_requests_model_prod_total 20" in text
    assert "lgbm_tpu_serve_rows_model_prod_total 320" in text
    assert "lgbm_tpu_run_recompiles 0" in text, text
    assert 'lgbm_tpu_serve_latency_s_model_prod{quantile="0.99"}' in text
    # every exposition line is either a comment or name[{labels}] value
    for line in text.strip().splitlines():
        assert line.startswith("#") or len(line.rsplit(None, 1)) == 2, line


def test_metrics_no_duplicate_metric_names(tmp_path):
    """A registry that mirrored the always-on counters (every telemetry
    run does: recompile/io-retry events bump registry counters of the
    same names) must not render the metric name twice — duplicate names
    are invalid exposition and fail the entire Prometheus scrape."""
    tele = obs.configure(freq=1)
    for name in ("recompiles", "io_retries", "predict_fallbacks",
                 "tree_kernel_launches", "my_counter"):
        tele.counter(name).inc(3)
    exp = start_exporter(tele, port=0)
    text = _get(exp, "/metrics")
    obs.disable()
    # a metric name may have many labeled samples, but only ONE # TYPE
    # declaration and no repeated (name, labels) sample key
    types = [line for line in text.splitlines() if line.startswith("# TYPE")]
    assert len(types) == len(set(types)), \
        "duplicate # TYPE declarations: %r" % sorted(
            t for t in types if types.count(t) > 1)
    keys = [line.rsplit(None, 1)[0] for line in text.splitlines()
            if line and not line.startswith("#")]
    dupes = {k for k in keys if keys.count(k) > 1}
    assert not dupes, "duplicate sample keys in exposition: %r" % dupes
    # the labeled always-on form survives; the plain registry echo is
    # dropped; non-mirrored registry counters render normally
    assert "lgbm_tpu_my_counter_total 3" in text
    assert text.count("# TYPE lgbm_tpu_io_retries_total") == 1


def test_healthz_two_servers_both_visible():
    """Two Servers in one process: the second must not evict the first's
    /healthz provider, and closing one leaves the other reporting."""
    from lightgbm_tpu.serving import Server
    booster, X, _ = _toy_booster(num_iterations=2)
    booster.train_chunk(2)
    a = Server(max_batch_wait_us=0)
    b = Server(max_batch_wait_us=0)
    try:
        h = health_snapshot()
        assert "serving" in h and "serving#2" in h
        b.close()
        h = health_snapshot()
        assert "serving" in h and "serving#2" not in h
        assert h["serving"]["draining"] is False  # a is alive and visible
    finally:
        a.close()
        b.close()
    assert "serving" not in health_snapshot()


def test_metrics_renders_always_on_counters():
    obs.recompile.record("gate_fn", "b7", 2)
    snap = {"counters": {}, "gauges": {}, "histograms": {}}
    text = render_prometheus(snap)
    assert 'lgbm_tpu_recompiles_total{fn="gate_fn",bucket="b7"} 2' in text
    assert "# TYPE lgbm_tpu_io_retries_total counter" in text
    assert "# TYPE lgbm_tpu_predict_fallbacks_total counter" in text
    assert "# TYPE lgbm_tpu_tree_kernel_launches_total counter" in text


def test_exporter_summary_json_is_live(tmp_path):
    tele = obs.configure(out=str(tmp_path / "t.jsonl"), freq=1)
    exp = start_exporter(tele, port=0)
    tele.gauge("train_rows").set(77)
    s = json.loads(_get(exp, "/summary.json"))
    assert s["metric"] == "telemetry_run" and s["rows"] == 77
    tele.gauge("train_rows").set(99)
    assert json.loads(_get(exp, "/summary.json"))["rows"] == 99
    obs.disable()


def test_exporter_unknown_path_404(tmp_path):
    tele = obs.configure(freq=1)
    exp = start_exporter(tele, port=0)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(exp, "/bogus")
    assert ei.value.code == 404
    obs.disable()


def test_exporter_stops_with_telemetry_close():
    tele = obs.configure(freq=1)
    exp = start_exporter(tele, port=0)
    assert any(t.name == "lgbm-tpu-metrics" for t in threading.enumerate())
    obs.disable()
    assert not any(t.name == "lgbm-tpu-metrics"
                   for t in threading.enumerate())
    with pytest.raises(urllib.error.URLError):
        _get(exp, "/healthz", timeout=1)


def test_exporter_idempotent_start(tmp_path):
    tele = obs.configure(freq=1)
    exp1 = start_exporter(tele, port=0)
    exp2 = start_exporter(tele, port=0)
    assert exp1 is exp2
    obs.disable()


# ---- exporter: /healthz ----

def test_healthz_ok_then_draining_on_preemption():
    tele = obs.configure(freq=1)
    exp = start_exporter(tele, port=0)
    h = json.loads(_get(exp, "/healthz"))
    assert h["status"] == "ok" and h["preemption_requested"] is False
    resilience.request_preemption()
    h = json.loads(_get(exp, "/healthz"))
    assert h["status"] == "draining" and h["preemption_requested"] is True
    resilience.clear_preemption()
    assert json.loads(_get(exp, "/healthz"))["status"] == "ok"
    obs.disable()


def test_healthz_serving_queue_depth_provider():
    from lightgbm_tpu.serving import Server
    booster, X, _ = _toy_booster(num_iterations=2)
    booster.train_chunk(2)
    srv = Server(max_batch_wait_us=0)
    try:
        srv.register("m", booster)
        srv.predict("m", X[:4])
        h = health_snapshot()
        assert "serving" in h and h["serving"]["queue_depth"] == 0
        assert h["serving"]["completed"] >= 1
        assert h["queue_depth"] == 0  # hoisted headline field
        assert h["serving"]["draining"] is False
    finally:
        srv.close()
    # close unregisters: a dead server must not haunt /healthz
    assert "serving" not in health_snapshot()


def test_healthz_watchdog_and_checkpoint_age(tmp_path):
    from lightgbm_tpu.checkpoint import last_checkpoint_time
    resilience.start_watchdog(30.0, abort=False)
    try:
        h = health_snapshot()
        assert h["watchdog"]["active"] is True
        assert h["watchdog"]["fired"] is False
        assert h["watchdog"]["open_sections"] == 0
        with resilience.watch("probe_section", compile_key=1):
            h2 = health_snapshot()
            assert h2["watchdog"]["open_sections"] == 1
            assert h2["watchdog"]["oldest_open_s"] >= 0.0
    finally:
        resilience.stop_watchdog()
    booster, _, _ = _toy_booster(num_iterations=2, snapshot_keep=0)
    booster.train_chunk(2)
    booster.save_checkpoint(str(tmp_path / "m.txt"))
    assert last_checkpoint_time() is not None
    h = health_snapshot()
    assert h["last_checkpoint_age_s"] is not None
    assert h["last_checkpoint_age_s"] < 60.0


def test_healthz_stalled_gives_503():
    tele = obs.configure(freq=1)
    exp = start_exporter(tele, port=0)
    fired = threading.Event()
    resilience.start_watchdog(0.05, abort=False,
                              on_stall=lambda d: fired.set(),
                              first_dispatch_grace=1.0)
    try:
        wd = resilience.watchdog_active()
        with wd.section("stuck", compile_key="k"):
            wd._completed.add(("stuck", "k"))  # skip compile grace
            assert fired.wait(timeout=5.0)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(exp, "/healthz")
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "stalled"
    finally:
        resilience.stop_watchdog()
        obs.disable()


def test_exporter_scrape_does_not_block_training(tmp_path):
    """Concurrency pin: continuous scraping while fused chunks dispatch —
    every scrape answers and training finishes (handlers only read
    snapshots; no lock is held across a dispatch)."""
    booster, _, _ = _toy_booster(num_iterations=16)
    booster.train_chunk(4)  # compile outside the timed loop
    tele = obs.configure(out=str(tmp_path / "c.jsonl"), freq=1)
    exp = start_exporter(tele, port=0)
    stop = threading.Event()
    scrapes = []
    errors = []

    def scraper():
        while not stop.is_set():
            try:
                scrapes.append(_get(exp, "/metrics"))
                json.loads(_get(exp, "/healthz"))
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

    th = threading.Thread(target=scraper)
    th.start()
    try:
        for _ in range(3):
            booster.train_chunk(4)
    finally:
        stop.set()
        th.join(timeout=10)
    obs.disable()
    assert not errors, errors[:3]
    assert scrapes and "lgbm_tpu_chunk_dispatch_s_count" in scrapes[-1]


# ---- spans ----

def test_span_events_validate_and_nest(tmp_path):
    path = str(tmp_path / "sp.jsonl")
    tele = obs.configure(out=path, freq=1)
    with spans.span("outer", phase="x"):
        with spans.span("inner"):
            time.sleep(0.01)
    obs.disable()
    evs = [e for e in read_events(path) if e["kind"] == "span"]
    for e in evs:
        validate_event(e)  # scalar-field schema accepts spans unchanged
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner"}
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner["trace_id"] == outer["trace_id"]
    assert inner["parent_id"] == outer["span_id"]
    assert outer["parent_id"] is None
    assert outer["dur_s"] >= inner["dur_s"] >= 0.01
    assert outer["t0"] <= inner["t0"]
    assert outer["phase"] == "x"


def test_span_off_keeps_the_memory_record_only(monkeypatch):
    """The telemetry-off pin, restated: with no run active a span draws no
    id, touches no Telemetry and writes nothing; the bounded in-memory
    record (always on, like obs.recompile) is all it does."""
    assert obs.active() is None
    drawn = []
    monkeypatch.setattr(spans, "new_id", lambda: drawn.append(1) or "x")
    spans.reset()
    with spans.span("a") as outer:
        with spans.span("b", k=1):
            pass
    assert not drawn and outer.tele is None and outer.span_id is None
    a, b = spans.records("a")[0], spans.records("b")[0]
    assert b["parent"] == a["id"] and a["parent"] == 0
    assert a["start"] <= b["start"] <= b["end"] <= a["end"]


def test_serving_request_span_lifeline(tmp_path):
    """Acceptance pin: a single request's lifeline carries DISTINCT
    queue-wait and dispatch spans under one trace, and the Chrome-trace
    conversion puts them on one lane as nested slices."""
    from lightgbm_tpu.serving import Server
    obs_report, _ = _tools()
    booster, X, _ = _toy_booster(num_iterations=4)
    booster.train_chunk(4)
    path = str(tmp_path / "serve.jsonl")
    with Server(max_batch_wait_us=2000) as srv:
        srv.register("m", booster)
        srv.predict("m", X[:8])  # warm outside the run
        tele = obs.configure(out=path, freq=1)
        srv.predict("m", X[:8])
    # close() joined the dispatcher: its post-completion span block is
    # done before the run is read back
    obs.disable()
    evs = [e for e in read_events(path) if e["kind"] == "span"]
    traces = {}
    for e in evs:
        traces.setdefault(e["trace_id"], {})[e["name"]] = e
    req_traces = [t for t in traces.values() if "serve_request" in t]
    assert len(req_traces) == 1
    t = req_traces[0]
    assert {"serve_request", "queue_wait", "coalesce", "dispatch"} <= set(t)
    root = t["serve_request"]
    for child in ("queue_wait", "coalesce", "dispatch"):
        assert t[child]["parent_id"] == root["span_id"]
    # queue wait strictly precedes dispatch; both nest inside the request
    assert t["queue_wait"]["t0"] + t["queue_wait"]["dur_s"] \
        <= t["dispatch"]["t0"] + 1e-6
    assert root["t0"] <= t["queue_wait"]["t0"] + 1e-6
    assert root["t0"] + root["dur_s"] >= t["dispatch"]["t0"] \
        + t["dispatch"]["dur_s"] - 1e-6
    # Chrome-trace conversion: all four on ONE lane (nested lifeline)
    lanes = obs_report._SpanLanes()
    slices = [obs_report.event_to_trace(e, lanes) for e in t.values()]
    assert all(s["ph"] == "X" for s in slices)
    assert len({s["tid"] for s in slices}) == 1
    assert {s["name"] for s in slices} == set(t)


def test_serving_spans_sampled_by_telemetry_freq(tmp_path):
    """telemetry_freq > 1 samples the per-request lifelines (every Nth
    batch) so high-qps tracing stays off the dispatch critical path; the
    serve_batch accounting events keep full cadence."""
    from lightgbm_tpu.serving import Server
    booster, X, _ = _toy_booster(num_iterations=4)
    booster.train_chunk(4)
    path = str(tmp_path / "sampled.jsonl")
    with Server(max_batch_wait_us=0) as srv:
        srv.register("m", booster)
        srv.predict("m", X[:8])  # warm outside the run
        obs.configure(out=path, freq=1000)
        for _ in range(6):
            srv.predict("m", X[:8])
    obs.disable()
    evs = read_events(path)
    batches = [e for e in evs if e["kind"] == "serve_batch"]
    spans_ = [e for e in evs if e["kind"] == "span"]
    assert len(batches) == 6  # accounting events keep full cadence
    assert len(spans_) < 6 * 4  # lifelines sampled, not per-request


def test_training_chunk_and_checkpoint_spans(tmp_path):
    path = str(tmp_path / "train.jsonl")
    tele = obs.configure(out=path, freq=1)
    booster, _, _ = _toy_booster(num_iterations=4, snapshot_freq=2,
                                 snapshot_keep=0)
    booster.train(snapshot_out=str(tmp_path / "m.txt"))
    run_trace = tele.trace_id
    obs.disable()
    sp = [e for e in read_events(path) if e["kind"] == "span"]
    names = {e["name"] for e in sp}
    assert "train_chunk" in names and "checkpoint_write" in names
    chunk = next(e for e in sp if e["name"] == "train_chunk")
    assert chunk["trace_id"] == run_trace
    assert chunk["dur_s"] > 0 and chunk["iters"] >= 1


def test_tree_build_spans_carry_level_structure(tmp_path):
    """Per-build spans on the per-iteration path: a tree build is ONE
    compiled program, so the span carries the level-dispatch structure
    (levels, classes, launches) rather than fabricated per-level walls."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    n = 4096
    rng = np.random.RandomState(3)
    X = rng.normal(size=(n, 8))
    y = X[:, 0] * 1.5 + rng.normal(scale=0.1, size=n)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=16)
    cfg = Config(dict(objective="regression", num_iterations=2,
                      min_data_in_leaf=2, num_leaves=8, max_depth=3,
                      tree_grow_mode="level"))
    b = GBDT(cfg, ds, create_objective("regression", cfg))
    b.learner.use_pallas = True
    b.learner.pallas_interpret = True
    b._fuse_failed = True  # per-iteration path: one host dispatch per tree
    assert b.learner.effective_grow_mode() == "level"
    path = str(tmp_path / "lvl.jsonl")
    tele = obs.configure(out=path, freq=1)
    b.train_chunk(2)
    obs.disable()
    builds = [e for e in read_events(path)
              if e["kind"] == "span" and e["name"] == "tree_build"]
    assert len(builds) == 2, len(builds)
    for e in builds:
        assert e["mode"] == "level"
        assert e["levels"] == b.learner.level_count()
        assert e["classes"] == b.learner.level_classes()
        assert e["launches"] == b.learner.launches_per_tree()
        assert e["trace_id"] == tele.trace_id
        assert e["dur_s"] > 0


# ---- pod telemetry: rank shards + merge ----

def test_rank_shard_sink_and_stamping(tmp_path):
    base = str(tmp_path / "pod.jsonl")
    tele = obs.configure(out=base, freq=1, rank=1, entry="t")
    tele.event("probe", x=1)
    finalize_run(tele)
    obs.disable()
    shard = obs.shard_path(base, 1)
    assert os.path.exists(shard) and not os.path.exists(base)
    evs = read_events(shard)
    assert evs and all(e["rank"] == 1 for e in evs)
    # non-leader writes NO summary (leader-only file discipline)
    assert not os.path.exists(base + ".summary.json")
    assert not os.path.exists(shard + ".summary.json")


def test_rank_zero_leader_writes_summary_at_base(tmp_path):
    base = str(tmp_path / "pod.jsonl")
    tele = obs.configure(out=base, freq=1, rank=0)
    tele.event("probe")
    summary = finalize_run(tele)
    obs.disable()
    assert os.path.exists(obs.shard_path(base, 0))
    assert os.path.exists(base + ".summary.json")
    assert summary["rank"] == 0 and summary["host"]


def test_rank_env_override(tmp_path, monkeypatch):
    base = str(tmp_path / "env.jsonl")
    monkeypatch.setenv(obs.RANK_ENV, "3")
    tele = obs.configure(out=base, freq=1)
    assert tele.rank == 3
    obs.disable()
    assert os.path.exists(obs.shard_path(base, 3))


def test_single_process_run_stays_unsharded(tmp_path):
    out = str(tmp_path / "solo.jsonl")
    tele = obs.configure(out=out, freq=1)
    assert tele.rank is None
    tele.event("probe")
    obs.disable()
    assert os.path.exists(out)
    assert "rank" not in read_events(out)[0]


def test_obs_report_merge_pod_view(tmp_path, capsys):
    """--merge reassembles shards of a died run: per-host breakdown, a
    merged table, and ONE skew-aligned trace with per-rank pids."""
    obs_report, _ = _tools()
    base = str(tmp_path / "died.jsonl")
    # two shards with a deliberate 100 s clock skew between run_starts;
    # rank 1's is torn mid-final-line like a preempted writer
    for rank, skew in ((0, 0.0), (1, 100.0)):
        with open(obs.shard_path(base, rank), "w") as fh:
            t0 = 1000.0 + skew
            fh.write(json.dumps({"v": 1, "ts": t0, "kind": "run_start",
                                 "rank": rank}) + "\n")
            fh.write(json.dumps({"v": 1, "ts": t0 + 1.0, "kind": "span",
                                 "rank": rank, "name": "train_chunk",
                                 "trace_id": "t%d" % rank, "span_id": "s",
                                 "parent_id": None, "t0": t0 + 1.0,
                                 "dur_s": 0.5}) + "\n")
            if rank == 1:
                fh.write('{"v": 1, "ts": 11')  # torn tail
    trace_out = str(tmp_path / "pod_trace.json")
    rc = obs_report.main([base, "--merge", "--trace", trace_out])
    assert rc == 0
    out = capsys.readouterr().out
    assert "pod view: 2 shard(s)" in out
    assert "telemetry summary" in out  # merged table rendered
    with open(trace_out) as fh:
        trace = json.load(fh)
    by_pid = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "X":
            by_pid[ev["pid"]] = ev
    assert set(by_pid) == {0, 1}
    # skew-aligned: both ranks' chunk slices land at the same aligned ts
    assert by_pid[0]["ts"] == pytest.approx(by_pid[1]["ts"], abs=1.0)
    labels = [ev for ev in trace["traceEvents"] if ev.get("ph") == "M"]
    assert {ev["args"]["name"] for ev in labels} == {"rank 0", "rank 1"}


def test_obs_report_merge_base_plus_rank0_distinct(tmp_path, capsys):
    """A run that started unsharded and resumed as a pod leaves BOTH the
    base file and a .rank0.jsonl shard: they must appear as distinct rows
    with distinct trace pids, not collide on rank 0."""
    obs_report, _ = _tools()
    base = str(tmp_path / "mixed.jsonl")
    with open(base, "w") as fh:
        fh.write(json.dumps({"v": 1, "ts": 10.0, "kind": "run_start"})
                 + "\n")
        fh.write(json.dumps({"v": 1, "ts": 11.0, "kind": "pre",
                             "dt_s": 0.5}) + "\n")
    for rank in (0, 1):
        with open(obs.shard_path(base, rank), "w") as fh:
            fh.write(json.dumps({"v": 1, "ts": 20.0, "kind": "run_start",
                                 "rank": rank}) + "\n")
    trace_out = str(tmp_path / "mixed_trace.json")
    rc = obs_report.main([base, "--merge", "--trace", trace_out,
                          "--no-table"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "pod view: 3 shard(s)" in out
    assert "base (unsharded)" in out
    with open(trace_out) as fh:
        trace = json.load(fh)
    labels = {ev["pid"]: ev["args"]["name"]
              for ev in trace["traceEvents"] if ev.get("ph") == "M"}
    assert sorted(labels.values()) == ["base (unsharded)", "rank 0",
                                       "rank 1"]
    assert len(labels) == 3  # three distinct pids
    # the base's slice kept its own pid (no shard's skew shift collision)
    slc = next(ev for ev in trace["traceEvents"] if ev.get("ph") == "X")
    assert labels[slc["pid"]] == "base (unsharded)"


def test_obs_report_merge_no_shards(tmp_path):
    obs_report, _ = _tools()
    assert obs_report.main([str(tmp_path / "none.jsonl"), "--merge",
                            "--no-table"]) == 2


def test_engine_train_pod_rank_writes_shard(tmp_path, monkeypatch):
    """engine.train under a forced rank: events land in the rank shard,
    no summary from the non-leader."""
    from lightgbm_tpu import engine
    from lightgbm_tpu.basic import Dataset
    monkeypatch.setenv(obs.RANK_ENV, "2")
    rng = np.random.RandomState(0)
    X = rng.normal(size=(400, 4))
    y = X[:, 0]
    base = str(tmp_path / "eng.jsonl")
    engine.train({"objective": "regression", "num_leaves": 7,
                  "verbosity": -1, "telemetry_out": base},
                 Dataset(X, label=y), num_boost_round=3)
    shard = obs.shard_path(base, 2)
    assert os.path.exists(shard) and not os.path.exists(base)
    assert not os.path.exists(base + ".summary.json")
    assert all(e["rank"] == 2 for e in read_events(shard))


# ---- streaming reader ----

def test_iter_events_streaming_and_torn_tail(tmp_path):
    path = str(tmp_path / "s.jsonl")
    with open(path, "w") as fh:
        for i in range(10):
            fh.write(json.dumps({"v": 1, "ts": float(i), "kind": "k%d" % i})
                     + "\n")
        fh.write('{"v": 1, "ts": 10.')  # torn final line
    it = iter_events(path)
    first = next(it)  # lazy: consuming one event does not slurp the file
    assert first["kind"] == "k0"
    rest = list(it)
    assert len(rest) == 9 and rest[-1]["kind"] == "k9"
    assert read_events(path) == [first] + rest


def test_iter_events_midfile_corruption_raises(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as fh:
        fh.write('{"v": 1, "ts": 1.0, "kind": "ok"}\n')
        fh.write("not json\n")
        fh.write('{"v": 1, "ts": 2.0, "kind": "ok"}\n')
    with pytest.raises(ValueError, match="line 2"):
        list(iter_events(path))


def test_obs_report_table_streams_from_events(tmp_path, capsys):
    obs_report, _ = _tools()
    path = str(tmp_path / "died.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({"v": 1, "ts": 1.0, "kind": "train_chunk",
                             "dt_s": 0.5}) + "\n")
        fh.write('{"v": 1, "ts": 2')  # died mid-write
    assert obs_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "train_chunk_s" in out


# ---- histogram reservoir semantics under the cap ----

def test_histogram_reservoir_covers_whole_run(monkeypatch):
    """Past HISTOGRAM_SAMPLE_CAP the buffer is a uniform reservoir: a
    late distribution shift shows in p50/p99 (earliest-only retention
    would pin the quantiles to the warmup regime forever); count/sum/min/
    max stay exact for every observation."""
    from lightgbm_tpu.obs import registry as reg
    monkeypatch.setattr(reg, "HISTOGRAM_SAMPLE_CAP", 256)
    random.seed(7)
    h = Histogram()
    for _ in range(256):
        h.observe(1.0)     # warmup regime fills the buffer exactly
    for _ in range(256 * 9):
        h.observe(100.0)   # the run's real regime: 90% of observations
    s = h.summary()
    assert s["count"] == 2560 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["sum"] == pytest.approx(256 * 1.0 + 2304 * 100.0)
    # ~90% of reservoir slots hold the late regime: p50 MUST see it
    assert s["p50"] == 100.0, "quantiles stuck on the earliest samples"
    assert s["p99"] == 100.0


def test_histogram_reservoir_buffer_stays_capped(monkeypatch):
    from lightgbm_tpu.obs import registry as reg
    monkeypatch.setattr(reg, "HISTOGRAM_SAMPLE_CAP", 64)
    h = Histogram()
    for i in range(1000):
        h.observe(float(i))
    assert len(h._samples) == 64
    assert h.count == 1000


# ---- perf gate ----

@pytest.mark.parametrize("breach, rc", [
    ({}, 0),
    ({"serving": {"failed": 4, "rejected": 0},
      "resilience": {"watchdog_stall_s": 12.5}}, 1),
    ({"gauges": {"recompiles_timed_window": 1}}, 1),
    ({"plan": {"provenance": "tuned", "cache_fallbacks": 1,
               "sites": {"partition": {"provenance": "tuned"}}}}, 1),
], ids=["healthy", "serving_failed_and_stall", "recompiles_steady",
        "plan_cache_fallback"])
def test_perf_gate_summary_serving_budgets(tmp_path, breach, rc):
    _, perf_gate = _tools()
    summary = {"metric": "telemetry_run",
               "gauges": {"recompiles_timed_window": 0},
               "serving": {"failed": 0, "rejected": 0, "dropped": 0},
               "resilience": {"watchdog_stall_s": None},
               "plan": {"provenance": "analytic", "cache_fallbacks": 0,
                        "sites": {"partition": {"provenance": "analytic"}}}}
    summary.update(breach)
    path = str(tmp_path / "run.summary.json")
    with open(path, "w") as fh:
        json.dump(summary, fh)
    assert perf_gate.main([path]) == rc


def test_perf_gate_unreadable_artifact(tmp_path):
    _, perf_gate = _tools()
    p = str(tmp_path / "junk.json")
    with open(p, "w") as fh:
        fh.write("{nope")
    assert perf_gate.main([p]) == 2


# ---- config / params wiring ----

def test_metrics_params_validate():
    from lightgbm_tpu.config import Config
    cfg = Config(metrics_port=9099, metrics_addr="127.0.0.1")
    assert cfg.metrics_port == 9099
    assert cfg.metrics_addr == "127.0.0.1"
    cfg2 = Config(telemetry_port=1234)  # alias
    assert cfg2.metrics_port == 1234
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        Config(metrics_port=-1)
    with pytest.raises(LightGBMError):
        Config(metrics_port=70000)


def test_engine_train_metrics_port_serves_live(tmp_path):
    """metrics_port through engine.train params: the exporter is up for
    the duration of the run and gone after (run-owned lifecycle)."""
    from lightgbm_tpu import engine
    from lightgbm_tpu.basic import Dataset
    rng = np.random.RandomState(0)
    X = rng.normal(size=(400, 4))
    y = X[:, 0]
    seen = {}

    class Probe:
        order = 0
        before_iteration = False

        def __call__(self, env):
            if env.iteration == 1 and "text" not in seen:
                exp = obs.active().exporter
                if exp is None:
                    return  # metrics_port=0: no listener (asserted below)
                seen["text"] = _get(exp, "/metrics")
                seen["health"] = json.loads(_get(exp, "/healthz"))

    engine.train({"objective": "regression", "num_leaves": 7,
                  "verbosity": -1, "metrics_port": 0,
                  "telemetry_out": str(tmp_path / "mp.jsonl")},
                 Dataset(X, label=y), num_boost_round=3,
                 callbacks=[Probe()])
    # port=0 is OFF at the param layer: no exporter was started
    assert "text" not in seen
    # now with a real ephemeral port picked by the test
    import socket
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    engine.train({"objective": "regression", "num_leaves": 7,
                  "verbosity": -1, "metrics_port": port,
                  "telemetry_out": str(tmp_path / "mp2.jsonl")},
                 Dataset(X, label=y), num_boost_round=3,
                 callbacks=[Probe()])
    assert "lgbm_tpu_" in seen["text"]
    assert seen["health"]["status"] == "ok"
    assert obs.active() is None
    assert not any(t.name == "lgbm-tpu-metrics"
                   for t in threading.enumerate())
