"""Performance-forensics plane (round 16): compile accounting with
first-dispatch-vs-steady attribution, device-memory telemetry import
safety, triggered profiler capture (+ flight-recorder boundedness), the
burn-rate alert engine with hand-computed goldens, the /alerts and
/debug/profile endpoint round trips, died-run recovery of the alerts and
compile sections, the perf-gate budget lines, and the zero-calls spy
extended over all four new modules."""
import glob
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from lightgbm_tpu import obs, resilience
from lightgbm_tpu.obs import alerts as obs_alerts
from lightgbm_tpu.obs import compile as obs_compile
from lightgbm_tpu.obs import devmem as obs_devmem
from lightgbm_tpu.obs import profiling as obs_profiling
from lightgbm_tpu.obs.alerts import (AlertEngine, breach_fraction,
                                     burn_rate, window_rate)
from lightgbm_tpu.obs.exporter import render_prometheus, start_exporter
from lightgbm_tpu.obs.registry import Telemetry
from lightgbm_tpu.obs.report import finalize_run, human_table

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


def _get(exp, path, timeout=90):
    return urllib.request.urlopen(
        "http://127.0.0.1:%d%s" % (exp.port, path), timeout=timeout).read(
    ).decode()


# ---- burn-rate math: hand-computed goldens ----

def test_breach_fraction_golden():
    samples = [(0.0, False), (10.0, True), (20.0, True), (30.0, False)]
    # window (15, 30]: samples at 20 (bad) and 30 (good) -> 1/2
    assert breach_fraction(samples, now=30.0, window_s=15.0) == 0.5
    # window (20, 30]: only the good sample at 30 -> 0
    assert breach_fraction(samples, now=30.0, window_s=10.0) == 0.0
    # whole history: 2 bad of 4
    assert breach_fraction(samples, now=30.0, window_s=100.0) == 0.5
    # empty window -> None (no verdict, not 0)
    assert breach_fraction(samples, now=300.0, window_s=10.0) is None
    assert breach_fraction([], now=0.0, window_s=10.0) is None


def test_burn_rate_golden():
    # 30% bad against a 10% budget burns at 3x
    assert burn_rate(0.3, 0.1) == pytest.approx(3.0)
    # exactly on budget = 1.0 (the firing threshold)
    assert burn_rate(0.1, 0.1) == pytest.approx(1.0)
    # zero budget: anything bad burns at the cap, nothing bad burns 0
    assert burn_rate(0.2, 0.0) == obs_alerts.BURN_CAP
    assert burn_rate(0.0, 0.0) == 0.0
    # no data passes through
    assert burn_rate(None, 0.1) is None
    # clamp keeps events/JSON finite
    assert burn_rate(1.0, 1e-12) == obs_alerts.BURN_CAP


def test_window_rate_golden():
    pts = [(0.0, 0.0), (10.0, 5.0), (20.0, 15.0)]
    # window start 10: baseline is the point AT 10 -> (15-5)/(20-10) = 1.0
    assert window_rate(pts, now=20.0, window_s=10.0) == pytest.approx(1.0)
    # window covers everything: (15-0)/20 = 0.75
    assert window_rate(pts, now=20.0, window_s=30.0) == pytest.approx(0.75)
    # a single point (or none) has no rate
    assert window_rate([(0.0, 3.0)], now=1.0, window_s=10.0) == 0.0
    assert window_rate([], now=1.0, window_s=10.0) == 0.0
    # a counter that never moves
    assert window_rate([(0.0, 7.0), (10.0, 7.0)], now=10.0,
                       window_s=20.0) == 0.0


# ---- alert engine ----

def test_alert_engine_gauge_rule_fires_and_resolves():
    tele = obs.configure(freq=1)
    rule = {"name": "q", "kind": "gauge", "gauge": "queue_depth",
            "max": 10.0, "budget": 0.0, "fast_window_s": 10.0,
            "slow_window_s": 30.0, "capture": False}
    eng = AlertEngine(tele, [rule], clock=lambda: 0.0)
    tele.gauge("queue_depth").set(50.0)
    eng.tick(now=0.0)
    snap = eng.snapshot()
    assert snap["firing"] == 1 and snap["fired_total"] == 1
    st = snap["series"][0]
    assert st["state"] == "firing" and st["value"] == 50.0
    assert st["fast_burn"] == obs_alerts.BURN_CAP
    # the transition emitted an event + the counter + the gauge
    kinds = [e for e in tele.events if e["kind"] == "alert"]
    assert kinds and kinds[-1]["state"] == "firing"
    assert tele.counter("alerts_fired").value == 1
    assert tele.gauge("alert_firing_q").value == 1.0
    # recover: good samples until every bad one leaves the SLOW window
    tele.gauge("queue_depth").set(1.0)
    for t in (31.0, 32.0, 33.0):
        eng.tick(now=t)
    snap = eng.snapshot()
    assert snap["firing"] == 0
    assert snap["series"][0]["state"] == "ok"
    assert tele.gauge("alert_firing_q").value == 0.0
    # resolution did not bump the fired tally again
    assert snap["fired_total"] == 1
    assert [e["state"] for e in tele.events
            if e["kind"] == "alert"] == ["firing", "resolved"]


def test_alert_engine_budget_fraction_golden():
    """budget=0.5 with a 10s window: 1 bad of 3 samples burns 0.67 (no
    fire); 3 bad of 5 burns 1.2 (fires) — hand-computed."""
    tele = obs.configure(freq=1)
    rule = {"name": "b", "kind": "gauge", "gauge": "g", "max": 1.0,
            "budget": 0.5, "fast_window_s": 10.0, "slow_window_s": 10.0,
            "capture": False}
    eng = AlertEngine(tele, [rule])
    g = tele.gauge("g")
    g.set(5.0)
    eng.tick(now=1.0)               # bad: 1/1 -> burn 2.0 BUT single window
    # both windows see the same single bad sample: fraction 1.0, burn 2.0
    assert eng.snapshot()["series"][0]["state"] == "firing"
    eng2 = AlertEngine(tele, [rule])
    seq = [(1.0, 5.0), (2.0, 0.0), (3.0, 0.0)]   # 1 bad of 3
    for t, v in seq:
        g.set(v)
        eng2.tick(now=t)
    st = eng2.snapshot()["series"][0]
    assert st["state"] == "ok"
    assert st["fast_burn"] == pytest.approx((1 / 3) / 0.5, abs=1e-4)
    for t, v in ((4.0, 5.0), (5.0, 5.0)):        # now 3 bad of 5
        g.set(v)
        eng2.tick(now=t)
    st = eng2.snapshot()["series"][0]
    assert st["state"] == "firing"
    assert st["fast_burn"] == pytest.approx((3 / 5) / 0.5, abs=1e-4)


def test_alert_engine_rate_rule():
    tele = obs.configure(freq=1)
    rule = {"name": "rej", "kind": "rate", "counter": "serve_rejected",
            "max_per_s": 0.0, "fast_window_s": 10.0, "slow_window_s": 30.0,
            "capture": False}
    eng = AlertEngine(tele, [rule])
    c = tele.counter("serve_rejected")
    eng.tick(now=0.0)
    assert eng.snapshot()["firing"] == 0  # flat counter: no rate
    c.inc(5)
    eng.tick(now=1.0)
    snap = eng.snapshot()
    assert snap["firing"] == 1
    assert snap["series"][0]["value"] == pytest.approx(5.0)  # 5/s fast rate
    # the counter stops moving; once the growth leaves both windows the
    # alert resolves
    for t in (32.0, 33.0, 34.0):
        eng.tick(now=t)
    assert eng.snapshot()["firing"] == 0


def test_alert_engine_quantile_idle_series_resolves():
    """A quantile series with no NEW observations appends no window
    samples: the cumulative statistic cannot re-assert a stale alert
    forever, and once every bad sample ages out of both windows the
    alert resolves (silence = no verdict)."""
    tele = obs.configure(freq=1)
    h = tele.histogram("serve_latency_s_model_x")
    h.observe(5.0)
    rule = {"name": "p", "kind": "quantile",
            "metric": "serve_latency_s_model_x", "quantile": "p99",
            "max": 1.0, "budget": 0.0, "fast_window_s": 10.0,
            "slow_window_s": 20.0, "capture": False}
    eng = AlertEngine(tele, [rule])
    eng.tick(now=0.0)
    assert eng.snapshot()["series"][0]["state"] == "firing"
    # no fresh traffic: the ticks add no samples, and past both windows
    # the one bad sample ages out -> resolved, not firing-forever
    for t in (5.0, 21.0):
        eng.tick(now=t)
    snap = eng.snapshot()
    assert snap["series"][0]["state"] == "ok"
    assert snap["fired_total"] == 1
    # fresh (still-bad) traffic re-arms it
    h.observe(5.0)
    eng.tick(now=22.0)
    assert eng.snapshot()["series"][0]["state"] == "firing"
    assert eng.snapshot()["fired_total"] == 2


def test_alert_engine_quantile_rule_matches_models():
    tele = obs.configure(freq=1)
    tele.histogram("serve_latency_s_model_a").observe(2.0)
    tele.histogram("serve_latency_s_model_b").observe(0.01)
    rule = {"name": "p99", "kind": "quantile",
            "metric": "serve_latency_s_model_*", "quantile": "p99",
            "max": 0.5, "budget": 0.0, "fast_window_s": 10.0,
            "slow_window_s": 10.0, "capture": False}
    eng = AlertEngine(tele, [rule])
    eng.tick(now=1.0)
    by_series = {st["series"]: st["state"]
                 for st in eng.snapshot()["series"]}
    assert by_series == {"serve_latency_s_model_a": "firing",
                        "serve_latency_s_model_b": "ok"}


def test_alert_rules_load_and_validation(tmp_path):
    path = tmp_path / "rules.json"
    path.write_text(json.dumps({"alerts": [
        {"name": "ok", "kind": "gauge", "gauge": "g", "max": 1},
        {"name": "bad-kind", "kind": "wat"},
        {"kind": "gauge", "gauge": "g", "max": 1},
    ]}))
    rules = obs_alerts.load_rules(str(path))
    assert [r["name"] for r in rules] == ["ok"]
    # bare-list form works too
    path.write_text(json.dumps([{"name": "l", "kind": "rate",
                                 "counter": "c"}]))
    assert [r["name"] for r in obs_alerts.load_rules(str(path))] == ["l"]
    # the repo budgets file itself parses into usable rules
    repo_rules = obs_alerts.load_rules(os.path.join(REPO,
                                                    "PERF_BUDGETS.json"))
    assert any(r["name"] == "serve_p99" for r in repo_rules)


def test_alerts_endpoint_roundtrip_and_close_stops_engine(tmp_path):
    tele = obs.configure(out=str(tmp_path / "t.jsonl"), freq=1)
    eng = obs_alerts.install(
        tele, rules=[{"name": "q", "kind": "gauge", "gauge": "d",
                      "max": 1.0, "fast_window_s": 1.0,
                      "slow_window_s": 2.0, "capture": False}],
        interval_s=0.05)
    exp = start_exporter(tele, port=0)
    tele.gauge("d").set(9.0)
    deadline = time.time() + 10
    body = None
    while time.time() < deadline:
        body = json.loads(_get(exp, "/alerts"))
        if body.get("firing"):
            break
        time.sleep(0.05)
    assert body["enabled"] and body["firing"] == 1, body
    assert body["series"][0]["rule"] == "q"
    # /metrics carries the labeled state gauge
    assert 'lgbm_tpu_alert_state{rule="q",series="d"} 1' in _get(
        exp, "/metrics")
    # the run owns the engine: close() stops its thread
    t = eng._thread
    obs.disable()
    assert t is not None and not t.is_alive()


def test_alerts_endpoint_without_engine(tmp_path):
    tele = obs.configure(freq=1)
    exp = start_exporter(tele, port=0)
    body = json.loads(_get(exp, "/alerts"))
    assert body == {"enabled": False, "series": [], "firing": 0,
                    "fired_total": 0}


# ---- triggered profiler capture ----

def test_debug_profile_endpoint_roundtrip(tmp_path):
    tele = obs.configure(out=str(tmp_path / "t.jsonl"), freq=1)
    exp = start_exporter(tele, port=0)
    body = json.loads(_get(exp, "/debug/profile?seconds=0.1"))
    assert body.get("error") is None, body
    assert body["reason"] == "http" and body["n"] == 1
    assert os.path.isdir(body["dir"])
    assert os.path.exists(os.path.join(body["dir"], "capture.json"))
    # run-scoped layout next to the telemetry artifacts
    assert body["dir"].startswith(str(tmp_path / "t.jsonl") + ".profiles")
    # the event stream carries the capture
    assert any(e["kind"] == "profile_capture" for e in tele.events)
    assert tele.counter("profile_captures").value == 1
    # summary section renders
    s = finalize_run(tele)
    assert s["profiling"]["captures"][0]["reason"] == "http"
    assert "profiler captures" in human_table(s)


def test_debug_profile_bad_seconds(tmp_path):
    tele = obs.configure(freq=1)
    exp = start_exporter(tele, port=0)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(exp, "/debug/profile?seconds=nope")
    assert ei.value.code == 400


def test_flight_recorder_fires_once(monkeypatch):
    tele = obs.configure(freq=1)
    calls = []
    monkeypatch.setattr(obs_profiling, "capture",
                        lambda t, seconds, reason: calls.append(reason)
                        or {"n": len(calls), "reason": reason})
    # disarmed: no capture
    assert obs_profiling.on_incident("early") is None
    obs_profiling.arm_flight_recorder(tele)
    assert obs_profiling.on_incident("first")["reason"] == "first"
    # one-shot: the second incident is a no-op
    assert obs_profiling.on_incident("second") is None
    assert calls == ["first"]


def test_capture_never_concurrent(tmp_path):
    tele = obs.configure(out=str(tmp_path / "t.jsonl"), freq=1)
    st = obs_profiling.state(tele, create=True)
    st.active = True  # a capture is "running"
    out = obs_profiling.capture(tele, seconds=0.05, reason="x")
    assert "already in progress" in out["error"]
    st.active = False
    # and an armed incident during a capture is swallowed, not queued
    obs_profiling.arm_flight_recorder(tele)
    st.active = True
    assert obs_profiling.on_incident("mid") is None
    st.active = False
    assert not st.auto_fired


def test_capture_layout_shared_with_profile_tree(tmp_path):
    d = obs_profiling.open_capture(str(tmp_path), 3, "profile tree!")
    assert os.path.basename(d) == "capture_03_profile_tree_"
    meta = obs_profiling.write_meta(d, reason="unit", seconds=0.1)
    assert meta["dir"] == d
    on_disk = json.load(open(os.path.join(d, "capture.json")))
    assert on_disk["reason"] == "unit" and on_disk["v"] == 1
    # trace_block never raises, even into a read-only/bogus location
    with obs_profiling.trace_block(d):
        pass


# ---- compile accounting ----

def test_compile_accounting_attribution():
    acct = obs_compile.CompileAccounting(warm_load_max_s=0.05)
    tele = obs.configure(freq=1)
    # first dispatch carries the compile: 2.0s wall
    acct.note(tele, "fn", 128, 2.0, misses=1)
    snap = acct.snapshot()
    key = snap["keys"]["fn|128"]
    # unresolved yet: priced at the full wall as an upper bound
    assert key["unresolved"] == 1 and key["compile_s"] == 2.0
    # two steady dispatches resolve it against their median
    acct.note(tele, "fn", 128, 0.1, misses=0)
    acct.note(tele, "fn", 128, 0.2, misses=0)
    snap = acct.snapshot()
    key = snap["keys"]["fn|128"]
    assert "unresolved" not in key
    assert key["compiles"] == 1 and key["warm_loads"] == 0
    # resolved at first steady note: 2.0 - 0.1 (single-sample median)
    assert key["compile_s"] == pytest.approx(1.9)
    assert key["steady_p50_s"] == pytest.approx(0.15)
    assert key["first_dispatch_s"] == 2.0
    assert snap["compile_seconds_total"] == pytest.approx(1.9)
    # the event stream carried the raw breadcrumb
    ev = [e for e in tele.events if e["kind"] == "compile"]
    assert len(ev) == 1 and ev[0]["fn"] == "fn" and ev[0]["n"] == 1
    # the true compile landed in the compile_s histogram
    assert tele.histogram("compile_s").count == 1


def test_compile_accounting_warm_load():
    """A persistent-cache warm load (tiny excess over steady) is counted
    apart from true compiles — the CLI's XLA disk cache makes repeat
    invocations' 'misses' cheap and the autotuner must not rank on them."""
    acct = obs_compile.CompileAccounting(warm_load_max_s=0.05)
    tele = obs.configure(freq=1)
    acct.note(tele, "fn", "8k", 0.10, misses=0)
    acct.note(tele, "fn", "8k", 0.10, misses=0)
    acct.note(tele, "fn", "8k", 0.13, misses=1)   # excess 0.03 <= 0.05
    acct.note(tele, "fn", "8k", 0.10, misses=0)   # resolves the pending
    snap = acct.snapshot()
    key = snap["keys"]["fn|8k"]
    assert key["warm_loads"] == 1 and key["compiles"] == 0
    assert key["compile_s"] == 0.0
    assert snap["warm_loads"] == 1
    # a real compile on the same key still prices normally
    acct.note(tele, "fn", "8k", 3.0, misses=1)
    acct.note(tele, "fn", "8k", 0.10, misses=0)
    key = acct.snapshot()["keys"]["fn|8k"]
    assert key["compiles"] == 1 and key["compile_s"] == pytest.approx(
        2.9, abs=0.01)


def test_compile_accounting_from_dispatch_sites(tmp_path):
    """The real sites attribute: a fused-train chunk's first dispatch and
    the predict buckets' first dispatches land as keys, steady repeats
    price them, and the summary carries the section."""
    booster, X, _ = _toy_booster(num_iterations=8)
    tele = obs.configure(out=str(tmp_path / "t.jsonl"), freq=1)
    booster.train_chunk(4)
    booster.train_chunk(4)          # steady chunk resolves k=4
    booster.predict(X[:600])
    booster.predict(X[:600])        # steady bucket dispatch
    acct = tele.compile_acct
    assert acct is not None
    snap = acct.snapshot()
    assert "fused_train|k=4" in snap["keys"]
    assert any(k.startswith("predict_blocked|") for k in snap["keys"])
    fused = snap["keys"]["fused_train|k=4"]
    assert fused["compiles"] == 1 and "unresolved" not in fused
    # the compile cost dominates its steady chunk wall on this box
    assert fused["compile_s"] > fused["steady_p50_s"]
    s = finalize_run(tele, gbdt=booster)
    assert s["compile"]["compile_seconds_total"] > 0
    assert "compile_seconds_total" in human_table(s)
    # /metrics renders the labeled series
    text = render_prometheus(tele.registry.snapshot(), compile_acct=snap)
    assert "lgbm_tpu_compile_seconds_total" in text
    assert 'lgbm_tpu_compile_seconds{fn="fused_train",bucket="k=4"}' in text


def test_steady_state_recompiles_zero_with_forensics_armed(tmp_path):
    """The acceptance pin: everything armed (accounting, alerts, flight
    recorder), a steady train+predict loop still reads 0 recompiles."""
    booster, X, _ = _toy_booster(num_iterations=12)
    tele = obs.configure(out=str(tmp_path / "t.jsonl"), freq=1,
                         flight_recorder=True)
    obs_alerts.install(tele, rules=[
        {"name": "q", "kind": "gauge", "gauge": "none", "max": 1.0,
         "capture": False}], interval_s=0.05)
    booster.train_chunk(4)
    booster.train_chunk(4)          # same-k chunk: fused-cache hit
    booster.predict(X[:600])        # compiles this ensemble's bucket
    obs.recompile.reset()
    booster.predict(X[:600])        # steady: same ensemble, same bucket
    booster.predict(X[:600])
    booster.train_chunk(4)          # steady: same-k program reused
    assert obs.recompile.total() == 0


# ---- device-memory telemetry ----

def test_devmem_import_safe_on_cpu():
    """CPU devices report no memory_stats: every entry point returns
    quietly instead of raising (TPU/GPU gauges light up on backends that
    report)."""
    stats = obs_devmem.device_memory_stats()
    assert isinstance(stats, list)
    tele = obs.configure(freq=1)
    out = obs_devmem.sample(tele, phase="train_chunk")
    assert out == stats
    if not stats:  # this box: no stats -> no gauges, no events, no block
        assert not any(k.startswith("devmem_")
                       for k in tele.registry.snapshot()["gauges"])
        assert not any(e["kind"] == "devmem" for e in tele.events)
        assert obs_devmem.snapshot(tele) == {}


def test_devmem_gauges_and_high_water_event():
    """Synthetic stats (monkeypatch-free via the tracker API): feed two
    samples through the gauge/event path by stubbing the probe."""
    tele = obs.configure(freq=1)
    seq = [[("0", {"bytes_in_use": 100, "peak_bytes_in_use": 120,
                   "largest_alloc_size": 50})],
           [("0", {"bytes_in_use": 90, "peak_bytes_in_use": 120,
                   "largest_alloc_size": 50})],
           [("0", {"bytes_in_use": 300, "peak_bytes_in_use": 310,
                   "largest_alloc_size": 200})]]
    orig = obs_devmem.device_memory_stats
    try:
        obs_devmem.device_memory_stats = lambda: seq.pop(0)
        obs_devmem.sample(tele, phase="train_chunk")
        obs_devmem.sample(tele, phase="train_chunk")   # no new high water
        obs_devmem.sample(tele, phase="train_chunk")   # new high water
    finally:
        obs_devmem.device_memory_stats = orig
    # deliberately NOT mirrored into registry gauges (the labeled /metrics
    # family is rendered from the fresh poll; a stale unlabeled copy would
    # disagree with it) — the tracker carries the state
    assert not any(k.startswith("devmem_")
                   for k in tele.registry.snapshot()["gauges"])
    evs = [e for e in tele.events if e["kind"] == "devmem"]
    assert [e["high_water"] for e in evs] == [True, False, True]
    snap = obs_devmem.snapshot(tele)
    assert snap["peak_bytes_max"] == 310
    assert snap["devices"]["0"]["bytes_in_use"] == 300
    # labeled exposition
    text = render_prometheus({}, devmem_stats=[
        ("0", {"bytes_in_use": 300, "peak_bytes_in_use": 310})])
    assert 'lgbm_tpu_device_bytes_in_use{device="0"} 300.0' in text


# ---- residency cross-check ----

def test_residency_snapshot_and_divergence_warn_once(tmp_path):
    from lightgbm_tpu.serving import Server
    from lightgbm_tpu.serving.registry import residency_snapshot
    booster, X, _ = _toy_booster(num_iterations=4)
    booster.train_chunk(4)
    tele = obs.configure(out=str(tmp_path / "t.jsonl"), freq=1)
    with Server(max_batch_wait_us=0) as srv:
        entry = srv.register("prod", booster)
        snap = residency_snapshot()
        assert snap["prod"]["accounted"] == snap["prod"]["actual"] > 0
        # healthy: divergence ~0, no warning counter
        checked = obs_devmem.check_residency(tele)
        assert checked["prod"]["divergence"] == 0.0
        g = tele.registry.snapshot()
        assert "residency_divergence_warnings" not in g["counters"]
        # doctor the ledger apart from the true footprint (>10%)
        entry.accounted_bytes = int(entry.resident_bytes * 0.5)
        checked = obs_devmem.check_residency(tele)
        obs_devmem.check_residency(tele)  # warned ONCE, value stays live
        g = tele.registry.snapshot()
        assert g["counters"]["residency_divergence_warnings"] == 1
        assert checked["prod"]["divergence"] == pytest.approx(0.5)
        assert obs_devmem.snapshot(tele)["residency_divergence"]["prod"] \
            == pytest.approx(0.5)
        assert any(e["kind"] == "residency_divergence"
                   for e in tele.events)
        # the /metrics exposition carries both kinds + the divergence,
        # rebuilt per scrape from LIVE models only
        text = render_prometheus({}, residency=checked)
        assert 'lgbm_tpu_residency_bytes{model="prod",kind="accounted"}' \
            in text
        assert 'lgbm_tpu_residency_bytes{model="prod",kind="actual"}' \
            in text
        assert 'lgbm_tpu_residency_divergence{model="prod"}' in text
        # the model departs: the next cross-check prunes its divergence
        # from tracker and exposition alike — no stale metric for a
        # model that no longer exists
        srv.registry.unregister("prod")
        checked = obs_devmem.check_residency(tele)
        assert not checked
        assert "residency_divergence" not in (obs_devmem.snapshot(tele)
                                              or {})


def test_residency_endpoint_live(tmp_path):
    from lightgbm_tpu.serving import Server
    booster, X, _ = _toy_booster(num_iterations=4)
    booster.train_chunk(4)
    tele = obs.configure(freq=1)
    exp = start_exporter(tele, port=0)
    with Server(max_batch_wait_us=0) as srv:
        srv.register("live", booster)
        text = _get(exp, "/metrics")
        assert 'lgbm_tpu_residency_bytes{model="live",kind="actual"}' \
            in text


# ---- died-run recovery + perf gate ----

def _write_events(path, events):
    with open(path, "w") as fh:
        for e in events:
            fh.write(json.dumps({"v": 1, "ts": 1.0, **e}) + "\n")


def test_obs_report_recovers_alerts_and_compile(tmp_path):
    obs_report, _ = _tools()
    path = str(tmp_path / "died.jsonl")
    _write_events(path, [
        {"kind": "run_start"},
        {"kind": "compile", "fn": "fused_train", "bucket": "k=8", "n": 1,
         "dispatch_s": 4.5},
        {"kind": "compile", "fn": "predict_blocked", "bucket": "1024",
         "n": 2, "dispatch_s": 0.8},
        {"kind": "alert", "rule": "serve_p99", "state": "firing",
         "series": "serve_latency_s_model_m", "severity": "page"},
        {"kind": "alert", "rule": "serve_p99", "state": "resolved"},
        {"kind": "alert", "rule": "serve_p99", "state": "firing"},
        {"kind": "profile_capture", "n": 1, "reason": "alert_serve_p99",
         "dir": "/tmp/x/capture_01"},
    ])
    summary = obs_report.summary_from_events(obs.iter_events(path))
    comp = summary["compile"]
    assert comp["recovered"] and comp["compiles"] == 3
    assert comp["compile_seconds_total"] == pytest.approx(5.3)
    assert comp["keys"]["fused_train|k=8"]["compile_s"] == 4.5
    al = summary["alerts"]
    assert al["fired_total"] == 2
    assert al["series"][0]["rule"] == "serve_p99"
    assert al["series"][0]["state"] == "firing"
    assert summary["profiling"]["captures"][0]["reason"] == "alert_serve_p99"
    table = human_table(summary)
    assert "compile_seconds_total" in table and "fired_total" in table


def test_obs_report_merge_folds_alert_shards(tmp_path, capsys):
    obs_report, _ = _tools()
    base = str(tmp_path / "pod.jsonl")
    _write_events(base + ".rank0.jsonl", [
        {"kind": "run_start", "rank": 0},
        {"kind": "alert", "rule": "r", "state": "firing", "rank": 0},
        {"kind": "compile", "fn": "f", "bucket": "1", "n": 1,
         "dispatch_s": 1.0, "rank": 0}])
    _write_events(base + ".rank1.jsonl", [
        {"kind": "run_start", "rank": 1},
        {"kind": "alert", "rule": "r", "state": "firing", "rank": 1},
        {"kind": "compile", "fn": "f", "bucket": "1", "n": 1,
         "dispatch_s": 2.0, "rank": 1}])
    assert obs_report.merge_report(base) == 0
    out = capsys.readouterr().out
    assert "fired_total" in out
    # both shards' incidents fold: 2 fired, 2 compiles summing 3.0s
    assert "2" in out.split("fired_total", 1)[1].splitlines()[0]
    assert "compile_seconds_total" in out


def test_perf_gate_alerts_budget(tmp_path):
    _, perf_gate = _tools()
    budgets = tmp_path / "budgets.json"
    ok = {"metric": "telemetry_run", "v": 1, "alerts": {"fired_total": 0}}
    budgets.write_text(json.dumps({"budgets": {"alerts_fired_max": 0}}))
    bad_alerts = dict(ok, alerts={"fired_total": 3})
    for name, doc, rc in (("ok.json", ok, 0), ("bada.json", bad_alerts, 1)):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        assert perf_gate.run_gate([str(p)], str(budgets)) == rc, name


# ---- zero-overhead spy over all four modules ----

def test_telemetry_off_forensics_zero_calls(monkeypatch, tmp_path):
    """The round-9 zero-calls contract extended over compile accounting,
    devmem, profiling and alerts: a telemetry-off train/predict/serve
    loop constructs nothing and notes nothing in any of the four."""
    calls = []

    def spy(name):
        return lambda *a, **k: calls.append((name, a))

    monkeypatch.setattr(obs_compile.CompileAccounting, "__init__",
                        spy("CompileAccounting"))
    monkeypatch.setattr(obs_compile, "note_dispatch", spy("compile_note"))
    monkeypatch.setattr(obs_devmem.DevMemTracker, "__init__",
                        spy("DevMemTracker"))
    monkeypatch.setattr(obs_devmem, "sample", spy("devmem_sample"))
    monkeypatch.setattr(obs_devmem, "check_residency",
                        spy("check_residency"))
    monkeypatch.setattr(obs_profiling.ProfilingState, "__init__",
                        spy("ProfilingState"))
    monkeypatch.setattr(obs_profiling, "capture", spy("capture"))
    monkeypatch.setattr(obs_alerts.AlertEngine, "__init__",
                        spy("AlertEngine"))
    monkeypatch.setattr(obs_alerts, "note_incident", spy("note_incident"))
    assert obs.active() is None
    booster, X, _ = _toy_booster(num_iterations=8)
    booster.train_chunk(8)
    booster.predict(X[:600])
    booster.train(None)
    from lightgbm_tpu.serving import Server
    with Server(max_batch_wait_us=0) as srv:
        srv.register("spy", booster)
        srv.predict("spy", X[:8])
    # incident hooks stay silent with no run
    assert obs_profiling.on_incident("noop") is None
    assert not any(t.name == "lgbm-tpu-alerts"
                   for t in threading.enumerate())
    assert calls == [], "telemetry-off run touched the forensics plane: " \
        "%r" % (calls[:5],)


# ---- config / param plumbing ----

def test_forensics_params_validate(tmp_path):
    from lightgbm_tpu.config import Config
    rules = tmp_path / "r.json"
    rules.write_text(json.dumps({"alerts": []}))
    cfg = Config(objective="regression",
                 telemetry_out=str(tmp_path / "o.jsonl"),
                 alert_rules=str(rules), alert_interval_s=0.5,
                 flight_recorder=True)
    assert cfg.alert_interval_s == 0.5 and cfg.flight_recorder is True
    with pytest.raises(Exception):
        Config(objective="regression", alert_interval_s=0.0)


def test_engine_train_arms_forensics(tmp_path):
    """engine.train with alert_rules + flight_recorder params installs
    the engine and arms the recorder on the run it owns."""
    import lightgbm_tpu as lgb
    rules = tmp_path / "r.json"
    rules.write_text(json.dumps({"alerts": [
        {"name": "noop", "kind": "gauge", "gauge": "missing", "max": 1.0,
         "capture": False}]}))
    rng = np.random.RandomState(0)
    X = rng.normal(size=(512, 4))
    y = X[:, 0] + rng.normal(scale=0.1, size=512)
    seen = {}
    orig_close = Telemetry.close

    def capture_close(self):
        seen.setdefault("alerts", self.alerts)
        seen.setdefault("profiling", self.profiling)
        orig_close(self)
    Telemetry.close, restore = capture_close, orig_close
    try:
        ds = lgb.Dataset(X, label=y)
        lgb.train({"objective": "regression", "num_iterations": 2,
                   "min_data_in_leaf": 5, "verbosity": -1,
                   "telemetry_out": str(tmp_path / "t.jsonl"),
                   "alert_rules": str(rules), "alert_interval_s": 0.1,
                   "flight_recorder": True}, ds)
    finally:
        Telemetry.close = restore
    assert seen["alerts"] is not None and seen["alerts"].rules
    assert seen["profiling"] is not None and seen["profiling"].armed
