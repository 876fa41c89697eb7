"""Telemetry subsystem (lightgbm_tpu/obs): registry semantics, JSONL
schema round-trip, per-iteration cadence, recompile accounting pinned at
zero in steady state, zero-overhead-when-off, and the one host timer: the
spans' totals (nested, re-entrant, per thread, across a reset).
"""
import json
import threading
import time

import numpy as np
import pytest

from lightgbm_tpu import obs
from lightgbm_tpu.obs.registry import (EVENT_SCHEMA_VERSION, Histogram,
                                       MetricsRegistry, Telemetry,
                                       read_events, validate_event)
from lightgbm_tpu.obs import spans


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts and ends with telemetry off."""
    obs.disable()
    yield
    obs.disable()


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


# ---- registry semantics ----

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    assert reg.counter("c").value == 5
    reg.gauge("g").set(2.5)
    reg.gauge("g").set(7.0)
    assert reg.gauge("g").value == 7.0
    h = reg.histogram("h")
    for v in range(100):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 0.0 and s["max"] == 99.0
    assert s["p50"] == pytest.approx(50.0, abs=1.0)
    assert s["p99"] == pytest.approx(98.0, abs=1.0)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["h"]["count"] == 100


def test_histogram_empty_and_single():
    h = Histogram()
    assert h.summary() == {"count": 0, "sum": 0.0}
    h.observe(3.0)
    s = h.summary()
    assert s["p50"] == 3.0 and s["p99"] == 3.0 and s["mean"] == 3.0


# ---- JSONL schema round-trip ----

def test_jsonl_schema_roundtrip(tmp_path):
    path = str(tmp_path / "tele.jsonl")
    tele = Telemetry(out=path, freq=3, meta={"entry": "test"})
    tele.event("iteration", iteration=1, dt_s=0.5)
    with tele.time_block("timed"):
        pass
    tele.close()
    events = read_events(path)
    kinds = [e["kind"] for e in events]
    assert kinds == ["run_start", "iteration", "timed"]
    for e in events:
        assert e["v"] == EVENT_SCHEMA_VERSION
        validate_event(e)
    assert events[0]["entry"] == "test"
    assert events[1]["iteration"] == 1
    assert events[2]["dt_s"] >= 0.0
    # in-memory mirror matches the file
    assert [e["kind"] for e in tele.events] == kinds


def test_jsonl_schema_rejects_bad_events(tmp_path):
    with pytest.raises(ValueError):
        validate_event({"ts": 1.0, "kind": "x"})  # no version
    with pytest.raises(ValueError):
        validate_event({"v": EVENT_SCHEMA_VERSION, "ts": "no", "kind": "x"})
    with pytest.raises(ValueError):
        validate_event({"v": EVENT_SCHEMA_VERSION, "ts": 1.0, "kind": ""})
    # mid-file corruption raises...
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 1, "ts": 1.0, "kind": "ok"}\nnot json\n'
                   '{"v": 1, "ts": 2.0, "kind": "ok"}\n')
    with pytest.raises(ValueError):
        read_events(str(bad))
    # ...but a torn FINAL line (writer killed mid-event) is dropped so a
    # preempted run's artifact stays readable
    torn = tmp_path / "torn.jsonl"
    torn.write_text('{"v": 1, "ts": 1.0, "kind": "ok"}\n{"v": 1, "ts": 2.')
    evs = read_events(str(torn))
    assert len(evs) == 1 and evs[0]["kind"] == "ok"


# ---- per-iteration event cadence vs telemetry_freq ----

@pytest.mark.parametrize("freq,expected", [(1, 10), (2, 5), (3, 3)])
def test_engine_train_iteration_cadence(tmp_path, freq, expected):
    from lightgbm_tpu import engine
    from lightgbm_tpu.basic import Dataset
    rng = np.random.RandomState(0)
    X = rng.normal(size=(600, 5))
    y = X[:, 0] + rng.normal(scale=0.1, size=600)
    out = str(tmp_path / "t.jsonl")
    engine.train({"objective": "regression", "num_leaves": 7,
                  "min_data_in_leaf": 5, "verbosity": -1,
                  "telemetry_out": out, "telemetry_freq": freq},
                 Dataset(X, label=y), num_boost_round=10)
    events = read_events(out)
    its = [e for e in events if e["kind"] == "iteration"]
    assert len(its) == expected
    # engine.train finalized the run: summary JSON sits next to the JSONL
    with open(out + ".summary.json") as fh:
        summary = json.load(fh)
    assert summary["iterations"] == 10
    assert summary["value"] is not None and summary["value"] > 0
    obs.disable()


def test_engine_train_closes_run_on_exception(tmp_path):
    """An error mid-train must not leak the engine-owned run: the next run
    in the process starts from obs.active() is None."""
    from lightgbm_tpu import engine
    from lightgbm_tpu.basic import Dataset

    def bad_fobj(score, ds):
        raise RuntimeError("user objective blew up")

    rng = np.random.RandomState(0)
    X = rng.normal(size=(400, 4))
    y = X[:, 0]
    out = str(tmp_path / "aborted.jsonl")
    with pytest.raises(RuntimeError):
        engine.train({"objective": "none", "num_leaves": 7, "verbosity": -1,
                      "telemetry_out": out}, Dataset(X, label=y),
                     num_boost_round=3, fobj=bad_fobj)
    assert obs.active() is None, "aborted run leaked as process-active"
    # the JSONL was closed (flushed) — whatever was recorded is readable
    for e in read_events(out):
        validate_event(e)


# ---- summary artifact contents (acceptance shape) ----

def test_summary_artifact_contents(tmp_path):
    """One run with telemetry_out set produces schema-valid JSONL + a
    summary with rows/s, host phases, checkpoint latencies, recompile
    counts per shape bucket and the MFU estimate fields."""
    out = str(tmp_path / "run.jsonl")
    tele = obs.configure(out=out, freq=1, entry="test")
    booster, X, _ = _toy_booster(num_iterations=6, snapshot_freq=2,
                                 snapshot_keep=0)
    booster.train(snapshot_out=str(tmp_path / "model.txt"))
    booster.predict(X[:600])  # per-bucket predict latency + recompile note
    from lightgbm_tpu.obs.report import finalize_run, human_table
    summary = finalize_run(tele, gbdt=booster, wall_s=1.0,
                           iters=int(booster.iter_))
    for e in read_events(out):
        validate_event(e)
    # per-iteration rows/s (chunk granularity on the fused driver)
    assert summary["rows_per_s"]["count"] >= 1
    # per-phase host dispatch times
    assert "fused_train_chunk" in summary["host_phases"]
    assert "checkpoint.write" in summary["host_phases"]
    # checkpoint latencies
    assert summary["histograms"]["checkpoint_write_s"]["count"] >= 1
    # per-shape-bucket predict latency
    assert any(k.startswith("predict_dispatch_s_bucket_")
               for k in summary["histograms"])
    # recompile counts are keyed per (function, shape bucket)
    assert any(k.startswith("fused_train|") for k in summary["recompiles"])
    # resilience rollup (round 11): the fault counters ride every summary
    res = summary["resilience"]
    assert res["preemptions"] == 0 and res["io_retries"] == 0
    assert res["predict_fallbacks"] == 0 and res["checkpoint_skipped"] == 0
    assert res["preempt_checkpoint_s"]["count"] == 0
    # the driver's train-loop gauges win over finalize_run's wall_s arg
    assert summary["wall_s"] != 1.0
    assert summary["value"] == pytest.approx(
        booster.num_data * booster.iter_ / summary["wall_s"])
    text = human_table(summary)
    assert "row-trees/s" in text and "recompiles (total)" in text


# ---- recompile accounting ----

def test_recompile_zero_across_steady_state_predict():
    booster, X, _ = _toy_booster(num_iterations=4)
    booster.train_chunk(4)
    booster.predict(X[:600])       # warmup: pad-to-1024 bucket compile
    obs.recompile.reset()
    for n in (600, 700, 1024, 130):  # 1024-bucket and 128-bucket reuse...
        booster.predict(X[:n])
    booster.predict(X[:600])
    assert obs.recompile.total("predict_blocked") == 0, \
        obs.recompile.counts()


def test_recompile_zero_across_fused_training_steady_state():
    booster, _, _ = _toy_booster(num_iterations=16, metric_freq=4)
    booster.train_chunk(4)         # compiles the k=4 fused program
    obs.recompile.reset()
    booster.train_chunk(4)         # same config-keyed chunk: cache hit
    booster.train_chunk(4)
    assert obs.recompile.total("fused_train") == 0, obs.recompile.counts()
    # a NEW chunk length is a legitimate compile and must be attributed
    booster.train_chunk(2)
    assert obs.recompile.counts().get(("fused_train", "k=2")) == 1


def test_recompile_baseline_follows_cache_clear():
    """After a jit-cache clear the observed size drops; growth from the
    NEW size must count (a high-water baseline would hide the storm)."""
    obs.recompile.reset()
    obs.recompile.note_dispatch("fn_clear", 1, 3)
    assert obs.recompile.total("fn_clear") == 3
    obs.recompile.note_dispatch("fn_clear", 1, 1)   # cache cleared
    obs.recompile.note_dispatch("fn_clear", 1, 2)   # real recompile
    assert obs.recompile.counts()[("fn_clear", "1")] == 4


def test_engine_train_zero_iterations_after_full_resume(tmp_path):
    """A resume that restored the final iteration runs the loop zero times;
    the epilogue must not crash (and the model must be intact)."""
    from lightgbm_tpu import engine
    from lightgbm_tpu.basic import Booster, Dataset
    rng = np.random.RandomState(0)
    X = rng.normal(size=(600, 5))
    y = X[:, 0] + rng.normal(scale=0.1, size=600)
    params = {"objective": "regression", "num_leaves": 7, "verbosity": -1,
              "snapshot_freq": 4}
    b = Booster(params=dict(params), train_set=Dataset(X, label=y))
    for _ in range(4):
        b.update()
    prefix = str(tmp_path / "full")
    b.save_checkpoint(prefix)
    out = engine.train(dict(params), Dataset(X, label=y),
                       num_boost_round=4, checkpoint_prefix=prefix,
                       verbose_eval=False)
    assert out.current_iteration() == 4


def test_recompile_note_dispatch_attribution():
    obs.recompile.reset()
    base = obs.recompile.total()
    assert base == 0
    obs.recompile.note_dispatch("fn_x", 128, 1)   # may or may not grow
    first = obs.recompile.total("fn_x")
    obs.recompile.note_dispatch("fn_x", 128, 1)   # same size: no growth
    assert obs.recompile.total("fn_x") == first
    obs.recompile.note_dispatch("fn_x", 1024, 3)  # +2 at the 1024 bucket
    assert obs.recompile.counts()[("fn_x", "1024")] == 2


def test_recompiles_scoped_per_run():
    """A second telemetry run must not inherit the first run's recompile
    counts (process-global counters, per-run baseline)."""
    from lightgbm_tpu.obs.report import summarize
    obs.recompile.record("fn_scoped", "b1")
    tele1 = obs.configure(freq=1)
    obs.recompile.record("fn_scoped", "b1", 2)
    s1 = summarize(tele1)
    assert s1["recompiles"].get("fn_scoped|b1") == 2, s1["recompiles"]
    tele2 = obs.configure(freq=1)  # fresh run: baseline includes all 3
    s2 = summarize(tele2)
    assert "fn_scoped|b1" not in s2["recompiles"]
    assert s2["recompile_total"] == 0
    # a reset inside the run re-zeroes the baseline: later compiles show
    obs.recompile.reset()
    obs.recompile.record("fn_scoped", "b1")
    s3 = summarize(tele2)
    assert s3["recompiles"].get("fn_scoped|b1") == 1


def test_host_phases_scoped_per_run():
    from lightgbm_tpu.obs.report import summarize
    with spans.span("phase_scoped"):
        time.sleep(0.06)
    tele = obs.configure(freq=1)
    s = summarize(tele)
    assert "phase_scoped" not in s["host_phases"]
    with spans.span("phase_scoped"):
        time.sleep(0.02)
    s2 = summarize(tele)
    assert 0.01 < s2["host_phases"]["phase_scoped"] < 1.0
    # a reset inside the run: totals under the baseline are the run's own
    spans.reset()
    with spans.span("phase_scoped"):
        time.sleep(0.02)
    s3 = summarize(tele)
    assert 0.01 < s3["host_phases"]["phase_scoped"] < 0.06


def test_resumed_run_iterations_not_inflated(tmp_path):
    """A checkpoint-resumed run's telemetry counts only the iterations it
    trained (its wall covers only this process)."""
    from lightgbm_tpu.checkpoint import load_checkpoint
    b1, _, _ = _toy_booster(num_iterations=4, snapshot_freq=2,
                            snapshot_keep=0, metric_freq=10)
    prefix = str(tmp_path / "m.txt")
    b1.train(snapshot_out=prefix)
    meta, arrays, model_str = load_checkpoint(prefix + ".ckpt_iter_2")
    b2, _, _ = _toy_booster(num_iterations=4, snapshot_freq=2,
                            snapshot_keep=0, metric_freq=10)
    b2.restore_train_state(meta, arrays, model_str)
    assert b2.iter_ == 2
    tele = obs.configure(freq=1)
    b2.train(None)
    assert b2.iter_ == 4
    assert tele.gauge("train_iterations").value == 2  # not 4


# ---- zero-overhead when off ----

def test_telemetry_off_hot_loop_makes_zero_calls(monkeypatch, tmp_path):
    """With telemetry disabled (the default), a fused-scan training run and
    a predict loop must export NOTHING: no events, no metric touches, no
    span ids drawn or span events written, no exporter listener thread
    (round 14 extends the spy over obs/spans.py and obs/exporter.py).  A
    span's in-memory record (always on, like obs.recompile) is all it does.
    The resilience paths are held to the same contract: a degraded-predict
    fallback and a retried I/O fault are counted in their always-on module
    counters but make zero telemetry calls when no run is active."""
    calls = []

    def spy(name):
        orig = getattr(Telemetry, name)

        def wrapper(self, *a, **k):
            calls.append((name, a))
            return orig(self, *a, **k)
        return wrapper

    for name in ("event", "counter", "gauge", "histogram", "time_block"):
        monkeypatch.setattr(Telemetry, name, spy(name))
    # span + exporter paths: zero ids drawn, zero record_span emissions,
    # zero exporter starts with telemetry off
    from lightgbm_tpu.obs import exporter as obs_exporter
    from lightgbm_tpu.obs import spans as obs_spans
    monkeypatch.setattr(
        obs_spans, "record_span",
        lambda *a, **k: calls.append(("record_span", a)))
    monkeypatch.setattr(
        obs_spans, "new_id",
        lambda *a, **k: calls.append(("new_id", a)))
    obs_spans.reset()
    monkeypatch.setattr(
        obs_exporter, "start_exporter",
        lambda *a, **k: calls.append(("start_exporter", a)))
    monkeypatch.setattr(
        obs_exporter.MetricsExporter, "__init__",
        lambda self, *a, **k: calls.append(("MetricsExporter", a)))
    # quality plane (round 15): zero monitor constructions, zero observes,
    # zero baseline builds with telemetry off — over the serving scheduler,
    # the binned predict hook and the registry provenance notes alike
    from lightgbm_tpu.obs import quality as obs_quality
    monkeypatch.setattr(
        obs_quality.QualityMonitor, "__init__",
        lambda self, *a, **k: calls.append(("QualityMonitor", a)))
    monkeypatch.setattr(
        obs_quality.QualityMonitor, "observe",
        lambda self, *a, **k: calls.append(("quality_observe", a)))
    monkeypatch.setattr(
        obs_quality.QualityBaseline, "from_model",
        classmethod(lambda cls, *a, **k: calls.append(("baseline", a))))
    # forensics plane (round 16): zero accountant/tracker/state/engine
    # constructions, zero notes/samples/captures with telemetry off
    from lightgbm_tpu.obs import alerts as obs_alerts
    from lightgbm_tpu.obs import compile as obs_compile
    from lightgbm_tpu.obs import devmem as obs_devmem
    from lightgbm_tpu.obs import profiling as obs_profiling
    monkeypatch.setattr(obs_compile.CompileAccounting, "__init__",
                        lambda self, *a, **k: calls.append(
                            ("CompileAccounting", a)))
    monkeypatch.setattr(obs_compile, "note_dispatch",
                        lambda *a, **k: calls.append(("compile_note", a)))
    monkeypatch.setattr(obs_devmem, "sample",
                        lambda *a, **k: calls.append(("devmem", a)))
    monkeypatch.setattr(obs_profiling, "capture",
                        lambda *a, **k: calls.append(("capture", a)))
    monkeypatch.setattr(obs_alerts.AlertEngine, "__init__",
                        lambda self, *a, **k: calls.append(
                            ("AlertEngine", a)))
    monkeypatch.setattr(obs_alerts, "note_incident",
                        lambda *a, **k: calls.append(("incident", a)))
    assert obs.active() is None
    booster, X, _ = _toy_booster(num_iterations=8)
    booster.train_chunk(8)
    # round 22: the quantized-gradient training path's chunk telemetry
    # (quant counters/gauges + kind="quant" events) is behind the same
    # tele-is-None gate and must stay silent too
    qb, _, _ = _toy_booster(n=512, num_iterations=2,
                            hist_precision="quantized")
    qb.train_chunk(2)
    booster.predict(X[:600])
    booster.predict_binned()  # the binned quality-hook path, off
    booster.predict_contrib(X[:64])  # the contrib plane (round 19), off
    booster.train(None)  # the driver path too
    # a serving round trip (the span-instrumented scheduler) stays silent
    # too, and no listener thread exists anywhere in the process
    from lightgbm_tpu.serving import Server
    with Server(max_batch_wait_us=0) as srv:
        srv.register("spy", booster)
        srv.predict("spy", X[:8])
    assert not any(t.name == "lgbm-tpu-metrics"
                   for t in threading.enumerate()), \
        "exporter listener running with telemetry off"
    with obs_spans.span("noop"):  # the off-path span: memory only
        pass
    kept = obs_spans.totals()
    assert kept["noop"]["count"] == 1 and "fused_train_chunk" in kept
    off = obs_spans.span("off")
    assert off.tele is None and off.span_id is None
    # degraded predict: the fallback counter must not touch Telemetry
    import lightgbm_tpu.core.predict_fused as pf
    real_pb = pf.predict_blocked
    monkeypatch.setattr(pf, "predict_blocked",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("injected")))
    booster._invalidate_predict_cache()
    booster.predict(X[:600])
    monkeypatch.setattr(pf, "predict_blocked", real_pb)
    # retried I/O fault: io_retry accounting stays off-Telemetry too
    import errno

    from lightgbm_tpu.utils import file_io
    state = {"n": 0}

    def eio_once(stage, path):
        if stage == "written" and state["n"] == 0:
            state["n"] += 1
            raise OSError(errno.EIO, "injected")

    file_io.set_fault_hook(eio_once)
    try:
        file_io.atomic_write(str(tmp_path / "t.txt"), "x")
    finally:
        file_io.set_fault_hook(None)
    assert calls == [], "telemetry-off run made %d telemetry calls: %r" % (
        len(calls), calls[:5])


def test_telemetry_off_no_events_attr_left():
    booster, _, _ = _toy_booster(num_iterations=4)
    assert obs.active() is None
    booster.train_chunk(4)
    # configure AFTER: nothing from the earlier run may leak in
    tele = obs.configure(freq=1)
    assert [e["kind"] for e in tele.events] == ["run_start"]


# ---- C-ABI impl layer ----

def test_c_api_telemetry_impls(tmp_path):
    from lightgbm_tpu.c_api import (_impl_telemetry_configure,
                                    _impl_telemetry_disable,
                                    _impl_telemetry_recompile_count,
                                    _impl_telemetry_summary)
    assert _impl_telemetry_summary() == ""
    out = str(tmp_path / "capi.jsonl")
    _impl_telemetry_configure(out, 2)
    tele = obs.active()
    assert tele is not None and tele.freq == 2
    tele.gauge("train_rows").set(10)
    s = json.loads(_impl_telemetry_summary())
    assert s["metric"] == "telemetry_run" and s["rows"] == 10
    assert _impl_telemetry_recompile_count() >= 0
    _impl_telemetry_disable()
    assert obs.active() is None
    assert _impl_telemetry_summary() == ""


# ---- the one host timer: the spans' totals (PR 39 folded the second) ----

def test_spans_nested_same_name_both_count():
    spans.reset()
    with spans.span("a"):
        time.sleep(0.02)
        with spans.span("a"):      # nested span of the SAME name
            time.sleep(0.02)
        inner = spans.seconds()["a"]
        assert inner >= 0.015
    # the OUTER one (~0.04) counts too
    assert spans.seconds()["a"] >= inner + 0.03
    assert spans.totals()["a"]["count"] == 2
    outer, nested = sorted(spans.records("a"), key=lambda r: r["start"])
    assert nested["parent"] == outer["id"]


def test_spans_reentrant_function():
    spans.reset()

    def rec(n):
        with spans.span("f"):
            if n:
                time.sleep(0.01)
                rec(n - 1)

    rec(3)
    # 4 nested spans of ~30/20/10/0 ms: total ~60ms, NOT just the leaf
    assert spans.seconds()["f"] >= 0.05
    assert spans.totals()["f"]["count"] == 4


def test_spans_threads_do_not_cross():
    spans.reset()

    def work(ms):
        with spans.span("w"):
            time.sleep(ms / 1000.0)

    with spans.span("main"):
        threads = [threading.Thread(target=work, args=(20,))
                   for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    # each thread closed its OWN span: ~4 * 20ms accumulated, and none of
    # them is a child of the span another thread holds open
    assert spans.seconds()["w"] >= 0.06
    assert [r["parent"] for r in spans.records("w")] == [0] * 4
    assert sorted(spans.seconds()) == ["main", "w"]


def test_spans_of_nothing():
    spans.reset()
    assert spans.seconds() == {} and "nope" not in spans.totals()
    assert spans.summary().splitlines() == [
        "LightGBM-TPU host timing summary:"]
    with spans.span("one"):
        pass
    assert spans.summary().splitlines()[1].startswith("  one: ")


def test_spans_reset_keeps_a_span_another_thread_holds_open():
    """A span opened before reset() on another thread is not lost: it is
    recorded when it closes (the totals never hold half of one)."""
    spans.reset()
    opened = threading.Event()
    go = threading.Event()

    def work():
        with spans.span("x"):
            opened.set()
            go.wait(timeout=5)     # closes AFTER the main thread's reset

    th = threading.Thread(target=work)
    th.start()
    opened.wait(timeout=5)
    spans.reset()
    assert "x" not in spans.seconds()
    go.set()
    th.join()
    assert spans.totals()["x"]["count"] == 1


# ---- round 22: quantized-training telemetry + died-run recovery ----

def test_quant_telemetry_counters_and_recovery(tmp_path):
    """A quantized run records the quant counters/gauges and kind="quant"
    events, the summary carries the quant block, and tools/obs_report.py
    rebuilds the same block from the raw events alone (died-run path).
    An exact run emits none of it."""
    import os
    import sys
    out = str(tmp_path / "q.jsonl")
    tele = obs.configure(out=out, freq=1)
    booster, _, _ = _toy_booster(n=512, num_iterations=4,
                                 hist_precision="quantized")
    booster.train_chunk(4)
    assert tele.counter("quant_chunks").value == 1
    assert tele.counter("quant_iters").value == 4
    assert tele.gauge("quant_grad_levels").value == 127
    assert tele.gauge("quant_hess_levels").value == 255
    assert tele.gauge("quant_hist_channels").value == 2
    from lightgbm_tpu.obs.report import finalize_run, human_table
    summary = finalize_run(tele, gbdt=booster, wall_s=1.0, iters=4)
    tele.flush()
    obs.disable()
    q = summary["quant"]
    assert q["chunks"] == 1 and q["iterations"] == 4
    assert q["grad_levels"] == 127 and q["hess_levels"] == 255
    assert q["hist_channels"] == 2
    assert "quant:" in human_table(summary)
    # died-run recovery: raw events alone rebuild the block (the event
    # stream has no summary to lean on)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    from lightgbm_tpu.obs.registry import read_events
    events = read_events(out)
    assert any(e["kind"] == "quant" and e["hist_channels"] == 2
               and e["exact_channels"] == 4 for e in events)
    rebuilt = obs_report.summary_from_events(events)
    rq = rebuilt["quant"]
    assert rq["recovered"] is True
    assert rq["chunks"] == 1 and rq["iterations"] == 4
    assert rq["grad_levels"] == 127 and rq["hist_channels"] == 2
    assert "quant:" in human_table(rebuilt)
    # an exact run's summary has no quant block
    tele2 = obs.configure(freq=1)
    b2, _, _ = _toy_booster(n=512, num_iterations=2)
    b2.train_chunk(2)
    from lightgbm_tpu.obs.report import summarize
    assert "quant" not in summarize(tele2)
    assert tele2.counter("quant_chunks").value == 0


# ---- nan_policy trips reach the telemetry counters ----

def test_nan_trip_counter(tmp_path):
    from lightgbm_tpu.utils.log import Log
    tele = obs.configure(freq=1)
    booster, _, _ = _toy_booster(num_iterations=3, nan_policy="clip")
    n = booster.num_data
    bad = np.full((1, n), np.nan, dtype=np.float32)
    good = np.ones((1, n), dtype=np.float32)
    lvl = Log._level
    Log.reset_level(Log.Level.FATAL)
    try:
        booster.train_one_iter(bad, good)
    finally:
        Log.reset_level(lvl)
    assert tele.counter("nan_policy_trips").value == 1
    kinds = [e["kind"] for e in tele.events]
    assert "nan_trip" in kinds


# ---- round 12: split-kernel launch accounting (always-on, like recompile) ----


def _fused_booster(iters=2, **params):
    """4096-row booster pinned to the interpret fused path (n % CHUNK == 0
    so the Pallas split pass engages off-TPU)."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    n = 4096
    rng = np.random.RandomState(3)
    X = rng.normal(size=(n, 8))
    y = X[:, 0] * 1.5 + np.sin(X[:, 1]) + rng.normal(scale=0.1, size=n)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=16)
    cfg = Config(dict(objective="regression", num_iterations=iters,
                      min_data_in_leaf=2, **params))
    b = GBDT(cfg, ds, create_objective("regression", cfg))
    b.learner.use_pallas = True
    b.learner.pallas_interpret = True
    return b


def test_tree_kernel_launches_leaf_wise_is_leaves_minus_one():
    """Leaf-wise growth dispatches exactly L-1 split launches per tree (the
    builder's fori_loop always runs its full budget; dead iterations still
    launch an empty-window pass)."""
    from lightgbm_tpu.obs import launches
    launches.reset()
    b = _fused_booster(iters=2, num_leaves=8)
    assert b._can_fuse_iters()
    b.train_chunk(2)
    assert launches.counts() == {"leaf": 2 * 7}
    assert launches.per_tree("leaf") == 7.0
    assert b.learner.launches_per_tree() == 7


def test_tree_kernel_launches_level_wise_bounded_by_depth_times_classes():
    """Level mode drops launches-per-tree from L-1 to
    <= depth * bucket-classes — the round-12 acceptance pin."""
    from lightgbm_tpu.obs import launches
    launches.reset()
    b = _fused_booster(iters=2, num_leaves=8, max_depth=3,
                       tree_grow_mode="level")
    assert b.learner.effective_grow_mode() == "level"
    b.train_chunk(2)
    classes = b.learner.level_classes()
    per_tree = launches.per_tree("level")
    assert per_tree is not None and per_tree <= 3 * classes
    assert launches.counts()["level"] == 2 * 3 * classes
    # strictly fewer dispatches than the leaf-wise L-1 for the same tree
    assert per_tree < b.config.num_leaves - 1


def test_tree_kernel_launches_per_iteration_path_counts_too():
    """The non-fused per-iteration path records through
    SerialTreeLearner.train (no pallas required: the counter tracks the
    builder's split-dispatch structure)."""
    from lightgbm_tpu.obs import launches
    b, _, _ = _toy_booster(num_iterations=2)
    b._fuse_failed = True  # force the per-iteration path
    launches.reset()
    b.train_chunk(2)
    assert launches.counts() == {"leaf": 2 * (b.config.num_leaves - 1)}


def test_tree_kernel_launches_in_summary_and_events(tmp_path):
    """A telemetry run's summary carries the run-scoped launch accounting
    (per growth mode, with launches-per-tree) and the registry counter."""
    from lightgbm_tpu.obs import launches
    from lightgbm_tpu.obs.report import finalize_run
    path = str(tmp_path / "t.jsonl")
    b = _fused_booster(iters=2, num_leaves=8, max_depth=3,
                       tree_grow_mode="level")
    tele = obs.configure(out=path, freq=1)
    b.train_chunk(2)
    summary = finalize_run(tele, gbdt=b, wall_s=1.0, iters=2)
    obs.disable()
    lv = summary["tree_kernel_launches"]["level"]
    assert lv["trees"] == 2
    assert lv["launches"] == summary["tree_kernel_launch_total"]
    assert lv["per_tree"] <= 3 * b.learner.level_classes()
    assert summary["counters"]["tree_kernel_launches"] == lv["launches"]
    table = __import__("lightgbm_tpu.obs.report",
                       fromlist=["human_table"]).human_table(summary)
    assert "launches[level]" in table


def test_level_schedule_capped_by_leaf_budget():
    """A 'just in case' huge max_depth must not blow up the level schedule:
    every live level grows >= 1 leaf, so levels past num_leaves-1 are
    guaranteed dead and the static schedule (and with it the launch
    counter's per-tree bound) is capped at L-1."""
    b = _fused_booster(iters=1, num_leaves=8, max_depth=63,
                       tree_grow_mode="level")
    assert b.learner.level_count() == 7
    assert b.learner.launches_per_tree() == 7 * b.learner.level_classes()
