"""``obs.spans``: the one span API.  The in-memory record is always on, the
profiler annotation always opened, the JSONL event written only under a
telemetry run (tests/test_obs_plane.py pins the event's nesting; the
telemetry-off pin is in tests/test_telemetry.py)."""
import threading

import jax
import numpy as np
import pytest

from lightgbm_tpu import obs
from lightgbm_tpu.obs import read_events, spans, validate_event


def _booster(n=4096, leaves=15, **params):
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    rng = np.random.RandomState(3)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + rng.normal(scale=0.5, size=n) > 0)
    ds = BinnedDataset.from_matrix(X, label=y.astype(np.float32), max_bin=63)
    cfg = Config(objective="binary", num_leaves=leaves, min_data_in_leaf=5,
                 verbosity=-1, **params)
    return GBDT(cfg, ds, create_objective("binary", cfg))


def test_nesting_and_parent_ids():
    spans.reset()
    with spans.span("outer") as outer:
        with spans.span("inner"):
            spans.note("measured_elsewhere", 0.25)
        with spans.span("inner"):
            pass
    recs = {r["id"]: r for r in spans.records()}
    assert [r["name"] for r in recs.values()] == [
        "measured_elsewhere", "inner", "inner", "outer"]   # in closing order
    inner1, inner2 = spans.records("inner")
    assert inner1["parent"] == inner2["parent"] == outer.seq
    assert recs[outer.seq]["parent"] == 0
    noted = spans.records("measured_elsewhere")[0]
    assert noted["parent"] == inner1["id"]
    assert noted["end"] - noted["start"] == pytest.approx(0.25)
    assert spans.current() is None


def test_a_thread_has_its_own_stack():
    spans.reset()
    seen = []
    with spans.span("main_thread"):
        t = threading.Thread(target=lambda: seen.append(spans.current()))
        t.start()
        t.join()
    assert seen == [None]


def test_totals_count_every_span_and_the_ring_is_bounded(monkeypatch):
    from collections import deque
    monkeypatch.setattr(spans, "_ring", deque(maxlen=8))
    spans.reset()
    for _ in range(20):
        with spans.span("tick"):
            pass
    assert len(spans.records()) == 8
    tot = spans.totals()["tick"]
    assert tot["count"] == 20 and tot["total_s"] >= tot["max_s"] > 0
    assert spans.RING == 65536
    spans.reset()
    assert spans.records() == [] and spans.totals() == {}


def test_telemetry_on_writes_the_same_jsonl_event(tmp_path):
    path = str(tmp_path / "sp.jsonl")
    spans.reset()
    obs.configure(out=path, freq=1)
    try:
        with spans.span("outer", phase="x"):
            with spans.span("inner"):
                pass
    finally:
        obs.disable()
    evs = [e for e in read_events(path) if e["kind"] == "span"]
    for e in evs:
        validate_event(e)
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert set(inner) >= {"trace_id", "span_id", "parent_id", "t0", "dur_s"}
    assert inner["parent_id"] == outer["span_id"] and outer["phase"] == "x"
    assert inner["trace_id"] == outer["trace_id"]
    # and the in-memory record is kept beside the export
    assert spans.totals()["outer"]["count"] == 1


def test_a_span_shows_in_a_profiler_trace(tmp_path):
    """Under any profiler session the span sits on the host line of the
    trace: that is what labels an idle gap of the device."""
    from jax.profiler import ProfileData
    import glob
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("gbdt.poll_stop"):
            jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    data = ProfileData.from_file(found[0])
    names = {e.name for p in data.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events}
    assert "gbdt.poll_stop" in names


def test_totals_after_two_fused_chunks_and_the_spans_one_chunk_opens():
    """Spans stay at dispatch granularity: a pin on how many one fused chunk
    opens (none per tree, per split or per row)."""
    g = _booster()
    g.train_chunk(4)                      # traces and compiles
    spans.reset()
    g.train_chunk(4)
    g._guard_chunk_scores()
    g.train_chunk(4)
    g._guard_chunk_scores()
    g.train_score.block_until_ready()
    counts = {n: t["count"] for n, t in spans.totals().items()
              if not n.startswith("jax.")}
    assert counts == {"fused_train_chunk": 2, "gbdt.guard_chunk_scores": 2}
    assert g._poll_stop() is False
    assert spans.totals()["gbdt.poll_stop"]["count"] == 1


def test_set_up_spans_of_one_booster():
    spans.reset()
    g = _booster()
    g.train_chunk(2)
    tot = spans.totals()
    for name in ("ingest.to_f64", "ingest.find_bins", "ingest.bin_columns",
                 "ingest.upload", "gbdt.construct", "gbdt.fused.trace"):
        assert tot[name]["count"] == 1, name
    upload, construct = spans.records("ingest.upload")[0], \
        spans.records("gbdt.construct")[0]
    assert upload["parent"] == construct["id"]
    # jax's own compile phases land under the span that caused them
    assert tot["jax.trace"]["count"] >= 1 and "jax.lower" in tot
    assert tot["jax.backend_compile"]["total_s"] >= 0.0


def test_set_up_spans_of_a_csr_ingest():
    from lightgbm_tpu.io.dataset import BinnedDataset
    rng = np.random.default_rng(3)
    level = rng.integers(0, 20, size=(4000, 3)) + np.arange(3) * 20
    indptr = np.arange(4001) * 3
    spans.reset()
    BinnedDataset.from_csr(indptr, level.reshape(-1), np.ones(12000), 60,
                           label=(level[:, 0] % 2).astype(np.float32))
    assert {n: t["count"] for n, t in spans.totals().items()} == {
        "ingest.csr_to_csc": 1, "ingest.find_bins": 1, "ingest.bin_columns": 1,
        "ingest.find_groups": 1, "ingest.bundle_columns": 1}


def test_spans_one_train_one_iter_opens():
    # balanced bagging keeps the per-iteration path (feature_fraction, this
    # test's lever before PR 36, trains in fused chunks now)
    g = _booster(pos_bagging_fraction=0.5, bagging_freq=1)
    assert not g._can_fuse_iters()
    g.train_one_iter()
    spans.reset()
    g.train_one_iter()
    counts = {n: t["count"] for n, t in spans.totals().items()
              if not n.startswith("jax.")}
    # gbdt.train_tree (PR 39: where FunctionTimer stood alone) is the call
    # into the learner, whichever learner; partition_build_tree its dispatch
    assert counts == {"gbdt.gradients": 1, "gbdt.bagging": 1,
                      "gbdt.train_tree": 1, "partition_build_tree": 1,
                      "gbdt.update_score": 1}


def test_cache_load_is_taken_out_of_backend_compile():
    """jax times a persistent-cache retrieval inside its backend-compile
    event; the listener reports the two apart."""
    from lightgbm_tpu.obs import compile as obs_compile
    spans.reset()
    obs_compile._on_jax_duration(obs_compile._CACHE_LOAD, 0.25)
    obs_compile._on_jax_duration(obs_compile._BACKEND_COMPILE, 0.75)
    obs_compile._on_jax_duration(obs_compile._BACKEND_COMPILE, 2.0)
    obs_compile._on_jax_duration("/jax/some/other_event", 9.0)
    tot = spans.totals()
    assert tot["jax.cache_load"]["total_s"] == pytest.approx(0.25)
    assert tot["jax.backend_compile"]["count"] == 2
    assert tot["jax.backend_compile"]["total_s"] == pytest.approx(2.5)
    assert set(tot) == {"jax.cache_load", "jax.backend_compile"}
