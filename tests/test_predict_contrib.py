"""Device-side ``pred_contrib`` (core/predict_contrib.py): the TreeSHAP
path-decomposition kernel pinned against the host ``Tree.predict_contrib``
scan on routing-stressing goldens (NaN, categorical bitsets, EFB, iteration
subsets, multiclass), the raw==binned bitwise identity, the sum-to-raw-score
invariant, the serving integration and the no-recompile cache pin.

Exactness contract (see the module docstring): the EAGER replay is pinned
bitwise identical to the host recursion — the schedule harvest is an
op-for-op transcription — while the jitted program is pinned to a few ULPs
(XLA:CPU legally refolds f64 chains and strips optimization barriers; PERF.md
round 19).  Routing is bit-exact everywhere by integer/boolean structure.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu import resilience
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.predict_contrib import (contrib_compile_count,
                                               contrib_scan,
                                               contrib_tree_block,
                                               harvest_contrib_host,
                                               predict_contrib_blocked,
                                               stack_contrib_blocked)
from lightgbm_tpu.core.predict_fused import PREDICT_BUCKETS, FusedPredictor
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.objective import create_objective

RTOL, ATOL = 1e-12, 1e-15


def _host_contrib(trees, X, ncol):
    """The host oracle: the per-tree TreeSHAP recursion in tree order —
    exactly GBDT.predict_contrib's degraded/host path for one class."""
    out = np.zeros((len(X), ncol), dtype=np.float64)
    for t in trees:
        out += t.predict_contrib(np.asarray(X, np.float32), ncol)
    return out


@pytest.fixture(scope="module")
def booster():
    rng = np.random.RandomState(7)
    n = 900
    X = rng.normal(size=(n, 9)).astype(np.float32)
    X[rng.uniform(size=X.shape) < 0.05] = np.nan   # missing routing
    y = (np.nan_to_num(X[:, 0]) + 0.4 * np.nan_to_num(X[:, 1])
         + rng.normal(scale=0.4, size=n) > 0).astype(np.float64)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
    cfg = Config(objective="binary", num_leaves=15, num_iterations=12,
                 learning_rate=0.2, max_bin=63)
    b = GBDT(cfg, ds, create_objective("binary", cfg))
    for _ in range(12):
        b.train_one_iter()
    return b, X, ds


def test_eager_replay_is_bitwise_host():
    """The schedule harvest + interpreter IS the host recursion: in eager
    execution (per-op IEEE, no compiler rewrites) the kernel's phi equals
    the host scan bit for bit — duplicate-feature unwinds included."""
    rng = np.random.RandomState(3)
    n = 400
    X = rng.normal(size=(n, 2)).astype(np.float32)  # 2 features ->
    y = (X[:, 0] + X[:, 1] ** 2 > 0).astype(np.float64)   # dup paths
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
    cfg = Config(objective="binary", num_leaves=16, num_iterations=4,
                 max_bin=63, min_data_in_leaf=5)
    b = GBDT(cfg, ds, create_objective("binary", cfg))
    for _ in range(4):
        b.train_one_iter()
    X = X[:64]  # eager per-op dispatch is slow; 64 rows pin the claim
    ncol = b.max_feature_idx + 2
    # the goldens must include duplicate-feature paths or the unwind
    # schedule is untested
    sched = harvest_contrib_host(b.models, ncol)
    assert sched.unw_act.any(), "no duplicate-feature unwind grown; " \
        "shrink the feature count"
    host = _host_contrib(b.models, X, ncol)
    blocks, _ = stack_contrib_blocked(b.models, ncol)
    with jax.enable_x64(True):
        with jax.disable_jit():
            phi = np.asarray(contrib_scan(blocks, jnp.asarray(X)))
    np.testing.assert_array_equal(phi, host)


def test_device_vs_host_binary(booster):
    b, X, _ = booster
    ncol = b.max_feature_idx + 2
    host = _host_contrib(b.models, X, ncol)
    resilience.reset_fallbacks()
    got = b.predict_contrib(X)
    # a clean device call serves nothing degraded: device-vs-host agreement
    # alone also holds when the host scan quietly answered instead
    assert resilience.fallback_counts() == {}
    assert got.shape == (len(X), ncol)
    np.testing.assert_allclose(got, host, rtol=RTOL, atol=ATOL)


def test_sum_to_raw_score_invariant(booster):
    b, X, _ = booster
    got = b.predict_contrib(X)
    raw = np.zeros(len(X))
    for t in b.models:
        raw += t.predict(np.asarray(X, np.float32))
    np.testing.assert_allclose(got.sum(axis=1), raw, rtol=1e-9, atol=1e-12)


def test_raw_vs_binned_bitwise(booster):
    """Training rows route identically through the u8 binned decide and
    the f32 raw decide, and the f64 schedule halves of both programs are
    the same HLO — pinned BITWISE identical."""
    b, X, ds = booster
    raw = b.predict_contrib(X)
    binned = b.predict_contrib_binned()
    np.testing.assert_array_equal(raw, binned)


@pytest.mark.parametrize("n", [PREDICT_BUCKETS[0] - 1, PREDICT_BUCKETS[0],
                               PREDICT_BUCKETS[0] + 1])
def test_bucket_boundary_parity(booster, n):
    """N at ladder-1 / ladder / ladder+1: padded rows never leak phi."""
    b, X, _ = booster
    ncol = b.max_feature_idx + 2
    host = _host_contrib(b.models, X[:n], ncol)
    np.testing.assert_allclose(b.predict_contrib(X[:n]), host,
                               rtol=RTOL, atol=ATOL)


def test_iteration_subsets(booster):
    b, X, _ = booster
    ncol = b.max_feature_idx + 2
    host = _host_contrib(b.models[3:8], X[:200], ncol)
    got = b.predict_contrib(X[:200], num_iteration=5, start_iteration=3)
    np.testing.assert_allclose(got, host, rtol=RTOL, atol=ATOL)
    # host path (below the device row floor) takes the same range
    got_small = b.predict_contrib(X[:4], num_iteration=5, start_iteration=3)
    np.testing.assert_array_equal(got_small,
                                  _host_contrib(b.models[3:8], X[:4], ncol))


def test_no_recompile_cache_pin(booster):
    """Contrib serving contract: repeated contrib predicts at ANY batch
    size inside warmed buckets never grow the compiled-program count."""
    b, X, _ = booster
    b.predict_contrib(X[:300])          # warm the 1024 bucket
    b.predict_contrib(X[:90])           # warm the 128 bucket
    base = contrib_compile_count()
    for n in (300, 700, 90, 128, 33, 512):
        b.predict_contrib(X[:n])
    assert contrib_compile_count() == base, \
        "steady-state contrib batch sizes inside warmed buckets recompiled"


def test_multiclass_concat():
    rng = np.random.RandomState(11)
    n = 400
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = (np.abs(X[:, 0]) + X[:, 1] > 1).astype(np.float64) \
        + (X[:, 2] > 0.5)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=31)
    cfg = Config(objective="multiclass", num_class=3, num_leaves=7,
                 num_iterations=5, max_bin=31)
    b = GBDT(cfg, ds, create_objective("multiclass", cfg))
    for _ in range(5):
        b.train_one_iter()
    K = b.num_tree_per_iteration
    assert K == 3
    ncol = b.max_feature_idx + 2
    got = b.predict_contrib(X)
    assert got.shape == (n, K * ncol)
    for k in range(K):
        host_k = _host_contrib(b.models[k::K], X, ncol)
        np.testing.assert_allclose(got[:, k * ncol:(k + 1) * ncol], host_k,
                                   rtol=RTOL, atol=ATOL)


def test_categorical_and_unseen_routing():
    """Categorical bitsets, unseen categories and NaN route on device
    exactly like the host recursion (phi agreement at tolerance pins the
    routing: a single mis-routed row moves phi at the 1e-2 scale)."""
    rng = np.random.RandomState(0)
    n, n_cats = 800, 40
    cat = rng.randint(0, n_cats, size=n)
    y = np.isin(cat, [0, 3, 7, 33]) * 3.0 + rng.normal(scale=0.2, size=n)
    X = np.column_stack([cat.astype(np.float64), rng.normal(size=n)])
    ds = BinnedDataset.from_matrix(X, label=y, categorical_feature=[0])
    cfg = Config(objective="regression", num_leaves=7, min_data_per_group=10,
                 cat_smooth=1.0, max_cat_to_onehot=4, num_iterations=8)
    b = GBDT(cfg, ds, create_objective("regression", cfg))
    for _ in range(8):
        b.train_one_iter()
    assert any(t.num_cat > 0 for t in b.models), "no categorical split"
    Xq = np.concatenate([X, [[99.0, 0.0], [np.nan, 0.0], [-3.0, 0.0]]])
    ncol = b.max_feature_idx + 2
    host = _host_contrib(b.models, Xq, ncol)
    np.testing.assert_allclose(b.predict_contrib(Xq), host,
                               rtol=RTOL, atol=1e-12)
    # binned identity on the training rows
    np.testing.assert_array_equal(b.predict_contrib(X),
                                  b.predict_contrib_binned())


def test_efb_unfold_binned_path():
    """Mutually exclusive sparse features bundle under EFB: the binned
    contrib path unfolds group codes exactly like the score path, pinned
    bitwise against the raw kernel and at tolerance against the host."""
    rng = np.random.RandomState(5)
    n, f = 700, 12
    X = np.zeros((n, f))
    owner = rng.randint(0, f, size=n)
    X[np.arange(n), owner] = rng.uniform(1, 5, size=n)  # one-hot-ish
    y = (owner % 3 == 0) * 2.0 + rng.normal(scale=0.1, size=n)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=31)
    assert ds.binned is not None and ds.binned.shape[1] < f, \
        "EFB did not bundle the mutually exclusive features"
    cfg = Config(objective="regression", num_leaves=7, num_iterations=6,
                 max_bin=31, min_data_in_leaf=5)
    b = GBDT(cfg, ds, create_objective("regression", cfg))
    for _ in range(6):
        b.train_one_iter()
    ncol = b.max_feature_idx + 2
    raw = b.predict_contrib(X)
    np.testing.assert_array_equal(raw, b.predict_contrib_binned())
    np.testing.assert_allclose(raw, _host_contrib(b.models, X, ncol),
                               rtol=RTOL, atol=ATOL)


def test_sharded_matches_single_device(booster):
    b, X, _ = booster
    from lightgbm_tpu.parallel import default_mesh, sharded_predict_contrib
    ncol = b.max_feature_idx + 2
    fp = FusedPredictor(b.models)
    single = fp.predict_contrib(X, ncol)
    got = sharded_predict_contrib(fp.contrib_blocks(ncol),
                                  np.asarray(X, np.float32), ncol,
                                  default_mesh(8))
    # a different compiled program (shard_map body): ULP-level agreement
    np.testing.assert_allclose(got, single, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(got, _host_contrib(b.models, X, ncol),
                               rtol=RTOL, atol=ATOL)


def test_degraded_fallback_counted(booster, monkeypatch):
    """A failing blocked contrib dispatch serves DEGRADED through the g=1
    contrib program — counted via resilience.note_fallback, ULP-equal."""
    b, X, _ = booster
    import lightgbm_tpu.core.predict_contrib as pc
    ncol = b.max_feature_idx + 2
    fp = FusedPredictor(b.models)
    want = fp.predict_contrib(X[:100], ncol)
    resilience.reset_fallbacks()
    monkeypatch.setattr(pc, "predict_contrib_blocked",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("injected")))
    fp2 = FusedPredictor(b.models)
    got = fp2.predict_contrib(X[:100], ncol)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    counts = resilience.fallback_counts()
    assert counts.get("predict_contrib_blocked") == 1, counts
    # double failure (blocked AND g=1 program): the host TreeSHAP net
    # serves raw requests — bitwise the host oracle — and is counted
    monkeypatch.setattr(pc, "predict_contrib_scan_fallback",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("injected too")))
    fp3 = FusedPredictor(b.models)
    got3 = fp3.predict_contrib(X[:40], ncol)
    np.testing.assert_array_equal(got3, _host_contrib(b.models, X[:40],
                                                      ncol))
    assert resilience.fallback_counts().get("predict_contrib") == 1


def test_serving_contrib_requests(booster):
    """The per-request pred_contrib knob: contrib and score requests ride
    the same scheduler without mixing batches; responses equal the direct
    device path bitwise (same compiled programs); single-row contrib
    requests take the batched dispatch, not the compiled if/else chain."""
    b, X, _ = booster
    from lightgbm_tpu.serving import Server
    ncol = b.max_feature_idx + 2
    fp = FusedPredictor(b.models)
    want = fp.predict_contrib(X[:64], ncol)
    with Server(max_batch_wait_us=200, single_row_fast=True) as srv:
        srv.register("m", b)
        futs = [srv.submit("m", X[:64], pred_contrib=True),
                srv.submit("m", X[:1], pred_contrib=True),
                srv.submit("m", X[:64], raw_score=True)]
        np.testing.assert_array_equal(futs[0].result(timeout=600), want)
        np.testing.assert_array_equal(futs[1].result(timeout=600),
                                      want[:1])
        np.testing.assert_array_equal(futs[2].result(timeout=600),
                                      fp(X[:64]))
        assert srv.stats()["single_row_fast"] == 0, \
            "single-row contrib must fall back to batched dispatch"
        assert srv.stats()["dropped"] == 0


def test_contrib_tree_block_sizing():
    assert contrib_tree_block(100, 1 << 14, vmem_bytes=1 << 20) == 50
    assert contrib_tree_block(10, 1 << 30, vmem_bytes=1 << 20) == 1
    assert contrib_tree_block(3, 64, vmem_bytes=1 << 20) == 3


def test_contrib_telemetry_block(booster, tmp_path):
    """contrib_latency_s histograms + counters flow into the summary's
    contrib block, and the died-run recovery rebuilds it from events."""
    b, X, _ = booster
    import json
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs.report import summarize
    out = str(tmp_path / "t.jsonl")
    tele = obs.configure(out=out, freq=1)
    try:
        b.predict_contrib(X[:64])
        summary = summarize(tele)
    finally:
        obs.disable()
    ctb = summary.get("contrib")
    assert ctb and ctb["calls"] >= 1 and ctb["rows"] >= 64
    assert "128" in ctb["latency_s"]
    # died-run recovery from the JSONL events
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from obs_report import summary_from_events
    events = [json.loads(line) for line in open(out)]
    rec = summary_from_events(events)
    assert rec.get("contrib", {}).get("calls", 0) >= 1
    assert rec["contrib"]["recovered"] is True


def test_cli_serve_contrib(tmp_path):
    """task=serve predict_contrib=true serves SHAP through the scheduler
    and matches task=predict's contrib output file exactly; the
    predict_leaf_index refusal stays (and names the binned alternative)."""
    from lightgbm_tpu.cli import Application
    rng = np.random.RandomState(2)
    X = rng.normal(size=(700, 5))
    y = (X[:, 0] > 0).astype(float)
    train = str(tmp_path / "d.train")
    with open(train, "w") as fh:
        for row, lab in zip(X[:600], y[:600]):
            fh.write("%g\t" % lab + "\t".join("%g" % v for v in row) + "\n")
    test = str(tmp_path / "d.test")
    with open(test, "w") as fh:
        for row, lab in zip(X[600:], y[600:]):
            fh.write("%g\t" % lab + "\t".join("%g" % v for v in row) + "\n")
    model = str(tmp_path / "model.txt")
    Application(["task=train", "data=%s" % train, "objective=binary",
                 "num_trees=5", "num_leaves=7", "output_model=%s" % model,
                 "verbosity=-1"]).run()
    out_p = str(tmp_path / "p.txt")
    out_s = str(tmp_path / "s.txt")
    Application(["task=predict", "data=%s" % test, "input_model=%s" % model,
                 "predict_contrib=true", "output_result=%s" % out_p,
                 "verbosity=-1"]).run()
    Application(["task=serve", "data=%s" % test, "input_model=%s" % model,
                 "predict_contrib=true", "output_result=%s" % out_s,
                 "max_batch_wait_us=2000", "verbosity=-1"]).run()
    a, s = np.loadtxt(out_p), np.loadtxt(out_s)
    assert a.shape == (100, 6)   # F+1 columns
    np.testing.assert_array_equal(a, s)
    with pytest.raises(Exception, match="predict_leaf_index_binned"):
        Application(["task=serve", "data=%s" % test,
                     "input_model=%s" % model, "predict_leaf_index=true",
                     "output_result=%s" % out_s, "verbosity=-1"]).run()
