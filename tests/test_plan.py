"""Kernel planner + persisted autotuner (lightgbm_tpu/plan, round 18).

Pins the acceptance contract of ISSUE 14:

- ANALYTIC PARITY GOLDENS: with no plan cache present, every produced
  plan is byte-equal to the hand-tuned constants at the four original
  sites (bucket ladder / level ladder / histogram layout / predict
  tree-block + bucket rungs) — the refactor is behavior-neutral by
  default.
- TUNED-PLAN A/B PIN: a deliberately different-but-valid plan produces a
  bit-identical model and bit-identical scores (plans change dispatch
  shape only, never numerics).
- ROBUSTNESS: corrupt / version-mismatched / wrong-device / doctored
  caches degrade to analytic with ONE warning and the always-on
  ``plan_cache_fallbacks`` counter.
- PROVENANCE: stamps reach the telemetry summary (and the perf gate
  checks them there).
"""
import json
import os

import numpy as np
import pytest

from lightgbm_tpu.core.histogram import (_factored_geometry, _use_factored)
from lightgbm_tpu.core.partition import (CHUNK, SMALL_CHUNK,
                                         fused_bucket_plan, level_plan)
from lightgbm_tpu.core.predict_fused import (PREDICT_BUCKETS, FusedPredictor,
                                             tree_block)
from lightgbm_tpu.plan import autotune, cache as plan_cache
from lightgbm_tpu.plan import device_specs, planner
from lightgbm_tpu.plan import state as plan_state


@pytest.fixture(autouse=True)
def _clean_plan_state():
    """Every test starts with no engaged cache, no pin, zeroed counters."""
    plan_state.reset()
    plan_cache.reset_fallbacks()
    yield
    plan_state.reset()
    plan_cache.reset_fallbacks()


def _sc(n=4096, f=8, b=32, **kw):
    kw.setdefault("device_kind", "cpu")
    return planner.shape_class(n, f, b, **kw)


# ---- analytic parity goldens -------------------------------------------

# the pinned shape set from the ISSUE: Higgs-like, wide-F factored
# (F=968 @ 63 bins), wide-F classic (F=600 @ 256 bins), plus the ladder
# boundary rows (992 / 16384 straddles) and a sub-chunk store
PARITY_SHAPES = [
    (11_000_000, 28, 256),   # Higgs-like
    (65_536, 968, 64),       # Bosch-like wide-F factored
    (65_536, 600, 256),      # wide-F classic
    (512, 8, 32), (992, 8, 32), (993, 8, 32),
    (4096, 8, 32), (16_384, 8, 32), (16_385, 8, 32), (1 << 20, 8, 32),
]


@pytest.mark.parametrize("n,f,b", PARITY_SHAPES)
def test_analytic_plan_matches_hand_tuned_constants(n, f, b):
    plan = planner.analytic_plan(_sc(n, f, b))
    assert plan.provenance == "analytic"
    assert plan.bucket_plan == fused_bucket_plan(n)
    assert plan.level_ladder == level_plan(n)
    assert plan.hist_factored == _use_factored(f, b)
    assert plan.hist_groups == _factored_geometry(f, b)[1]
    assert plan.predict_buckets == tuple(PREDICT_BUCKETS)
    assert plan.hist_accum_budget_bytes == 4 << 20
    assert plan.predict_block_vmem_bytes == 1 << 20
    planner.validate_plan(plan, n)


def test_analytic_hist_layout_goldens():
    """The two wide-F regimes the round-6 kernels were pinned on: F=968
    factored at 63 bins, F=600x256 classic (accumulator past the 4 MiB
    gate)."""
    assert planner.analytic_plan(_sc(65_536, 968, 64)).hist_factored
    assert not planner.analytic_plan(_sc(65_536, 600, 256)).hist_factored
    # Higgs-like narrow-F large-B stays factored
    assert planner.analytic_plan(_sc(4096, 28, 256)).hist_factored


def test_analytic_tree_block_parity():
    """Planner-sized predict blocks equal predict_fused.tree_block for a
    grid of model shapes (incl. the shapes each PREDICT_BUCKETS rung
    serves — G depends on the model, not the rung, so one G per model
    covers the whole ladder)."""
    plan = planner.analytic_plan(_sc())
    for t, m, l in [(1, 1, 2), (100, 31, 32), (100, 255, 256),
                    (500, 1023, 1024), (64, 7, 8), (1000, 63, 64)]:
        assert planner.tree_block_for(plan, t, m, l) == tree_block(t, m, l)


def test_resolve_analytic_equals_site_defaults():
    """state.resolve with nothing engaged IS the analytic plan — and the
    site-facing overrides report nothing (sites keep their historical
    defaults)."""
    for n, f, b in PARITY_SHAPES:
        assert plan_state.resolve(n, f, b) == planner.analytic_plan(
            planner.shape_class(n, f, b))
    assert plan_state.hist_layout_override(968, 64) is None
    assert plan_state.predict_block_vmem() is None
    assert plan_state.current_provenance() == "analytic"


def test_device_specs_single_source_of_truth():
    """The VMEM budgets all come from plan/device_specs.py — one row
    per device_kind."""
    v5e = device_specs.spec_for("tpu v5 lite")
    assert v5e.vmem_bytes == 16 << 20
    assert device_specs.hist_accum_budget_bytes("v5e") == 4 << 20
    # a host without a chip keeps the v5e-shaped budgets (analytic
    # byte-equality everywhere); a device nobody looked up is an error,
    # not a default
    cpu = device_specs.spec_for("cpu")
    assert cpu.vmem_bytes == 16 << 20
    with pytest.raises(ValueError, match="warp-drive-9000"):
        device_specs.spec_for("warp-drive-9000")
    from lightgbm_tpu.core.predict_fused import BLOCK_VMEM_BYTES
    assert BLOCK_VMEM_BYTES == device_specs.PREDICT_BLOCK_VMEM_BYTES


# ---- plan validation ----------------------------------------------------


def test_validate_plan_rejects_malformed_schedules():
    base = planner.analytic_plan(_sc())
    bad = [
        ("chunk", base._replace(bucket_plan=((False, 2048, None),))),
        ("order", base._replace(bucket_plan=((True, SMALL_CHUNK, 992),
                                             (False, CHUNK, 100),
                                             (False, CHUNK, None)))),
        ("bounded-last", base._replace(bucket_plan=((False, CHUNK, 100),))),
        ("small-bound", base._replace(bucket_plan=((True, SMALL_CHUNK, 1024),
                                                   (False, CHUNK, None)))),
        ("small-chunk", base._replace(bucket_plan=((True, CHUNK, 992),
                                                   (False, CHUNK, None)))),
        ("mid-small", base._replace(bucket_plan=((False, CHUNK, 100),
                                                 (True, SMALL_CHUNK, None)))),
        ("empty", base._replace(level_ladder=())),
        ("prov", base._replace(provenance="vibes")),
        ("buckets", base._replace(predict_buckets=(128, 128))),
        ("vmem", base._replace(predict_block_vmem_bytes=0)),
    ]
    for name, plan in bad:
        with pytest.raises(ValueError):
            planner.validate_plan(plan)
        del name
    planner.validate_plan(base)  # and the analytic plan always passes


# ---- persisted cache ----------------------------------------------------


def test_cache_round_trip(tmp_path):
    sc = _sc(8192, 8, 32)
    tuned = planner.analytic_plan(sc)._replace(
        bucket_plan=((False, CHUNK, None),),
        level_ladder=((False, CHUNK, None),))
    cache = plan_cache.PlanCache(device_kind="cpu")
    cache.put(sc, tuned, metrics={"train": 1.25})
    path = cache.save(str(tmp_path / "plans.json"))
    loaded = plan_cache.load_cache(path, device_kind="cpu")
    assert loaded is not None
    got = loaded.lookup(sc)
    assert got is not None and got.provenance == "tuned"
    assert got.bucket_plan == ((False, CHUNK, None),)
    assert got.predict_buckets == tuned.predict_buckets
    # same power-of-two class, different exact n: the entry still serves
    assert loaded.lookup(_sc(8000, 8, 32)) is not None
    # different class: miss (analytic), NOT a fallback
    assert loaded.lookup(_sc(1 << 20, 8, 32)) is None
    assert plan_cache.fallback_count() == 0


def _warn_counter(monkeypatch):
    from lightgbm_tpu.utils.log import Log
    hits = []
    orig = Log.warning

    def counting(msg, *a):
        if "plan cache" in str(msg):
            hits.append(msg)
        orig(msg, *a)
    monkeypatch.setattr(Log, "warning", staticmethod(counting))
    return hits


def test_cache_corrupt_falls_back_with_one_warning(tmp_path, monkeypatch):
    hits = _warn_counter(monkeypatch)
    path = str(tmp_path / "plans.json")
    with open(path, "w") as fh:
        fh.write("{ not json")
    assert plan_cache.load_cache(path) is None
    assert plan_cache.load_cache(path) is None  # second engagement
    assert plan_cache.fallback_count() == 2
    assert len(hits) == 1, "the fallback warning must fire exactly once"


def test_cache_version_and_device_mismatch(tmp_path):
    sc = _sc()
    cache = plan_cache.PlanCache(device_kind="cpu")
    cache.put(sc, planner.analytic_plan(sc))
    path = cache.save(str(tmp_path / "plans.json"))
    doc = json.load(open(path))
    # version bump -> fallback
    doc_v = dict(doc, version=99)
    p_v = str(tmp_path / "v.json")
    json.dump(doc_v, open(p_v, "w"))
    assert plan_cache.load_cache(p_v, device_kind="cpu") is None
    # plan-schema bump -> fallback
    doc_s = dict(doc, plan_schema=99)
    p_s = str(tmp_path / "s.json")
    json.dump(doc_s, open(p_s, "w"))
    assert plan_cache.load_cache(p_s, device_kind="cpu") is None
    # a cache tuned on another device is stale here -> fallback
    doc_d = dict(doc, device_kind="tpu v5 lite")
    p_d = str(tmp_path / "d.json")
    json.dump(doc_d, open(p_d, "w"))
    assert plan_cache.load_cache(p_d, device_kind="cpu") is None
    assert plan_cache.fallback_count() == 3
    # missing file is the documented silent default, NOT a fallback
    before = plan_cache.fallback_count()
    assert plan_cache.load_cache(str(tmp_path / "nope.json")) is None
    assert plan_cache.fallback_count() == before


def test_cache_doctored_entry_falls_back_at_lookup(tmp_path):
    sc = _sc()
    cache = plan_cache.PlanCache(device_kind="cpu")
    key = cache.put(sc, planner.analytic_plan(sc))
    # doctor the persisted entry into an INVALID dispatch shape (chunk
    # 2048 exists in no kernel variant)
    cache.entries[key]["plan"]["bucket_plan"] = [[False, 2048, None]]
    path = cache.save(str(tmp_path / "plans.json"))
    loaded = plan_cache.load_cache(path, device_kind="cpu")
    assert loaded is not None
    assert loaded.lookup(sc) is None
    assert plan_cache.fallback_count() == 1


def test_resolve_precedence_pinned_over_tuned(tmp_path):
    sc = _sc(8192, 8, 32)
    tuned = planner.analytic_plan(sc)._replace(
        bucket_plan=((False, CHUNK, None),),
        level_ladder=((False, CHUNK, None),))
    cache = plan_cache.PlanCache(device_kind="cpu")
    cache.put(sc, tuned)
    path = cache.save(str(tmp_path / "plans.json"))
    assert plan_state.configure(path) is not None
    got = plan_state.resolve(8192, 8, 32, device_kind="cpu")
    assert got.provenance == "tuned"
    assert got.bucket_plan == ((False, CHUNK, None),)
    assert plan_state.current_provenance() == "tuned"
    # a pin outranks the engaged cache
    pin = planner.analytic_plan(sc)._replace(
        bucket_plan=((False, SMALL_CHUNK, None),),
        level_ladder=((False, SMALL_CHUNK, None),))
    with plan_state.pinned(pin):
        got = plan_state.resolve(8192, 8, 32, device_kind="cpu")
        assert got.provenance == "pinned"
        assert got.bucket_plan == ((False, SMALL_CHUNK, None),)
    # unknown shape under the cache: analytic, silently
    assert plan_state.resolve(1 << 20, 8, 32,
                              device_kind="cpu").provenance == "analytic"


def test_pinned_plan_overrides_tree_block_and_hist_layout():
    sc = _sc()
    base = planner.analytic_plan(sc)
    g0 = tree_block(100, 31, 32)
    pin = base._replace(predict_block_vmem_bytes=31 * 32 * 4 * 2,
                        hist_factored=not base.hist_factored)
    with plan_state.pinned(pin):
        assert tree_block(100, 31, 32) == 2       # two trees fit the pin
        assert _use_factored(8, 32) == pin.hist_factored
    assert tree_block(100, 31, 32) == g0
    assert _use_factored(8, 32) == base.hist_factored


# ---- A/B bit-exactness pins --------------------------------------------


def _toy_booster(n, monkeypatch_learner=None, iters=2, **params):
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective

    rng = np.random.RandomState(3)
    X = rng.normal(size=(n, 8))
    y = X[:, 0] * 1.5 + np.sin(X[:, 1]) + rng.normal(scale=0.1, size=n)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=16)
    base = dict(objective="regression", num_leaves=8, num_iterations=iters,
                min_data_in_leaf=2)
    base.update(params)
    cfg = Config(base)
    booster = GBDT(cfg, ds, create_objective("regression", cfg))
    if monkeypatch_learner is not None:
        monkeypatch_learner(booster.learner)
    return booster


def test_tuned_plan_train_bit_identical(tmp_path):
    """The tuned-plan A/B pin: a full fused train under a deliberately
    different-but-valid plan (engaged through the REAL cache->resolve->
    learner path) is bit-identical to the analytic run."""
    n = 4096
    # max_bin=16 -> every group fits a nibble, so the learner keys its
    # shape class with packed=True (two bin codes per byte)
    sc = planner.shape_class(n, 8, 32, packed=True)
    tuned = planner.analytic_plan(sc)._replace(
        bucket_plan=((False, CHUNK, None),),
        level_ladder=((False, CHUNK, None),))
    cache = plan_cache.PlanCache(device_kind=sc.device_kind)
    cache.put(sc, tuned)
    path = cache.save(str(tmp_path / "plans.json"))

    results = {}
    for mode in ("analytic", "tuned"):
        plan_state.reset()
        if mode == "tuned":
            assert plan_state.configure(path) is not None

        def pin(learner):
            learner.use_pallas = True
            learner.pallas_interpret = True

        b = _toy_booster(n, pin, iters=2)
        if mode == "tuned":
            assert b.learner.plan.provenance == "tuned"
            assert b.learner.bucket_plan == ((False, CHUNK, None),)
        else:
            assert b.learner.plan.provenance == "analytic"
            assert b.learner.bucket_plan is None
        assert b._can_fuse_iters()
        b.train_chunk(2)
        results[mode] = (b.save_model_to_string(),
                         np.asarray(b.train_score).copy())
        del b

    assert results["analytic"][0] == results["tuned"][0], \
        "tuned plan changed the MODEL — plans must be dispatch-only"
    np.testing.assert_array_equal(results["analytic"][1],
                                  results["tuned"][1])
    assert plan_cache.fallback_count() == 0


def test_tuned_plan_predict_bit_identical():
    """Scores under a non-default predict tree-block G (via a pinned
    plan's VMEM budget) are bit-identical to the default blocking, and
    the steady-state dispatch never recompiles."""
    b = _toy_booster(800, None, iters=3)
    b.train()
    trees = list(b.models)
    X = np.random.RandomState(5).normal(size=(200, 8)).astype(np.float32)
    base = FusedPredictor(trees)
    want = base(X)
    sc = _sc(800, 8, 32)
    # a 1-byte budget floors the cap at one tree per block (the degraded
    # g=1 re-blocking, already pinned bit-exact in test_resilience)
    pin = planner.analytic_plan(sc)._replace(predict_block_vmem_bytes=1)
    with plan_state.pinned(pin):
        fp = FusedPredictor(trees)
        assert fp.ens.path_len.shape[1] == 1
        got = fp(X)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        # steady state: repeat dispatches grow no compiled programs
        from lightgbm_tpu.core.predict_fused import predict_compile_count
        before = predict_compile_count()
        got2 = fp(X)
        assert predict_compile_count() == before
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got2))


# ---- provenance stamping ------------------------------------------------


def test_stamp_reaches_summary_and_events():
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import report
    tele = obs.configure(out=None)
    try:
        plan_state.stamp(tele, "tree_build", "analytic", key="n4096_b32",
                         mode="leaf")
        plan_state.stamp(tele, "tree_build", "analytic", key="n4096_b32",
                         mode="leaf")  # deduped
        plan_state.stamp(tele, "predict_fused", "tuned", key="t8_g8")
        # the serving-warm stamp shape: bucket list as a comma-joined
        # SCALAR (a list field would fail the JSONL sink's validate_event
        # — caught live by the drift-swap fault scenario)
        plan_state.stamp(tele, "serving_warm", "analytic", key="m",
                         buckets="128,1024")
        events = [e for e in tele.events if e["kind"] == "plan"]
        assert len(events) == 3
        from lightgbm_tpu.obs.registry import validate_event
        for e in events:
            validate_event(e)
        summary = report.summarize(tele)
        blk = summary["plan"]
        assert blk["provenance"] == "tuned"  # tuned anywhere wins headline
        assert blk["sites"]["tree_build"]["provenance"] == "analytic"
        assert blk["sites"]["predict_fused"]["key"] == "t8_g8"
        assert blk["cache_fallbacks"] == 0
        assert "_tag" not in blk["sites"]["tree_build"]
        table = report.human_table(summary)
        assert "plan provenance" in table and "tuned" in table
    finally:
        obs.disable()


def test_train_run_stamps_plan_into_summary():
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import report
    b = _toy_booster(800, None, iters=2)
    tele = obs.configure(out=None)
    try:
        b.train()
        summary = report.summarize(tele)
        blk = summary.get("plan")
        assert blk is not None and blk["provenance"] == "analytic"
        assert blk["sites"]["tree_build"]["provenance"] == "analytic"
    finally:
        obs.disable()


def test_fallback_counter_reaches_telemetry_and_exporter(tmp_path):
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs.exporter import render_prometheus
    path = str(tmp_path / "bad.json")
    with open(path, "w") as fh:
        fh.write("garbage")
    tele = obs.configure(out=None)
    try:
        assert plan_cache.load_cache(path) is None
        assert tele.registry.snapshot()["counters"][
            "plan_cache_fallbacks"] == 1
        text = render_prometheus(tele.registry.snapshot())
        assert "lgbm_tpu_plan_cache_fallbacks_total 1" in text
        # the registry mirror must NOT duplicate the always-on metric
        assert text.count("lgbm_tpu_plan_cache_fallbacks_total") == 2  \
            # TYPE line + sample
    finally:
        obs.disable()


def test_died_run_recovery_rebuilds_plan_block(tmp_path):
    """tools/obs_report.py recovers the plan block from kind=plan /
    kind=plan_fallback breadcrumbs of a run that never summarized."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from obs_report import summary_from_events
    events = [
        {"v": 1, "ts": 1.0, "kind": "plan", "site": "tree_build",
         "provenance": "tuned", "key": "n4096_b32"},
        {"v": 1, "ts": 2.0, "kind": "plan_fallback", "path": "x",
         "reason": "unreadable"},
    ]
    summary = summary_from_events(events)
    blk = summary["plan"]
    assert blk["recovered"] and blk["provenance"] == "tuned"
    assert blk["sites"]["tree_build"]["provenance"] == "tuned"
    assert blk["cache_fallbacks"] == 1


# ---- autotuner ----------------------------------------------------------


def test_candidate_plans_are_valid_and_distinct():
    for n in (4096, 65_536, 1 << 20):
        sc = _sc(n, 8, 32)
        cands = autotune.candidate_plans(sc)
        assert cands[0].name == "analytic"
        assert len(cands) >= 3
        seen = set()
        for cand in cands:
            planner.validate_plan(cand.plan, n)
            sig = cand.plan[:-1]
            assert sig not in seen, "duplicate candidate %s" % cand.name
            seen.add(sig)
        names = {c.name for c in cands}
        if n > 2 * 16384:
            assert "wide-mid" in names
        if n > 16384:
            # below _MID_MAX the ladder has no separate mid bucket, so
            # "no-small" collapses onto "single-mid" and is deduped
            assert "no-small" in names


class _FakeDriver:
    """Scripted steady medians: ranking/merge logic without kernels."""

    def __init__(self, train_s, predict_s):
        self.train_s = train_s
        self.predict_s = predict_s

    def measure_train(self, cand):
        v = self.train_s.get(cand.name)
        return None if v is None else {"steady_p50_s": v, "compile_s": 0.1}

    def measure_predict(self, cand):
        v = self.predict_s.get(cand.name)
        return None if v is None else {"steady_p50_s": v, "compile_s": 0.1}


def test_tune_shape_merges_site_winners():
    sc = _sc(1 << 20, 8, 32)
    driver = _FakeDriver(
        train_s={"analytic": 1.0, "single-large": 0.5, "single-mid": 2.0,
                 "no-small": 3.0, "wide-mid": 4.0},
        predict_s={"analytic": 1.0, "predict-halfvmem": 2.0,
                   "predict-2xvmem": 0.25})
    res = autotune.tune_shape(sc, driver=driver)
    win = planner.plan_from_dict(res["winner"]["plan"])
    assert res["winner"]["name"] == "single-large+predict-2xvmem"
    assert win.bucket_plan == ((False, CHUNK, None),)
    assert win.level_ladder == ((False, CHUNK, None),)
    assert win.predict_block_vmem_bytes == 2 * (1 << 20)
    assert win.provenance == "tuned"
    assert res["margin"]["train"] == pytest.approx(2.0)
    assert res["margin"]["predict"] == pytest.approx(4.0)
    planner.validate_plan(win, sc.n_rows)


def test_tune_shape_keeps_analytic_when_it_wins():
    sc = _sc(1 << 20, 8, 32)
    driver = _FakeDriver(
        train_s={"analytic": 1.0, "single-large": 1.5, "single-mid": 2.0,
                 "no-small": 3.0, "wide-mid": 4.0},
        predict_s={"analytic": 0.2, "predict-halfvmem": 2.0,
                   "predict-2xvmem": 0.9})
    res = autotune.tune_shape(sc, driver=driver)
    assert res["winner"]["name"] == "analytic"
    win = planner.plan_from_dict(res["winner"]["plan"])
    assert win.bucket_plan == fused_bucket_plan(sc.n_rows)
    assert res["margin"]["train"] == pytest.approx(1.0)


def test_compile_accounting_prices_candidates_not_warm_loads():
    """The ranking substrate end-to-end: a miss-bearing first dispatch is
    priced against the steady median, so compiles never leak into the
    per-candidate steady_p50_s the tuner ranks on."""
    from lightgbm_tpu.obs.compile import CompileAccounting
    acct = CompileAccounting()
    acct.note(None, "train_tree", "analytic", 5.0, 1)   # compile-heavy
    for _ in range(4):
        acct.note(None, "train_tree", "analytic", 1.0, 0)
    snap = acct.snapshot()["keys"]["train_tree|analytic"]
    assert snap["steady_p50_s"] == pytest.approx(1.0)
    assert snap["compile_s"] == pytest.approx(4.0)
    assert snap["compiles"] == 1 and snap["warm_loads"] == 0


# ---- config / engagement ------------------------------------------------


def test_configure_from_config_missing_path_counts(tmp_path):
    cfg = type("C", (), {"plan_cache": str(tmp_path / "nope.json")})()
    assert plan_state.configure_from_config(cfg) is None
    assert plan_cache.fallback_count() == 1
    assert plan_state.configured_path() is None


def test_explicit_configure_survives_entrypoint_discovery(tmp_path):
    """lgb.train's default-discovery probe must not disengage a cache the
    user explicitly configured via lightgbm_tpu.plan.configure()."""
    sc = _sc(8192, 8, 32, device_kind=device_specs.current_device_kind())
    cache = plan_cache.PlanCache(device_kind=sc.device_kind)
    cache.put(sc, planner.analytic_plan(sc)._replace(
        bucket_plan=((False, CHUNK, None),),
        level_ladder=((False, CHUNK, None),)))
    path = cache.save(str(tmp_path / "plans.json"))
    assert plan_state.configure(path) is not None
    # what engine.train does when plan_cache is unset
    cfg = type("C", (), {"plan_cache": ""})()
    assert plan_state.configure_from_config(cfg) is not None
    assert plan_state.configured_path() == path
    assert plan_state.resolve(8192, 8, 32).provenance == "tuned"
    # an explicit param still wins over the earlier explicit configure
    plan_state.configure_from_config(
        type("C", (), {"plan_cache": str(tmp_path / "missing.json")})())
    assert plan_state.configured_path() is None


def test_predict_vmem_override_requires_cache_consensus(tmp_path):
    """Disagreeing tuned predict budgets across shape classes must NOT
    leak one class's budget into every model's tree_block — analytic is
    the honest fallback."""
    kind = device_specs.current_device_kind()
    cache = plan_cache.PlanCache(device_kind=kind)
    a = _sc(8192, 8, 32, device_kind=kind)
    b = _sc(1 << 20, 968, 64, device_kind=kind)
    cache.put(a, planner.analytic_plan(a)._replace(
        predict_block_vmem_bytes=2 << 20))
    cache.put(b, planner.analytic_plan(b)._replace(
        predict_block_vmem_bytes=1 << 19))
    path = cache.save(str(tmp_path / "plans.json"))
    assert plan_state.configure(path) is not None
    assert plan_state.predict_block_vmem() is None
    # consensus: one agreed value applies
    cache.put(b, planner.analytic_plan(b)._replace(
        predict_block_vmem_bytes=2 << 20))
    path = cache.save(str(tmp_path / "plans.json"))
    assert plan_state.configure(path) is not None
    assert plan_state.predict_block_vmem() == 2 << 20


def test_plan_ladder_resyncs_when_level_mode_degrades(tmp_path):
    """A tuned cache may carry different leaf vs level ladders; when
    tree_grow_mode=level degrades to leaf at build time the installed
    schedule must follow (a hand-pinned bucket_plan is never touched)."""
    kind = device_specs.current_device_kind()
    sc = planner.shape_class(4096, 8, 32, packed=True, device_kind=kind)
    tuned = planner.analytic_plan(sc)._replace(
        bucket_plan=((False, CHUNK, None),),
        level_ladder=((False, SMALL_CHUNK, None),))
    cache = plan_cache.PlanCache(device_kind=kind)
    cache.put(sc, tuned)
    path = cache.save(str(tmp_path / "plans.json"))
    assert plan_state.configure(path) is not None
    b = _toy_booster(4096, None, iters=2, tree_grow_mode="level")
    learner = b.learner
    # construction installed the LEVEL ladder (configured mode)
    assert learner.bucket_plan == ((False, SMALL_CHUNK, None),)
    # off-TPU the fused path is unavailable: level degrades to leaf and
    # the planner-installed schedule follows the effective mode
    assert learner.effective_grow_mode() == "leaf"
    assert learner.bucket_plan == ((False, CHUNK, None),)
    # a hand pin is sacred
    learner.bucket_plan = ((True, SMALL_CHUNK, 992), (False, CHUNK, None))
    learner.effective_grow_mode()
    assert learner.bucket_plan == ((True, SMALL_CHUNK, 992),
                                   (False, CHUNK, None))


def test_configure_from_config_engages_valid_cache(tmp_path):
    sc = _sc(8192, 8, 32, device_kind=device_specs.current_device_kind())
    cache = plan_cache.PlanCache(device_kind=sc.device_kind)
    cache.put(sc, planner.analytic_plan(sc)._replace(
        bucket_plan=((False, CHUNK, None),),
        level_ladder=((False, CHUNK, None),)))
    path = cache.save(str(tmp_path / "plans.json"))
    cfg = type("C", (), {"plan_cache": path})()
    assert plan_state.configure_from_config(cfg) is not None
    assert plan_state.configured_path() == path
    got = plan_state.resolve(8192, 8, 32)
    assert got.provenance == "tuned"
    assert plan_cache.fallback_count() == 0
