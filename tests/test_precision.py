"""Round 20: error-budgeted mixed-precision serving + ensemble compaction.

The bf16 tier's contract is pinned from both ends: the exact path stays
BYTE-identical (every dtype cast in ``scan_blocks`` is a no-op for f32 —
the jaxpr may not change), while the lossy tier keeps leaf *routing*
bit-exact (integer/threshold decide + a ±1 path-sign dot that bf16
represents exactly) and only the weighted leaf sum carries rounding, so
the measured score delta stays under the declared ``bf16_max_score_delta``
budget.  Serving-side: exact and bf16 requests NEVER share a dispatch
(the batch key carries the tier), contrib has no lossy tier anywhere on
the ladder, a quantize-only compacted republish is a pure jit-cache hit,
and the quality plane folds both tiers' scores through the same training
fingerprint (no per-tier baselines, no per-tier false alarms).
"""
import json
import os
import sys

import numpy as np
import pytest

from lightgbm_tpu import obs
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.compact import (compact_booster, compact_trees,
                                       measure_compaction)
from lightgbm_tpu.core.predict_fused import FusedPredictor
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.obs import recompile
from lightgbm_tpu.objective import create_objective
from lightgbm_tpu.serving import Server
from lightgbm_tpu.utils.log import LightGBMError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _budgets():
    with open(os.path.join(REPO, "PERF_BUDGETS.json")) as fh:
        return json.load(fh)["budgets"]


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.disable()
    yield
    obs.disable()


def _train(seed=0, n=800, objective="regression", num_leaves=8, iters=10,
           features=6, **extra):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-2, 2, size=(n, features)).astype(np.float32)
    base = X[:, 0] * 2 + np.sin(X[:, 1] * 2)
    if objective == "binary":
        y = (base + rng.normal(scale=0.4, size=n) > 0).astype(np.float64)
    else:
        y = (base + 0.1 * rng.normal(size=n)).astype(np.float64)
    cfg = Config(objective=objective, num_leaves=num_leaves,
                 min_data_in_leaf=5, verbosity=-1, num_iterations=iters,
                 **extra)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                   min_data_in_leaf=cfg.min_data_in_leaf)
    b = GBDT(cfg, ds, create_objective(cfg.objective, cfg))
    for _ in range(iters):
        b.train_one_iter()
    return b, X


@pytest.fixture(scope="module")
def model():
    return _train(seed=0, iters=12, num_leaves=15)


# ---- exact path byte-identity (the non-negotiable) ----

def test_exact_path_byte_identical_and_bf16_free(model):
    """precision='exact' is the SAME program as before the tier existed:
    outputs byte-identical to the default predictor and the traced jaxpr
    carries no bfloat16 anywhere (every cast is a no-op for f32)."""
    import jax
    from lightgbm_tpu.core.predict_fused import predict_blocked
    b, X = model
    fp_default = FusedPredictor(b.models)
    fp_exact = FusedPredictor(b.models, precision="exact")
    got_d = np.asarray(fp_default(X[:200]))
    got_e = np.asarray(fp_exact(X[:200]))
    np.testing.assert_array_equal(got_d, got_e)
    assert got_e.dtype == got_d.dtype
    jx = str(jax.make_jaxpr(predict_blocked)(fp_exact.ens,
                                             np.asarray(X[:64])))
    assert "bf16" not in jx and "bfloat16" not in jx
    # the booster API default is likewise the exact tier, bit for bit
    np.testing.assert_array_equal(
        b.predict(X[:200], raw_score=True),
        b.predict(X[:200], raw_score=True, precision="exact"))


def test_bf16_deterministic_bounded_and_distinct(model):
    """The lossy tier is deterministic (lossy, not noisy), measurably
    different from exact (the knob does something), and within the
    declared budget — routing exactness keeps the error at leaf-rounding
    scale, not misroute scale."""
    b, X = model
    budget = float(_budgets()["bf16_max_score_delta"])
    exact = b.predict(X[:400], raw_score=True)
    bf16_a = b.predict(X[:400], raw_score=True, precision="bf16")
    bf16_b = b.predict(X[:400], raw_score=True, precision="bf16")
    np.testing.assert_array_equal(bf16_a, bf16_b)
    delta = float(np.max(np.abs(exact - bf16_a)))
    assert 0.0 < delta <= budget
    # leaf routing is tier-independent: bf16 path signs are ±1/0 exactly,
    # so the leaf-index surface (pure routing) cannot move
    np.testing.assert_array_equal(b.predict_leaf_index(X[:200], -1),
                                  b.predict_leaf_index(X[:200], -1))


def test_bf16_ensemble_halves_leaf_bytes(model):
    """The mechanism the tier buys: the [G,M,L] routing/leaf operands are
    2-byte, halving the bytes every row-tree streams per dispatch."""
    b, _ = model
    fp = FusedPredictor(b.models)
    fpb = FusedPredictor(b.models, precision="bf16")
    assert fpb.ens.path_sign.dtype == "bfloat16"
    assert fpb.ens.leaf_value.dtype == "bfloat16"
    tier_bytes = fpb.ens.path_sign.nbytes + fpb.ens.leaf_value.nbytes
    exact_bytes = fp.ens.path_sign.nbytes + fp.ens.leaf_value.nbytes
    assert tier_bytes * 2 == exact_bytes
    assert tier_bytes / exact_bytes <= _budgets()["bf16_bytes_ratio_max"]


# ---- validation + contrib rejection (no silent upgrades) ----

def test_precision_validation_and_contrib_rejection(model):
    from lightgbm_tpu.basic import Booster
    b, X = model
    with pytest.raises(ValueError):
        b.predict(X[:8], precision="fp8")
    with pytest.raises(ValueError):
        FusedPredictor(b.models, precision="f16")
    fpb = FusedPredictor(b.models, precision="bf16")
    with pytest.raises(ValueError):
        fpb.predict_contrib(X[:8], b.max_feature_idx + 2)
    bw = Booster(model_str=b.save_model_to_string())
    with pytest.raises(LightGBMError):
        bw.predict(X[:8], pred_contrib=True, precision="bf16")
    with Server(max_batch_wait_us=0) as srv:
        srv.register("m", b)
        with pytest.raises(LightGBMError):
            srv.submit("m", X[:8], pred_contrib=True, precision="bf16")
        with pytest.raises(LightGBMError):
            srv.submit("m", X[:8], precision="int8")


# ---- batch-key isolation: tiers never coalesce ----

def test_exact_and_bf16_never_share_a_dispatch(model):
    """Concurrent exact + bf16 requests for the same rows coalesce into
    per-tier batches only: every serve_batch event carries one tier, the
    per-tier request counters add up, and each response is bit-exact
    against ITS tier's fused program — a cross-tier ride would show up as
    the wrong scores."""
    b, X = model
    ref_e = np.asarray(FusedPredictor(b.models)(X[:64]))
    ref_b = np.asarray(FusedPredictor(b.models, precision="bf16")(X[:64]))
    assert not np.array_equal(ref_e, ref_b), \
        "premise: the tiers must disagree for isolation to be observable"
    tele = obs.configure(freq=1, entry="test_precision")
    with Server(max_batch_wait_us=30000) as srv:
        srv.register("m", b)
        srv.registry._resident["m"].warm((128,),
                                         precisions=("exact", "bf16"))
        futs = [srv.submit("m", X[:64], raw_score=True,
                           precision=("bf16" if i % 2 else "exact"))
                for i in range(6)]
        outs = [np.asarray(f.result(timeout=60)) for f in futs]
    for i, got in enumerate(outs):
        np.testing.assert_array_equal(got, ref_b if i % 2 else ref_e)
    ev = [e for e in tele.events if e["kind"] == "serve_batch"]
    assert {e["precision"] for e in ev} == {"exact", "bf16"}
    by_tier = {"exact": 0, "bf16": 0}
    for e in ev:
        by_tier[e["precision"]] += e["requests"]
    assert by_tier == {"exact": 3, "bf16": 3}
    assert tele.counter("serve_requests_precision_exact").value == 3
    assert tele.counter("serve_requests_precision_bf16").value == 3
    # and the 30ms coalescing window DID merge within each tier: fewer
    # batches than requests proves the keys only split across tiers
    assert len(ev) < 6


# ---- compaction ----

def test_compact_quantize_only_preserves_structure(model):
    """Codebook quantization alone (no merge/prune/cap) keeps every
    tree's structure: same leaf counts, same splits, leaf values on the
    codebook grid, declared bound respected on real rows."""
    b, X = model
    trees = b.models
    out, stats = compact_trees(trees, leaf_codes=255, merge_subtrees=False)
    assert [t.num_leaves for t in out] == [t.num_leaves for t in trees]
    for told, tnew in zip(trees, out):
        # _rebuild renumbers nodes pre-order, so compare the split
        # multiset, not positional arrays
        old_splits = sorted(zip(np.asarray(told.split_feature).tolist(),
                                np.asarray(told.threshold).tolist()))
        new_splits = sorted(zip(np.asarray(tnew.split_feature).tolist(),
                                np.asarray(tnew.threshold).tolist()))
        assert old_splits == new_splits
    fp_old = FusedPredictor(trees)
    fp_new = FusedPredictor(out)
    delta = float(np.max(np.abs(np.asarray(fp_old(X[:400]))
                                - np.asarray(fp_new(X[:400])))))
    assert delta <= stats["declared_max_score_delta"]
    assert stats["tree_reduction"] == 0.0


def test_compact_booster_reduces_and_stays_in_budget(model):
    """The full pipeline (prune + cap + quantize + merge) on the bench
    recipe: real node/byte reduction, measured delta within the declared
    bound, AUC preserved on the training rows, and the distilled
    generation round-trips through model text exactly."""
    b, X = _train(seed=3, objective="binary", iters=30, num_leaves=31,
                  n=2000, features=10)
    gen, stats = compact_booster(b, leaf_codes=255, prune_frac=0.05,
                                 leaf_cap=24)
    budgets = _budgets()
    assert stats["tree_reduction"] >= budgets["compact_tree_reduction_min"]
    assert stats["byte_reduction"] >= budgets["compact_byte_reduction_min"]
    assert stats["max_leaves_out"] <= 24 < stats["max_leaves_in"]
    y = (np.asarray(b.predict(X, raw_score=True)) > 0).astype(np.float64)
    meas = measure_compaction(b, gen, X[:1000], y=y[:1000])
    assert meas["max_score_delta"] <= stats["declared_max_score_delta"]
    assert meas["auc_delta"] <= budgets["compact_auc_delta_max"]
    # immutable-generation discipline: text round-trip is exact
    gen2 = GBDT(gen.config)
    gen2.load_model_from_string(gen.save_model_to_string())
    np.testing.assert_array_equal(gen.predict(X[:200], raw_score=True),
                                  gen2.predict(X[:200], raw_score=True))


def test_compacted_republish_is_pure_jit_cache_hit(model):
    """A quantize-only compacted generation stacks to the SAME shapes as
    its parent, so the registry hot-swap republish is a pure jit-cache
    hit: recompile gauge flat across swap + post-swap traffic, responses
    bit-exact vs the compacted program, fingerprints carried."""
    b, X = model
    gen, _ = compact_booster(b, leaf_codes=255, merge_subtrees=False)
    ref = np.asarray(FusedPredictor(gen.models)(X[:64]))
    with Server(max_batch_wait_us=0) as srv:
        srv.register("m", b)
        srv.predict("m", X[:64], raw_score=True)  # warm the rung
        base = recompile.total()
        srv.swap("m", gen, warm=False)
        got = srv.predict("m", X[:64], raw_score=True)
        np.testing.assert_array_equal(got, ref)
        assert recompile.total() - base == 0, \
            "same-shape compacted republish must not compile anything"
        stats = srv.stats()
        assert stats["dropped"] == 0 and stats["failed"] == 0
    assert getattr(gen, "_score_fingerprint_raw", None) \
        is getattr(b, "_score_fingerprint_raw", None)


# ---- quality plane: one fingerprint path for both tiers ----

def test_quality_plane_no_per_tier_false_alarm(model):
    """bf16 scores fold into score-PSI through the SAME training
    fingerprint as exact: one model entry (no per-tier baselines), and
    serving the same rows on both tiers stays at level ok — the bf16
    rounding is orders of magnitude below a decile width."""
    from lightgbm_tpu.obs.quality import capture_fingerprints
    b, X = _train(seed=5, iters=8)
    capture_fingerprints(b)
    assert getattr(b, "_score_fingerprint_raw", None) is not None
    tele = obs.configure(freq=1, entry="test_precision_quality")
    rng = np.random.RandomState(11)
    with Server(max_batch_wait_us=0) as srv:
        srv.register("m", b)
        srv.registry._resident["m"].warm((128, 1024),
                                         precisions=("exact", "bf16"))
        for i in range(12):
            rows = X[rng.randint(0, len(X), 256)]
            srv.submit("m", rows, raw_score=True,
                       precision=("bf16" if i % 2 else "exact")
                       ).result(timeout=60)
    mon = tele.quality
    assert mon is not None
    snap = mon.snapshot()
    assert set(snap["models"]) == {"m"}, \
        "tiers must not mint separate quality entries"
    info = snap["models"]["m"]
    assert info["score_psi"] is not None
    assert info["level"] == "ok", \
        "mixed-tier traffic on in-distribution rows must not alarm"


# ---- obs: tier split renders live and from raw events ----

def test_precision_tier_in_serving_block_and_died_run_recovery(model,
                                                               tmp_path):
    from lightgbm_tpu.obs.report import human_table, summarize
    b, X = model
    out = str(tmp_path / "prec.jsonl")
    tele = obs.configure(out=out, freq=1, entry="test_precision_obs")
    with Server(max_batch_wait_us=0) as srv:
        srv.register("m", b)
        srv.submit("m", X[:17], raw_score=True).result(timeout=60)
        srv.submit("m", X[:17], raw_score=True,
                   precision="bf16").result(timeout=60)
        srv.submit("m", X[:33], raw_score=True,
                   precision="bf16").result(timeout=60)
    summary = summarize(tele)
    prec = summary["serving"]["precisions"]
    assert prec["exact"] == {"requests": 1, "rows": 17}
    assert prec["bf16"] == {"requests": 2, "rows": 50}
    assert "precision tiers" in human_table(summary)
    tele.flush()
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    from lightgbm_tpu.obs.registry import read_events
    rebuilt = obs_report.summary_from_events(read_events(out))
    assert rebuilt["serving"]["precisions"]["bf16"] == \
        {"requests": 2, "rows": 50}
    assert rebuilt["serving"]["precisions"]["exact"] == \
        {"requests": 1, "rows": 17}
    obs.disable()
