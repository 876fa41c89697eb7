"""Round 22: quantized-gradient training — integer histogram operands.

The contract is pinned from both ends, mirroring round 20's precision
tiers: ``hist_precision=exact`` (the default) traces a program with NO
quantization ops in it (the stochastic-rounding hash constants may not
appear in the jaxpr), while the lossy path is deterministic (stateless
(seed, iteration, global row) hash — not noisy), measurably distinct
from exact, within the declared ``quant_*`` budgets, bit-exact across
checkpoint resume (the rounding stream is iteration-clocked, no RNG
state rides the checkpoint), bit-exact between the XLA segment-sum
fallback and the fused Pallas kernels (integer sums ≤ 2^24 are exact in
f32 — parity is equality, not tolerance), and on the parallel learners
the histogram collective narrows to bf16 (pinned on the lowered HLO)
while preserving serial model quality.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu import obs
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.compact import _auc
from lightgbm_tpu.core.histogram import (_factored_geometry,
                                         _factored_out_shape,
                                         _hist_channels)
from lightgbm_tpu.core.quant import (GRAD_LEVELS, HESS_LEVELS, _QUANT_TAG,
                                     quant_uniforms, quantize_gradients)
from lightgbm_tpu.core.tree_learner import SerialTreeLearner
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.objective import create_objective

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.disable()
    yield
    obs.disable()


def _make_data(n=800, features=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, features))
    logit = X[:, 0] * 1.5 - 0.8 * X[:, 1] + np.sin(X[:, 2] * 2.0)
    y = (logit + rng.logistic(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


def _budgets():
    with open(os.path.join(REPO, "PERF_BUDGETS.json")) as fh:
        return json.load(fh)["budgets"]


def _train(hist_precision, n=800, iters=8, seed=7, pallas=False,
           features=8, **extra):
    X, y = _make_data(n=n, features=features)
    cfg = Config(dict(objective="binary", num_leaves=15,
                      min_data_in_leaf=5, learning_rate=0.1,
                      num_iterations=iters, seed=seed, verbosity=-1,
                      hist_precision=hist_precision, **extra))
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
    b = GBDT(cfg, ds, create_objective("binary", cfg))
    if pallas:
        b.learner.use_pallas = True
        b.learner.pallas_interpret = True
    b.train_chunk(iters)
    return np.asarray(b.train_score, np.float32).ravel(), b, X


# ---- exact path unchanged (the non-negotiable) ----

def test_exact_path_jaxpr_has_no_quant_ops():
    """hist_precision=exact traces the SAME program as before the knob
    existed: the stochastic-rounding hash constants (the quant domain tag
    in particular) may not appear anywhere in the jaxpr, and an explicit
    exact config traces byte-identically to the default config."""
    X, y = _make_data(n=512)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
    grad = jnp.asarray(-(y - y.mean()), jnp.float32)
    hess = jnp.ones((512,), jnp.float32)

    def trace(cfg):
        learner = SerialTreeLearner(ds, cfg)
        return str(jax.make_jaxpr(
            lambda g, h, it: learner.train(g, h, 512, iteration=it))(
                grad, hess, jnp.int32(0)))

    jx_default = trace(Config(num_leaves=15, min_data_in_leaf=5))
    jx_exact = trace(Config(num_leaves=15, min_data_in_leaf=5,
                            hist_precision="exact"))
    jx_quant = trace(Config(num_leaves=15, min_data_in_leaf=5,
                            hist_precision="quantized"))
    tag = str(_QUANT_TAG)
    assert tag not in jx_default and tag not in jx_exact
    assert jx_exact == jx_default, \
        "explicit exact must trace identically to the default config"
    # the knob does something: the quantized trace carries the hash
    assert tag in jx_quant
    assert jx_quant != jx_exact


def test_operand_and_accumulator_geometry():
    """The mechanism: 2 value rows instead of 4, and the factored
    accumulator packs 2x the features per group (total f32 bytes
    layout-invariant — the win is half the MXU group passes)."""
    assert _hist_channels(False) == 4 and _hist_channels(True) == 2
    assert _hist_channels(True) / _hist_channels(False) \
        <= _budgets()["quant_bytes_ratio_max"]
    for F, B in ((20, 256), (32, 64)):
        p_e, g_e = _factored_geometry(F, B, False)
        p_q, g_q = _factored_geometry(F, B, True)
        assert p_q == 2 * p_e
        assert g_q == -(-F // p_q) < g_e
        # with p_q | F there is no group padding, so the total f32
        # accumulator is exactly layout-invariant (the freed channel rows
        # pack 2x the features; the win is the halved group count)
        assert F % p_q == 0
        shp_e = _factored_out_shape(F, B, False)
        shp_q = _factored_out_shape(F, B, True)
        assert shp_e[0] * shp_e[1] == shp_q[0] * shp_q[1]
        # the weighted-hi operand's p * nch * nhi rows are the accumulator's
        # LANES, a whole 128 of them either way: what VMEM holds is what
        # the element count says (PR 37)
        assert shp_e[1] == shp_q[1] == 128


# ---- the quantizer itself ----

def test_quantizer_integer_exact_zero_pinned_and_stateless():
    rows = jnp.arange(4096, dtype=jnp.int32)
    g = jnp.linspace(-3.0, 3.0, 4096).at[7].set(0.0)
    h = jnp.linspace(0.0, 1.0, 4096).at[7].set(0.0)
    qg, qh, qs = quantize_gradients(g, h, rows, it=3, seed=11)
    qg, qh = np.asarray(qg), np.asarray(qh)
    # exact integers on the declared grids
    np.testing.assert_array_equal(qg, np.round(qg))
    np.testing.assert_array_equal(qh, np.round(qh))
    assert np.abs(qg).max() <= GRAD_LEVELS and qh.min() >= 0
    assert qh.max() <= HESS_LEVELS
    # exact zeros stay exact zero (bagged-out rows get no phantom level)
    assert qg[7] == 0.0 and qh[7] == 0.0
    # stateless: same (seed, it, rows) -> same stream; new it -> new stream
    qg2, _, _ = quantize_gradients(g, h, rows, it=3, seed=11)
    np.testing.assert_array_equal(qg, np.asarray(qg2))
    qg3, _, _ = quantize_gradients(g, h, rows, it=4, seed=11)
    assert not np.array_equal(qg, np.asarray(qg3))
    # uniforms strictly inside [0, 1): a 1.0 would phantom-round zeros
    u = np.asarray(quant_uniforms(rows, 11, 3))
    assert u.min() >= 0.0 and u.max() < 1.0


def test_quantized_rounding_is_unbiased_in_expectation():
    """Stochastic rounding's point: E[q * s] = value.  Averaged over many
    rows of a CONSTANT gradient, the dequantized mean lands within a few
    standard errors of the true value — nearest-rounding would miss by
    the full quantization-step bias."""
    n = 1 << 16
    rows = jnp.arange(n, dtype=jnp.int32)
    val = 0.7321  # deliberately off the 127-level grid
    g = jnp.full((n,), val, jnp.float32)
    h = jnp.full((n,), val, jnp.float32)
    qg, _, qs = quantize_gradients(g, h, rows, it=0, seed=3)
    s_g = float(np.asarray(qs)[0])
    got = float(np.mean(np.asarray(qg))) * s_g
    step = s_g  # one integer level
    se = step / np.sqrt(12.0 * n)
    assert abs(got - val) < 6 * se, (got, val, se)


# ---- determinism, distinctness, budgets ----

def test_quantized_deterministic_distinct_and_budgeted():
    budgets = _budgets()
    s_exact, _, _ = _train("exact")
    s_quant, _, _ = _train("quantized")
    s_quant2, _, _ = _train("quantized")
    np.testing.assert_array_equal(s_quant, s_quant2)
    delta = float(np.max(np.abs(s_exact - s_quant)))
    assert 0.0 < delta <= budgets["quant_max_score_delta"]
    _, y = _make_data()
    assert _auc(s_exact, y) - _auc(s_quant, y) \
        <= budgets["quant_auc_delta_max"]


def test_quantized_grad_alias_and_validation():
    from lightgbm_tpu.utils.log import LightGBMError
    cfg = Config(dict(quantized_grad="quantized"))
    assert cfg.hist_precision == "quantized"
    with pytest.raises(LightGBMError):
        Config(dict(hist_precision="int8"))


# ---- resume: the rounding stream is iteration-clocked ----

def test_resume_bit_exact_quantized(tmp_path):
    """train(N) vs train(k) -> kill -> resume -> N, byte-identical model
    strings: no RNG state rides the checkpoint, so the resumed run must
    replay the identical stochastic-rounding stream (the same contract
    the bagging mask holds in test_checkpoint.py)."""
    X, y = _make_data(n=600)

    def build(snapshot_freq=-1):
        cfg = Config(dict(objective="binary", num_leaves=15,
                          min_data_in_leaf=5, num_iterations=12,
                          seed=7, verbosity=-1, snapshot_freq=snapshot_freq,
                          hist_precision="quantized",
                          bagging_fraction=0.8, bagging_freq=3))
        ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
        return create_boosting(cfg.boosting, cfg, ds,
                               create_objective("binary", cfg))

    out = str(tmp_path / "model.txt")
    full = build(snapshot_freq=5)
    full.train(snapshot_out=out)
    resumed = build(snapshot_freq=5)
    it = resumed.resume_from_checkpoint(out)
    assert 0 < it < 12
    resumed.train()
    assert full.save_model_to_string() == resumed.save_model_to_string()


# ---- backend parity: integer sums make it bit-exact ----

def test_backend_bit_exact_xla_vs_pallas_interpret():
    """Quantized histogram sums are small integers in f32, so the XLA
    segment-sum fallback and the fused Pallas kernels (interpret off-TPU)
    must agree np.array_equal at full-train granularity — any epsilon
    would mean a backend is not accumulating the same integers."""
    kw = dict(n=4096, iters=2, features=6)  # CHUNK-aligned: fused engages
    s_fb, _, _ = _train("quantized", **kw)
    s_pl, _, _ = _train("quantized", pallas=True, **kw)
    np.testing.assert_array_equal(s_fb, s_pl)


# ---- parallel: the collective narrows to bf16 ----

def _parallel_learner(hist_precision, d=8):
    from lightgbm_tpu.parallel import DataParallelTreeLearner, default_mesh
    rng = np.random.RandomState(0)
    n, F = 1024, 16
    X = rng.normal(size=(n, F))
    y = X[:, 0] + rng.normal(scale=0.1, size=n)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=15)
    cfg = Config(num_leaves=8, min_data_in_leaf=2,
                 hist_precision=hist_precision)
    learner = DataParallelTreeLearner(ds, cfg, mesh=default_mesh(d))
    grad = learner.pad_rows(jnp.asarray(-(y - y.mean()), jnp.float32))
    hess = learner.pad_rows(jnp.ones((n,), jnp.float32))
    fm = jnp.ones((learner.feat.num_bin.shape[0],), bool)
    txt = learner._build_fn.lower(
        learner.bins, grad, hess, jnp.int32(n), fm, learner.feat,
        jnp.int32(0)).as_text()
    return txt


def _collective_blobs(txt, op):
    lines = txt.splitlines()
    return [" ".join(lines[i:i + 8]) for i, ln in enumerate(lines)
            if op in ln]


def test_parallel_hist_collective_is_bf16():
    """On the lowered data-parallel program, every histogram
    reduce_scatter rides a bf16 payload under quantized (HALF the f32
    collective bytes) — and stays f32 under exact."""
    txt_q = _parallel_learner("quantized")
    txt_e = _parallel_learner("exact")
    rs_q = _collective_blobs(txt_q, "reduce_scatter")
    rs_e = _collective_blobs(txt_e, "reduce_scatter")
    assert rs_q and rs_e, "histogram reduce_scatter missing from HLO"
    assert all("bf16" in b for b in rs_q), \
        "quantized hist collective must ride bf16"
    assert all("bf16" not in b for b in rs_e), \
        "exact hist collective must stay f32"


def test_parallel_quantized_quality_matches_serial():
    """End-to-end data-parallel quantized training holds serial-quantized
    model quality: the bf16 psum rounds the integer sums (charged to the
    quant budgets), so the pin is training-loss parity, not bit equality
    — same form as test_parallel's psum reduction-order allowance, but
    wider: a bf16-rounded bin sum can flip a near-tie split, changing
    WHICH tree is grown (observed ~2% l2 wobble either direction at this
    scale), so the band pins quality-holds, not tree-identity."""
    scores = {}
    for lt in ("serial", "data"):
        rng = np.random.RandomState(7)
        X = rng.normal(size=(4000, 11))
        y = X[:, 0] * 1.5 + np.nan_to_num(X[:, 1]) ** 2 \
            + rng.normal(scale=0.1, size=4000)
        ds = BinnedDataset.from_matrix(X, label=y, max_bin=63)
        cfg = Config(objective="regression", tree_learner=lt,
                     num_leaves=7, num_iterations=5, learning_rate=0.2,
                     hist_precision="quantized", seed=7)
        b = GBDT(cfg, ds, create_objective("regression", cfg))
        for _ in range(5):
            b.train_one_iter()
        pred = np.asarray(b.train_score[0, :ds.num_data])
        scores[lt] = float(np.mean((np.asarray(ds.metadata.label)
                                    - pred) ** 2))
    assert scores["data"] == pytest.approx(scores["serial"], rel=5e-2)
