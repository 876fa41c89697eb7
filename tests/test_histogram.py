import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.core.histogram import (histogram_pallas,
                                         histogram_pallas_rows,
                                         histogram_xla, histogram_xla_masked,
                                         pack_nibbles, rows_split_xla,
                                         _use_factored)


def make(n=1024, f=6, b=32, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    vals = np.stack([grad, hess], axis=0)  # [2, N] channel-major
    return bins, vals


def reference_hist(bins, vals, b):
    n, f = bins.shape
    out = np.zeros((f, 2, b), dtype=np.float64)
    for i in range(n):
        for j in range(f):
            out[j, :, bins[i, j]] += vals[:, i]
    return out


def test_histogram_xla_matches_numpy():
    bins, vals = make()
    b = 32
    got = np.asarray(histogram_xla(jnp.asarray(bins), jnp.asarray(vals), b))
    want = reference_hist(bins, vals, b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_histogram_pallas_interpret_matches_xla():
    bins, vals = make(n=2048, f=4, b=128)
    got = np.asarray(histogram_pallas(jnp.asarray(bins), jnp.asarray(vals), 128,
                                      row_tile=1024, interpret=True))
    want = np.asarray(histogram_xla(jnp.asarray(bins), jnp.asarray(vals), 128))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_histogram_pallas_exact_mode_tight_tolerance():
    """LIGHTGBM_TPU_EXACT_HIST path: f32 HIGHEST contraction should match a
    float64 reference to near machine precision (the bf16 hi/lo default is
    only ~2^-16 relative), so near-tie split parity can be debugged."""
    bins, vals = make(n=2048, f=4, b=128, seed=3)
    want = reference_hist(bins, vals, 128)
    got = np.asarray(histogram_pallas(jnp.asarray(bins), jnp.asarray(vals),
                                      128, row_tile=1024, interpret=True,
                                      exact=True))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-5)


def make_rows_store(n, f, b, seed=0, bpc=1, packed=False, W=128):
    rng = np.random.RandomState(seed)
    nbytes = (f + 1) // 2 if packed else f * bpc
    voff = -(-nbytes // 64) * 64          # past the bin columns, 4-aligned
    W = max(W, voff + 64)
    rows = np.zeros((n, W), dtype=np.uint8)
    if packed:
        codes = rng.randint(0, min(b, 16), size=(n, f)).astype(np.uint8)
        rows[:, :(f + 1) // 2] = pack_nibbles(codes)
    elif bpc == 2:
        codes = rng.randint(0, b, size=(n, f)).astype(np.uint16)
        rows[:, 0:2 * f:2] = (codes & 255).astype(np.uint8)
        rows[:, 1:2 * f:2] = (codes >> 8).astype(np.uint8)
    else:
        rows[:, :f] = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    rows[:, voff:voff + 4] = grad.view(np.uint8).reshape(n, 4)
    rows[:, voff + 4:voff + 8] = hess.view(np.uint8).reshape(n, 4)
    return rows, voff


@pytest.mark.parametrize("b,bpc,packed,f", [
    (32, 1, False, 6),        # factored 8x4
    (64, 1, False, 28),       # factored 8x8 (the bench shape)
    (256, 1, False, 11),      # factored 16x16 (max_bin=255)
    (512, 2, False, 5),       # factored 16x32, two-byte codes
    (32, 1, True, 7),         # factored over nibble-packed columns
    (64, 1, False, 125),      # wide F (multi-M-tile extraction dot)
])
def test_histogram_rows_interpret_matches_xla(b, bpc, packed, f):
    """histogram_pallas_rows (factored hi/lo MXU path) vs the
    backend-agnostic reference, over a sub-window."""
    n = 2048
    rows, voff = make_rows_store(n, f, b, seed=b + f, bpc=bpc, packed=packed,
                                 W=128 if bpc == 1 else 256)
    start, count = 700, 900
    got = np.asarray(histogram_pallas_rows(
        jnp.asarray(rows), b, jnp.int32(start), jnp.int32(count),
        num_features=f, voff=voff, bpc=bpc, packed=packed,
        row_tile=1024, interpret=True))
    bins, values = rows_split_xla(jnp.asarray(rows), f, voff, bpc, packed)
    want = np.asarray(histogram_xla_masked(
        bins, values, b, jnp.int32(start), jnp.int32(count)))
    assert _use_factored(f, b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_histogram_rows_classic_fallback(monkeypatch):
    """The classic packed-tile path stays correct (it serves accumulators
    past the factored path's 4 MiB VMEM bound, e.g. F > 1024 at B=64)."""
    import lightgbm_tpu.core.histogram as H
    monkeypatch.setattr(H, "_use_factored",
                        lambda f, b, quantized=False: False)
    for f, b, bpc, packed in ((9, 64, 1, False), (5, 512, 2, False),
                              (7, 32, 1, True)):
        n = 2048
        rows, voff = make_rows_store(n, f, b, seed=1, bpc=bpc, packed=packed,
                                     W=128 if bpc == 1 else 256)
        got = np.asarray(H.histogram_pallas_rows(
            jnp.asarray(rows), b, jnp.int32(100), jnp.int32(1500),
            num_features=f, voff=voff, bpc=bpc, packed=packed,
            row_tile=1024, interpret=True))
        bins, values = rows_split_xla(jnp.asarray(rows), f, voff, bpc,
                                      packed)
        want = np.asarray(histogram_xla_masked(
            bins, values, b, jnp.int32(100), jnp.int32(1500)))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"f={f} b={b} bpc={bpc}")


def test_histogram_rows_wide_f_factored_grid():
    """Grid-over-groups at Bosch width (F=968, 63-bin setting): the round-5
    layout unrolled 242 feature groups into the program and could not
    compile at this width; the grid layout keeps program size O(p) and this
    test pins its numerics (interpret mode)."""
    n, f, b = 1024, 968, 64
    rows, voff = make_rows_store(n, f, b, seed=5, W=1152)
    assert _use_factored(f, b)
    got = np.asarray(histogram_pallas_rows(
        jnp.asarray(rows), b, jnp.int32(100), jnp.int32(800),
        num_features=f, voff=voff, row_tile=1024, interpret=True))
    bins, values = rows_split_xla(jnp.asarray(rows), f, voff, 1, False)
    want = np.asarray(histogram_xla_masked(
        bins, values, b, jnp.int32(100), jnp.int32(800)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_histogram_rows_wide_f_classic_grid():
    """Wide F x 256 bins exceeds the factored accumulator's 4 MiB gate and
    takes the classic packed-tile path — now a grid over lane tiles with
    dynamic-index extraction (the unrolled version was the other
    multi-10-minute compile)."""
    n, f, b = 1024, 600, 256
    rows, voff = make_rows_store(n, f, b, seed=6, W=768)
    assert not _use_factored(f, b)
    got = np.asarray(histogram_pallas_rows(
        jnp.asarray(rows), b, jnp.int32(50), jnp.int32(900),
        num_features=f, voff=voff, row_tile=1024, interpret=True))
    bins, values = rows_split_xla(jnp.asarray(rows), f, voff, 1, False)
    want = np.asarray(histogram_xla_masked(
        bins, values, b, jnp.int32(50), jnp.int32(900)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_histogram_rows_feature_window_matches_slice():
    """Traced f_begin (feature-parallel shards histogram only their own F/d
    block) against the full build's slice — the dynamic-group extraction
    must honor the window base."""
    n, f, b = 2048, 24, 64
    rows, voff = make_rows_store(n, f, b, seed=8)
    full = np.asarray(histogram_pallas_rows(
        jnp.asarray(rows), b, jnp.int32(300), jnp.int32(1500),
        num_features=f, voff=voff, row_tile=1024, interpret=True))
    for f0, fc in ((0, 12), (12, 12), (8, 8)):
        win = np.asarray(histogram_pallas_rows(
            jnp.asarray(rows), b, jnp.int32(300), jnp.int32(1500),
            num_features=fc, voff=voff, row_tile=1024, interpret=True,
            f_begin=jnp.int32(f0)))
        np.testing.assert_allclose(win, full[f0:f0 + fc], rtol=1e-4,
                                   atol=1e-4)


def test_histogram_masked_rows_contribute_nothing():
    bins, vals = make()
    vals[:, 500:] = 0.0  # masked-out rows
    b = 32
    got = np.asarray(histogram_xla(jnp.asarray(bins), jnp.asarray(vals), b))
    want = reference_hist(bins[:500], vals[:, :500], b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
