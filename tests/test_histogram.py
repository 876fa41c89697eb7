import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.core.histogram import (histogram_pallas,
                                         histogram_pallas_rows,
                                         histogram_xla, histogram_xla_masked,
                                         pack_nibbles, rows_split_xla,
                                         _factored_geometry,
                                         _factored_out_shape, _fold_factored,
                                         _group_block, _hilo_factors,
                                         _hist_channels, _use_factored)


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


def _quantize_values(rows, voff, seed):
    """Integer-valued grad / hess in place (|v| <= 255: exact in bf16), as
    ``core/quant.py`` leaves them in the store."""
    rng = np.random.RandomState(seed)
    n = rows.shape[0]
    for off, lo in ((voff, -255), (voff + 4, 0)):
        v = rng.randint(lo, 256, size=n).astype(np.float32)
        rows[:, off:off + 4] = v.view(np.uint8).reshape(n, 4)


_STEP_CASES = [(f, b, mode, 1, False, 0)
               for f in (9, 28, 67) for b in (64, 256)
               for mode in ("plain", "quantized", "exact")] + [
    (13, 32, "plain", 1, True, 0),        # nibble-packed: p = 8, a block a group
    (11, 512, "plain", 2, False, 0),      # two-byte codes: both bytes in one E
    (28, 256, "plain", 1, False, 6),      # a feature window: 11 of 28 from 6
]


@pytest.mark.parametrize("f,b,mode,bpc,packed,f_begin", _STEP_CASES)
def test_factored_step_matches_xla(f, b, mode, bpc, packed, f_begin):
    """The factored block step (one extraction dot a block of groups, the
    select-weighted hi operand, the lane-dense contraction and its fold)
    against the backend-agnostic reference, in interpret mode: G not a
    multiple of the block wherever a block holds several groups (the last
    block's skipped groups), a window that starts and ends inside a tile."""
    quantized, exact = mode == "quantized", mode == "exact"
    fc = f if not f_begin else 11
    k, blocks = _group_block(fc, b, quantized)
    _, G = _factored_geometry(fc, b, quantized)
    assert _use_factored(fc, b, quantized)
    assert k == 1 or G % k, "the case should leave the last block short"
    n = 2048
    rows, voff = make_rows_store(n, f, b, seed=b + f, bpc=bpc, packed=packed,
                                 W=128 if bpc == 1 else 256)
    if quantized:
        _quantize_values(rows, voff, seed=f)
    start, count = 700, 900              # tiles of 1024: both cut
    got = np.asarray(histogram_pallas_rows(
        jnp.asarray(rows), b, jnp.int32(start), jnp.int32(count),
        num_features=fc, voff=voff, bpc=bpc, packed=packed, row_tile=1024,
        interpret=True, exact=exact, quantized=quantized,
        f_begin=jnp.int32(f_begin) if f_begin else 0))
    bins, values = rows_split_xla(jnp.asarray(rows), f, voff, bpc, packed)
    want = np.asarray(histogram_xla_masked(
        bins, values, b, jnp.int32(start), jnp.int32(count))
    )[f_begin:f_begin + fc]
    assert got.shape == (fc, 2, b)
    if quantized:
        np.testing.assert_array_equal(got, want)      # integer sums: exact
    elif exact:
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-5)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("f,b,quantized", [
    (28, 256, False), (9, 256, False), (67, 64, False), (28, 256, True)])
def test_fold_factored_reads_the_feature_diagonal(f, b, quantized):
    """A hand-made accumulator of ``_factored_out_shape``: row (group,
    feature q', lo), lane (feature q, channel, hi).  The fold keeps q' = q,
    puts (hi, lo) back in bin order and adds the lo value channels to the
    hi ones; the cross blocks (q' != q) and the features past F are junk it
    must not read."""
    nhi, nlo = _hilo_factors(b)
    p, G = _factored_geometry(f, b, quantized)
    nch = _hist_channels(quantized)
    shape = _factored_out_shape(f, b, quantized)
    assert shape == (G * p * nlo, p * nch * nhi) and shape[1] % 128 == 0
    raw = np.full((G, p, nlo, p, nch, nhi), 1e9, np.float32)
    want = np.zeros((f, 2, b), np.float32)
    for feat in range(f):
        g, q = divmod(feat, p)
        for c in range(nch):
            for hi in range(nhi):
                for lo in range(nlo):
                    v = feat * 1000 + c * 100 + hi * nlo + lo + 0.5
                    raw[g, q, lo, q, c, hi] = v
                    want[feat, c % 2, hi * nlo + lo] += v
    got = np.asarray(_fold_factored(jnp.asarray(raw.reshape(shape)), f, b,
                                    quantized))
    np.testing.assert_array_equal(got, want)


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
