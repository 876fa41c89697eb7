"""Fused partition+histogram kernel vs the plain-XLA reference contract.

The kernel runs in Pallas interpret mode here (CPU CI); the same code path
compiles for the TPU.  partition_hist_xla documents the output contract:
stable partition of the window, smaller-child histogram, left count.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.core.partition import (CHUNK, fold_hist,
                                         partition_hist_pallas,
                                         partition_hist_xla)

W = 128
VOFF = 32            # pretend 32 bin columns, then grad/hess/order


def make_rows(n_pad, f, num_bins, seed=0, bpc=1, packed=False):
    rng = np.random.RandomState(seed)
    ncols = (f + 1) // 2 if packed else f * bpc
    rows = np.zeros((n_pad, W), dtype=np.uint8)
    if packed:
        codes = rng.randint(0, min(num_bins, 16),
                            size=(n_pad, f)).astype(np.uint8)
        if f % 2:
            codes = np.concatenate([codes, np.zeros((n_pad, 1), np.uint8)],
                                   axis=1)
        rows[:, :ncols] = codes[:, 0::2] | (codes[:, 1::2] << 4)
    elif bpc == 2:
        codes = rng.randint(0, num_bins, size=(n_pad, f)).astype(np.uint16)
        rows[:, 0:2 * f:2] = (codes & 255).astype(np.uint8)
        rows[:, 1:2 * f:2] = (codes >> 8).astype(np.uint8)
    else:
        rows[:, :f] = rng.randint(0, num_bins, size=(n_pad, f)).astype(np.uint8)
    grad = rng.normal(size=n_pad).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n_pad).astype(np.float32)
    rows[:, VOFF:VOFF + 4] = grad.view(np.uint8).reshape(n_pad, 4)
    rows[:, VOFF + 4:VOFF + 8] = hess.view(np.uint8).reshape(n_pad, 4)
    order = np.arange(n_pad, dtype=np.int32)
    rows[:, VOFF + 8:VOFF + 12] = order.view(np.uint8).reshape(n_pad, 4)
    return rows


def run_case(wb, wc, n_pad=3 * CHUNK, f=6, num_bins=32, thr=11, seed=0,
             mt=0, dbin=0, is_cat=0, bitset=None, hist_left=1,
             use_unfold=0, eoff=1, gcol=2, nb=None, bpc=1, packed=False):
    rows = make_rows(n_pad, f, num_bins, seed=seed, bpc=bpc, packed=packed)
    nb = num_bins if nb is None else nb
    scal = np.zeros(12 + num_bins // 32, dtype=np.int32)
    scal[:12] = [wb, wc, gcol, thr, 1, mt, nb, dbin, is_cat, hist_left,
                 use_unfold, eoff]
    if bitset is not None:
        scal[12:12 + len(bitset)] = np.asarray(bitset, np.uint32).view(np.int32)
    r_jax = jnp.asarray(rows)
    s_jax = jnp.asarray(scal)
    got_rows, got_h4, got_nl = partition_hist_pallas(
        r_jax, s_jax, num_features=f, num_bins=num_bins, voff=VOFF,
        bpc=bpc, packed=packed, interpret=True)
    got_hist = fold_hist(got_h4, f, num_bins)
    want_rows, want_hist, want_nl = partition_hist_xla(
        r_jax, s_jax, num_features=f, num_bins=num_bins, voff=VOFF,
        bpc=bpc, packed=packed)
    assert int(got_nl[0, 0]) == int(want_nl), \
        f"nl {int(got_nl[0, 0])} != {int(want_nl)}"
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))
    np.testing.assert_allclose(np.asarray(got_hist), np.asarray(want_hist),
                               rtol=1e-4, atol=1e-4)


def test_full_logical_window():
    # contract: >= one spare CHUNK past the window end (n_pad = 3*CHUNK)
    run_case(wb=0, wc=2 * CHUNK)


def test_unaligned_window():
    run_case(wb=1234, wc=2513, seed=1)


def test_tiny_window():
    run_case(wb=777, wc=5, seed=2)


def test_empty_window():
    run_case(wb=0, wc=0, seed=3)


def test_window_at_end():
    # window ends exactly at n_pad - CHUNK (the tightest the contract allows)
    run_case(wb=CHUNK - 17, wc=CHUNK + 17, seed=4)


def test_all_left():
    # threshold >= max bin -> everything routes left
    run_case(wb=400, wc=3000, thr=31, seed=5)


def test_all_right():
    run_case(wb=400, wc=3000, thr=-1, seed=6)


def test_hist_right_side():
    run_case(wb=100, wc=4000, hist_left=0, seed=7)


def test_missing_nan_default_left():
    run_case(wb=50, wc=2200, mt=1, seed=8)


def test_missing_zero_default_bin():
    run_case(wb=50, wc=2200, mt=2, dbin=3, seed=9)


def test_categorical_bitset():
    # bins {1, 5, 17, 30} go left
    bs = (1 << 1) | (1 << 5) | (1 << 17) | (1 << 30)
    run_case(wb=300, wc=3100, is_cat=1, bitset=[bs], seed=10)


def test_efb_unfold():
    run_case(wb=300, wc=3100, use_unfold=1, eoff=4, nb=9, seed=11)


def test_packed_nibble_rows():
    # 4-bit packed bins (two features per byte); kernel block stays 32 lanes
    run_case(wb=321, wc=3000, thr=7, nb=16, seed=13, packed=True)


def test_packed_odd_feature_column():
    run_case(wb=100, wc=2500, thr=7, nb=16, gcol=3, seed=14, packed=True)


def test_u16_bins_bpc2():
    # 2-byte bin codes (num_bins > 256 datasets)
    run_case(wb=55, wc=2800, num_bins=512, thr=300, seed=15, bpc=2)


def test_fused_kernel_classic_hist_fallback(monkeypatch):
    """The fused kernel's classic (non-factored) in-kernel histogram — the
    path wide-F x 256-bin datasets take past the 4 MiB accumulator gate —
    now a rolled fori_loop over lane tiles with dynamic extraction."""
    import lightgbm_tpu.core.partition as P
    monkeypatch.setattr(P, "_use_factored",
                        lambda f, b, quantized=False: False)
    # the jit cache key does not see the monkeypatch: force retraces both
    # entering (pick up the classic path) and leaving (restore factored)
    P.partition_hist_pallas.clear_cache()
    try:
        run_case(wb=321, wc=3000, seed=16)
        run_case(wb=100, wc=2500, thr=7, nb=16, seed=17, packed=True)
    finally:
        P.partition_hist_pallas.clear_cache()


def test_sequential_splits_stay_consistent():
    """Split the root, then split each child window; windows stay coherent."""
    n_pad, f, num_bins = 3 * CHUNK, 6, 32
    rows = make_rows(n_pad, f, num_bins, seed=12)
    n = 2 * CHUNK + 517           # logical rows; rest is padding slack
    scal = np.zeros(12 + num_bins // 32, dtype=np.int32)
    scal[:12] = [0, n, 2, 9, 1, 0, num_bins, 0, 0, 1, 0, 1]
    r = jnp.asarray(rows)
    r, _, nl = partition_hist_pallas(r, jnp.asarray(scal), num_features=f,
                                     num_bins=num_bins, voff=VOFF,
                                     interpret=True)
    nl = int(nl[0, 0])
    rx, _, nlx = partition_hist_xla(jnp.asarray(rows), jnp.asarray(scal),
                                    num_features=f, num_bins=num_bins,
                                    voff=VOFF)
    assert nl == int(nlx)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rx))
    # split the right child on another feature
    scal2 = scal.copy()
    scal2[:12] = [nl, n - nl, 4, 20, 1, 0, num_bins, 0, 0, 0, 0, 1]
    r2, _, nl2 = partition_hist_pallas(r, jnp.asarray(scal2), num_features=f,
                                       num_bins=num_bins, voff=VOFF,
                                       interpret=True)
    r2x, _, nl2x = partition_hist_xla(rx, jnp.asarray(scal2), num_features=f,
                                      num_bins=num_bins, voff=VOFF)
    assert int(nl2[0, 0]) == int(nl2x)
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(r2x))
