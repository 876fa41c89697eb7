"""The flush rings' packed word view (core/partition.py, PR 31).

Two layers.  The merge helper alone, in a tiny interpret-mode kernel, against
a NumPy row-range blend: what a subtile's append may touch and what it must
leave.  Then the pipelined kernels at chosen right-block phases and sizes
against ``partition_hist_xla``, every byte of the store compared, so the
copy-back's constant masks, its last tile and the read-modify-write finals
are held to the neighbour leaves' rows.  ``check_right_block`` takes
``interpret``: ``chip_smoke.py``'s kernel phase runs the same cases compiled,
where the word view is a bitcast of the ref that interpret mode cannot run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.core import partition as P

W = 128
TS = P.TS
F = 6
VOFF = 32
NUM_BINS = 32
THR = 11


# ---- the helper ----------------------------------------------------------

def _append_twice_kernel(scal_ref, ring_in, comp1, comp2, ring_out):
    """Two appends to one stream, as phase C makes them: [start, start + n)
    into tile 0 (wrapping into tile 1), then n2 more rows behind them."""
    ring_out[...] = ring_in[...]
    ring = P._WordRef(ring_out, True)
    base = P._word_base(W)
    start, n, n2 = scal_ref[0], scal_ref[1], scal_ref[2]
    P._append_placed(
        ring, [(0, 1, pltpu.bitcast(comp1[...], jnp.int32), start, n)], base)
    cur = jnp.where(start + n >= TS, 1, 0)
    start2 = (start + n) & (TS - 1)
    P._append_placed(
        ring, [(cur, cur + 1, pltpu.bitcast(comp2[...], jnp.int32), start2,
                n2)], base)


@jax.jit
def _append_twice(scal, ring, comp1, comp2):
    return pl.pallas_call(
        _append_twice_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(ring.shape, jnp.uint8),
        interpret=True)(scal, ring, comp1, comp2)


def _placed(rows, start):
    """``rows`` at the circular positions [start, start + n) mod TS of a
    tile, zeros elsewhere: what the one-hot placement dot leaves."""
    comp = np.zeros((TS, W), np.uint8)
    comp[(start + np.arange(len(rows))) % TS] = rows
    return comp


@pytest.mark.parametrize("n", [0, 1, 4, 127, 128])
@pytest.mark.parametrize("start", [0, 1, 3, 4, 31, 32, 127])
def test_append_is_a_row_range_blend(start, n):
    # start + n > TS wraps; start + n == TS leaves the second append to open
    # tile 1 with start == 0; a wrap opens it with a plain store
    rng = np.random.RandomState(1000 * start + n)
    old = rng.randint(1, 256, size=(3, TS, W)).astype(np.uint8)
    n2 = 5
    new1 = rng.randint(1, 256, size=(n, W)).astype(np.uint8)
    new2 = rng.randint(1, 256, size=(n2, W)).astype(np.uint8)
    start2 = (start + n) % TS
    got = np.asarray(_append_twice(
        jnp.asarray([start, n, n2], jnp.int32), jnp.asarray(old),
        jnp.asarray(_placed(new1, start)), jnp.asarray(_placed(new2, start2))))

    # NumPy: the stream is what tile 0 held below start, then the new rows
    want = old.copy().reshape(3 * TS, W)
    want[start:start + n] = new1
    want[start + n:start + n + n2] = new2
    want = want.reshape(3, TS, W)
    fill = start + n + n2                     # the stream's fill point
    cur = 1 if start + n >= TS else 0         # the second append's tile
    opened = {0, cur} | ({1} if start + n > TS else set()) \
        | ({cur + 1} if start2 + n2 > TS else set())
    for t in range(3):
        if t not in opened:
            # a tile nothing opened may still be in flight: not a byte moves
            np.testing.assert_array_equal(got[t], old[t], err_msg="tile %d" % t)
        else:
            # rows past the fill point are nobody's: not compared
            keep = min(max(fill - t * TS, 0), TS)
            np.testing.assert_array_equal(got[t, :keep], want[t, :keep],
                                          err_msg="tile %d" % t)


@pytest.mark.parametrize("start", [0, 1, 3, 4, 5, 31, 32, 100, 127, 128])
def test_rows_from_is_a_byte_mask_of_rows(start):
    m = np.asarray(P._rows_from(P._word_base(W), jnp.int32(start)))
    rows = m.view(np.uint8).reshape(P._WPT, W, 4).transpose(0, 2, 1)
    rows = rows.reshape(TS, W)                # byte k of word i: row 4 i + k
    assert (rows[:start] == 0).all() and (rows[start:] == 255).all()


# ---- the kernels ---------------------------------------------------------

def _store(n_pad, wb, wc, nl, seed):
    """A row store whose window [wb, wb + wc) sends exactly ``nl`` rows
    left (bin of column 2 <= THR), scattered; every byte random."""
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, 256, size=(n_pad, W)).astype(np.uint8)
    rows[:, :F] = rng.randint(0, NUM_BINS, size=(n_pad, F))
    left = np.zeros(wc, bool)
    left[rng.permutation(wc)[:nl]] = True
    rows[wb:wb + wc, 2] = np.where(
        left, rng.randint(0, THR + 1, size=wc),
        rng.randint(THR + 1, NUM_BINS, size=wc))
    vals = rng.normal(size=(n_pad, 2)).astype(np.float32)
    rows[:, VOFF:VOFF + 8] = vals.view(np.uint8).reshape(n_pad, 8)
    return rows


def check_right_block(chunk, ph, nr, interpret, hist_left=0):
    """One window whose right block is ``nr`` rows copied back at 32-row
    phase ``ph``, ending at the store's last legal row with that phase (the
    phase is the end's less ``nr``: at the limit itself for ``nr`` = 128 at
    phase 0, 127 and 4095 at 1, 1, 129 and 4097 at 31): rows, histogram and
    left count against ``partition_hist_xla``.  The store grows with ``nr``
    and keeps a chunk of rows after the window: EVERY row of it is compared,
    which is what catches a tile of the scratch's garbage tail flushed past
    the window from a partial last chunk read."""
    n_pad = (4 + nr // (2 * P.CHUNK)) * P.CHUNK
    limit = n_pad - P.CHUNK                   # a window may end here, no later
    end = limit - (limit - nr - ph) % P._ALIGN
    nl = 290
    wc = nl + nr
    wb = end - wc
    assert (wb + nl) % P._ALIGN == ph and limit - P._ALIGN < end <= limit
    assert wb >= TS                           # a neighbour's rows before it
    rows = jnp.asarray(_store(n_pad, wb, wc, nl, seed=97 * ph + nr))
    scal = np.zeros(12 + NUM_BINS // 32, np.int32)
    scal[:12] = [wb, wc, 2, THR, 1, 0, NUM_BINS, 0, 0, hist_left, 0, 1]
    scal = jnp.asarray(scal)
    kw = dict(num_features=F, num_bins=NUM_BINS, voff=VOFF)
    got_rows, got_h, got_nl = P.partition_hist_pallas(
        rows, scal, chunk=chunk, interpret=interpret, **kw)
    want_rows, want_h, want_nl = P.partition_hist_xla(rows, scal, **kw)
    assert int(got_nl[0, 0]) == int(want_nl) == nl
    got_rows, want_rows = np.asarray(got_rows), np.asarray(want_rows)
    bad = np.flatnonzero((got_rows != want_rows).any(axis=1))
    assert bad.size == 0, "rows differ at %s (window [%d, %d), nl %d)" % (
        bad[:8], wb, wb + wc, nl)
    np.testing.assert_allclose(
        np.asarray(P.fold_hist(got_h, F, NUM_BINS)), np.asarray(want_h),
        rtol=2e-3, atol=2e-3)


# chip_smoke.py's kernel phase runs the same cases compiled.  The copy-back
# reads the scratch a chunk at a time (PR 33): for each chunk size one under,
# at and one over one and two chunk reads, and a partial third
RIGHT_ROWS = [1, 127, 128, 129, 1023, 1024, 1025, 2047, 2049, 3073,
              4095, 4096, 4097, 8191, 8192, 8193, 12289]
PHASES = [0, 1, 31]
CHUNKS = [P.CHUNK, P.SMALL_CHUNK]


@pytest.mark.parametrize("nr", RIGHT_ROWS)
@pytest.mark.parametrize("ph", PHASES)
@pytest.mark.parametrize("chunk", CHUNKS)
def test_right_block_phases(chunk, ph, nr):
    check_right_block(chunk, ph, nr, interpret=True)
