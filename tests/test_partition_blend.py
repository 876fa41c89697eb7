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

def _stream(ring, tile, cur, comp, start, n):
    """One stream of ``_append_placed`` as phase C hands it over since PR 40:
    the ring, the open tile's words, the word-row offset of its slot, the
    placed words, the mask's two scalars and the advance flag of ``start``,
    ``n`` (what phase A ships in the bank)."""
    return (ring, tile, cur * P._WPT, pltpu.bitcast(comp[...], jnp.int32),
            start - (start & 3), jnp.int32(-1) << (8 * (start & 3)),
            (start + n >= TS).astype(jnp.int32))


def _append_twice_kernel(scal_ref, ring_in, comp1, comp2, ring_out, open_out):
    """Two appends to one stream, as phase C makes them: [start, start + n)
    into tile 0 (wrapping into tile 1), then n2 more rows behind them; the
    open tile rides in registers from one append to the next."""
    ring_out[...] = ring_in[...]
    ring = P._WordRef(ring_out, True)
    base = P._word_base(W)
    start, n, n2 = scal_ref[0], scal_ref[1], scal_ref[2]
    tile = ring.load(0)                       # tile 0 is the open one
    tile, = P._append_placed([_stream(ring, tile, 0, comp1, start, n)], base)
    cur = jnp.where(start + n >= TS, 1, 0)
    start2 = (start + n) & (TS - 1)
    tile, = P._append_placed(
        [_stream(ring, tile, cur, comp2, start2, n2)], base)
    open_out[...] = pltpu.bitcast(tile, jnp.uint8)


@jax.jit
def _append_twice(scal, ring, comp1, comp2):
    """``ring``: [tiles, TS, W] u8, handed to the kernel as the [1, tiles *
    TS, W] ring the split kernel keeps a stream.  Returns the ring and the
    open tile after the two appends."""
    out, tile = pl.pallas_call(
        _append_twice_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, ring.shape[0] * TS, W), jnp.uint8),
                   jax.ShapeDtypeStruct((TS, W), jnp.uint8)],
        interpret=True)(scal, ring.reshape(1, -1, W), comp1, comp2)
    return out.reshape(ring.shape), tile


def _placed(rows, start):
    """``rows`` at the circular positions [start, start + n) mod TS of a
    tile, zeros elsewhere: what the one-hot placement dot leaves."""
    comp = np.zeros((TS, W), np.uint8)
    comp[(start + np.arange(len(rows))) % TS] = rows
    return comp


@pytest.mark.parametrize("n", [0, 1, 4, 127, 128])
@pytest.mark.parametrize("start", [0, 1, 3, 4, 31, 32, 127])
def test_append_is_a_row_range_blend(start, n):
    # start + n >= TS completes tile 0: the ring holds it whole and the
    # placed words become the open tile (start + n == TS leaves it empty,
    # and the second append fills it from row 0)
    rng = np.random.RandomState(1000 * start + n)
    old = rng.randint(1, 256, size=(3, TS, W)).astype(np.uint8)
    n2 = 5
    new1 = rng.randint(1, 256, size=(n, W)).astype(np.uint8)
    new2 = rng.randint(1, 256, size=(n2, W)).astype(np.uint8)
    start2 = (start + n) % TS
    got, tile = _append_twice(
        jnp.asarray([start, n, n2], jnp.int32), jnp.asarray(old),
        jnp.asarray(_placed(new1, start)), jnp.asarray(_placed(new2, start2)))
    got, tile = np.asarray(got), np.asarray(tile)

    # NumPy: the stream is what tile 0 held below start, then the new rows
    want = old.copy().reshape(3 * TS, W)
    want[start:start + n] = new1
    want[start + n:start + n + n2] = new2
    want = want.reshape(3, TS, W)
    fill = start + n + n2                     # the stream's fill point
    cur = 1 if start + n >= TS else 0         # the second append's tile
    last = cur + (1 if start2 + n2 >= TS else 0)   # the open tile after it
    for t in range(3):
        if t < last:
            # a complete tile: the ring's copy is what the flush reads
            np.testing.assert_array_equal(got[t], want[t],
                                          err_msg="tile %d" % t)
        elif t > cur:
            # a slot no append stored into may still be in flight: not a
            # byte moves
            np.testing.assert_array_equal(got[t], old[t], err_msg="tile %d" % t)
    # the open tile rides in registers; rows past the fill point are nobody's
    keep = fill - last * TS
    assert 0 <= keep < TS
    np.testing.assert_array_equal(tile[:keep], want[last, :keep])


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


def _hold_to_xla(rows, wb, wc, nl, chunk, interpret, hist_left, what):
    """One split of the window [wb, wb + wc) of ``rows`` (NumPy), ``nl`` of
    its rows left: EVERY byte of the store, the left count and the histogram
    against ``partition_hist_xla``."""
    rows = jnp.asarray(rows)
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
    assert bad.size == 0, "rows differ at %s (window [%d, %d), %s)" % (
        bad[:8], wb, wb + wc, what)
    np.testing.assert_allclose(
        np.asarray(P.fold_hist(got_h, F, NUM_BINS)), np.asarray(want_h),
        rtol=2e-3, atol=2e-3)


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
    _hold_to_xla(_store(n_pad, wb, wc, nl, seed=97 * ph + nr), wb, wc, nl,
                 chunk, interpret, hist_left, "nl %d" % nl)


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


# ---- the placement stage's fixed costs (PR 40) ---------------------------
# Phase C's scalars come ready-made from phase A, a stream's finished tiles
# leave in aligned blocks of ``_flush_run`` ring slots and what a window's
# end leaves short of a block goes a tile at a time: windows built a chunk at
# a time, so that a chunk finishes a chosen number of one stream's tiles.

WINDOW_STORE = 16 * P.CHUNK


def check_window(chunk, wb, lefts, interpret, hist_left=1, seed=0):
    """A window that starts at ``wb`` and whose kernel chunk c (``chunk``
    rows from the 32-row-aligned start) sends ``lefts[c]`` of its rows left,
    scattered, the rest right: every byte of the store, the left count and
    the histogram against ``partition_hist_xla``."""
    wb_al = wb - wb % P._ALIGN
    wc = len(lefts) * chunk - (wb - wb_al)
    n_pad = WINDOW_STORE                      # one shape: one compile a chunk size
    assert wb + wc <= n_pad - P.CHUNK
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, 256, size=(n_pad, W)).astype(np.uint8)
    rows[:, :F] = rng.randint(0, NUM_BINS, size=(n_pad, F))
    left = np.zeros(n_pad, bool)
    for c, k in enumerate(lefts):
        lo = max(wb, wb_al + c * chunk)
        hi = wb_al + (c + 1) * chunk
        left[lo + rng.permutation(hi - lo)[:min(k, hi - lo)]] = True
    rows[:, 2] = np.where(left, rng.randint(0, THR + 1, size=n_pad),
                          rng.randint(THR + 1, NUM_BINS, size=n_pad))
    vals = rng.normal(size=(n_pad, 2)).astype(np.float32)
    rows[:, VOFF:VOFF + 8] = vals.view(np.uint8).reshape(n_pad, 8)
    _hold_to_xla(rows, wb, wc, int(left[wb:wb + wc].sum()), chunk, interpret,
                 hist_left, "lefts %s" % lefts)


def tile_patterns(chunk):
    """Left rows a chunk, in tiles of one stream: none, one, a power of two,
    all but a few rows and all of them (``chunk // TS`` - 1 and ``chunk //
    TS`` tiles: the other stream gets the few), long enough that both rings
    wrap, that blocks fill across chunk edges and that the drain's tail
    takes every length under ``_flush_run``."""
    n = chunk // TS
    return {
        "0-1-pow2-all": [0, TS, TS * max(n // 4, 2), chunk - 5, chunk,
                         chunk, 3 * TS + 7, chunk, 0, chunk // 2],
        "all-left-wraps": [chunk] * (P._ring_depth(chunk) // n + 2) + [TS + 1],
        "all-right-wraps": [0] * (P._ring_depth(chunk) // n + 2) + [5],
        "tiles-1": [chunk - TS] * 3 + [2 * TS - 1],
    }


# chip_smoke.py's kernel phase runs the same cases compiled
WINDOW_CASES = [(chunk, name, wb)
                for chunk in CHUNKS
                for name in tile_patterns(chunk)
                for wb in ((P.CHUNK + 13,) if name != "tiles-1"
                           else (P.CHUNK, P.CHUNK + 31))]


@pytest.mark.parametrize("chunk,name,wb", WINDOW_CASES)
def test_chunks_that_finish_chosen_tiles(chunk, name, wb):
    check_window(chunk, wb, tile_patterns(chunk)[name], interpret=True,
                 hist_left=0 if "right" in name else 1, seed=len(name) + wb)


def lopsided(chunk, share_left, chunks=5, seed=3):
    """``share_left`` of every chunk's rows go left, to the row."""
    rng = np.random.RandomState(seed)
    return [int(round(chunk * share_left)) + int(rng.randint(-3, 4))
            for _ in range(chunks)]


@pytest.mark.parametrize("share_left", [0.03, 0.97])
@pytest.mark.parametrize("chunk", CHUNKS)
def test_lopsided_window_with_an_unaligned_head(chunk, share_left):
    # 3% right is expo_onehot_train's window (PERF.md section 5); the
    # smaller child is the histogrammed one
    check_window(chunk, 2 * P.CHUNK + 19, lopsided(chunk, share_left),
                 interpret=True, hist_left=1 if share_left < 0.5 else 0,
                 seed=7)


def _phase_c_scalars_before_pr40(tot, incl, headL, fillL, fillR, nsub,
                                 nb_ring):
    """What the parent's phase C derived, subtile by subtile, from the
    totals bank and its own scalar fills (NumPy): per side the subtile's
    first tile row, its wrap and advance flags and its open tile's ring
    slot, and the two slots after the chunk."""
    curL = ((headL + fillL) // TS) % nb_ring
    curR = (fillR // TS) % nb_ring
    out = {k: np.zeros(2 * nsub, np.int64)
           for k in ("start", "wrap", "adv", "cur")}
    for s in range(nsub):
        nls, nrs = tot[s], tot[nsub + s]
        baseL = fillL + incl[s] - nls
        baseR = fillR + incl[nsub + s] - nrs
        startL = (headL + baseL) & (TS - 1)
        startR = baseR & (TS - 1)
        for j, start, n, cur in ((s, startL, nls, curL),
                                 (nsub + s, startR, nrs, curR)):
            out["start"][j], out["cur"][j] = start, cur
            out["wrap"][j] = start + n > TS
            out["adv"][j] = start + n >= TS
        curL = (curL + 1) % nb_ring if startL + nls >= TS else curL
        curR = (curR + 1) % nb_ring if startR + nrs >= TS else curR
    return out, curL, curR


@pytest.mark.parametrize("share_left", [0.0, 0.03, 0.5, 0.97, 1.0])
@pytest.mark.parametrize("headL", [0, 13, 31])
@pytest.mark.parametrize("chunk", CHUNKS)
def test_the_bank_holds_what_phase_c_derived(chunk, headL, share_left):
    """Random routings, every subtile of enough chunks that both rings
    wrap: the rows phase A ships equal the scalars phase C computed before,
    with the fills and slots carried as the kernel carries them."""
    nsub, nb_ring = chunk // TS, P._ring_depth(chunk)
    rng = np.random.RandomState(chunk + 100 * headL + int(100 * share_left))
    cumL = cumR = 0
    slotL = slotR = jnp.zeros((1, 1), jnp.int32)
    for c in range(2 * nb_ring // nsub + 3):
        gl = (rng.uniform(size=(nsub, TS)) < share_left).astype(np.int32)
        inw = np.ones((nsub, TS), np.int32)
        if c == 0:
            inw.reshape(-1)[:headL] = 0       # the head is not the window's
        S_L, S_R = jnp.asarray(gl * inw), jnp.asarray((1 - gl) * inw)
        totals = P._subtile_totals_lanes(S_L, S_R, nsub=nsub)
        bank, slotL2, slotR2 = P._subtile_scalars_lanes(
            totals, jnp.int32(headL), jnp.full((1, 1), cumL, jnp.int32),
            jnp.full((1, 1), cumR, jnp.int32), slotL, slotR, nsub=nsub,
            nb_ring=nb_ring)
        bank = np.asarray(bank)
        tot, incl = np.asarray(totals)
        want, curL, curR = _phase_c_scalars_before_pr40(
            tot, incl, headL, cumL, cumR, nsub, nb_ring)
        np.testing.assert_array_equal(bank[P._BK_TOT], tot)
        np.testing.assert_array_equal(bank[P._BK_INCL], incl)
        start = want["start"]
        np.testing.assert_array_equal(bank[P._BK_FIRST], start - (start & 3))
        np.testing.assert_array_equal(
            bank[P._BK_CUT].view(np.uint32),
            (0xFFFFFFFF << (8 * (start & 3))) & 0xFFFFFFFF)
        np.testing.assert_array_equal(bank[P._BK_ADV], want["adv"])
        np.testing.assert_array_equal(bank[P._BK_CUR], want["cur"] * P._WPT)
        # a wrap is an advance that leaves rows in the next tile: the rows a
        # subtile leaves there are what the placed words hold below its start
        np.testing.assert_array_equal(
            want["wrap"], want["adv"] * ((start + tot) % TS > 0))
        assert (int(slotL2[0, 0]), int(slotR2[0, 0])) == (curL, curR)
        slotL, slotR = slotL2, slotR2
        cumL += int(incl[nsub - 1])
        cumR += int(incl[2 * nsub - 1])
