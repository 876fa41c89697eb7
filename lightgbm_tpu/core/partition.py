"""Fused split pass: routing + stable partition + child histogram in ONE
Pallas kernel invocation per split.

Counterpart of the reference's per-split trio — ``DataPartition::Split``
(src/treelearner/data_partition.hpp:113), the ordered-index histogram
(src/io/dense_bin.hpp:48 ConstructHistogram over begin..end), and the GPU
learner's copy/kernel overlap (src/treelearner/gpu_tree_learner.cpp:952-1055)
— rebuilt for the TPU memory system:

- XLA's row scatter costs ~5-10 ns/row in per-row DMA descriptors, and the
  bucketed ``lax.switch`` the round-3 builder used forced buffer-unification
  copies of the whole row store every split (PERF.md).  Together those were
  ~45% of every boosting iteration.
- This kernel instead streams the parent leaf's window through VMEM in
  ``chunk``-row double-buffered tiles, routes each row (same binned-decision
  semantics as ``tree_learner._route_left``), and *places* rows with a one-hot
  permutation matmul on the MXU — left rows compact to the window's front
  (in-place, behind the read cursor), right rows stream to a scratch region
  and are copied back after the left block settles.  Every HBM touch is a
  contiguous DMA at a 32-row-aligned offset: the window, the smaller child's
  block and the scratch for the copy-back are READ a ``chunk`` at a time
  (512 KB at chunk=4096, 128 KB at 1024), the flush rings WRITE blocks of
  TS-row tiles (128 KB at chunk=4096, 32 KB at 1024) and the copy-back 16 KB
  tiles; zero per-row descriptors, no switch, cost proportional to the
  window.
- The smaller child's histogram (serial_tree_learner.cpp:347-356 subtraction
  trick feeds on it) accumulates in the same pass from the same VMEM tiles —
  the routing/scatter/histogram fusion PERF.md round 3 listed as the next
  lever.
- Round 6: the chunk loop is SOFTWARE-PIPELINED — phase C (scalar blends +
  flushes) trails behind phases A/B on banked totals and placement buffers,
  so the per-chunk totals VMEM->SMEM round-trip and the flush-semaphore
  waits overlap the next chunk's matmuls instead of stalling them (round 5
  measured phase A at ~10x its isolated compute replica, all scheduling);
  the per-feature-group histogram loops are ROLLED (dynamic group index) so
  program size stays O(1) in F and wide-F row stores compile.
- Round 7 (size-bucketed kernels): per-split cost now scales with the leaf
  WINDOW instead of paying one fixed CHUNK=4096 pipeline on every split —
  the documented remaining gap in the 1M-row head-to-head, where deep-tree
  leaf windows shrink below one chunk and per-split fixed cost dominates:
  (a) the totals round-trip is ONE VMEM->SMEM DMA per ``totk`` chunks (the
  double-banked layout generalized to group banks; phase C trails ``totk``
  chunks behind A/B instead of one), (b) ``chunk`` itself is a parameter —
  1024 for mid windows so they stop padding to the 4096-row floor, 4096 for
  the streaming regime — and (c) a SMALL-WINDOW kernel variant handles
  sub-chunk leaves (the majority of splits at num_leaves=255 on <=1M rows):
  single chunk, no input ring, no deferred phase C, no totals DMA at all —
  lane-resident totals drive an in-register permutation and one write-back
  DMA.  :func:`fused_bucket_plan` is the dispatch schedule the tree builder
  switches over (bucket choice by window size; the variant set is
  trace-static so the fused ``lax.scan`` boosting path compiles once).
  All variants share the same phase-A/histogram building blocks, so
  interpret-mode numerics are bit-exact across buckets (pinned by
  tests/test_partition_buckets.py).
- PR 40 (the placement stage's fixed costs: phases B and C and the flush
  loops were bound by ISSUE, not by memory, 5,281 scheduled bundles a
  4,096-row chunk): (a) phase A works out, for all ``2 * nsub`` subtiles at
  once, every scalar phase C needs of a subtile (the first tile row's two
  mask scalars, whether the subtile completes its tile, the word-row offset
  of its ring slot) and ships them in further rows of the same VMEM -> SMEM
  bank DMA (:func:`_subtile_scalars_lanes`): phase C reads four scalars a
  stream and derives none; (b) phase B's placement one-hot emits its rows
  byte-major, so the placed rows are made into 32-bit words with shifts and
  ors of whole vregs (:func:`_words_of`, the copy-back's way) and
  ``comp_buf`` HOLDS WORDS; (c) a stream's open tile rides in registers from
  subtile to subtile and from chunk to chunk: no load from the ring, the
  merged tile stored to its slot every time, the next open tile a select:
  no branch, so a chunk's subtiles are ONE basic block (the parent's 54
  bundles a subtile were one serial chain a stream, cut by two branches, the
  right stream's load waiting on the left stream's stores to the same
  buffer; now 18.5), and the two streams' rings are separate buffers; (d) a
  stream's finished tiles leave in ALIGNED blocks of :func:`_flush_run` ring
  slots, one descriptor and one semaphore a block, started when the block's
  last tile is complete and awaited as they were started; the drain sends
  what a window's end leaves short of a block a tile at a time
  (:func:`_ring_depth` has the invariants).

Mosaic constraints honored (probed on v5e): no u8 vector arithmetic (u8 used
only for DMA/select; math in i32/bf16/f32), no dynamic sublane rotate on u8
(placement is done by matmul, not roll), dynamic DMA offsets must be provably
32-row aligned (``pl.multiple_of`` + by-construction alignment).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import scopes as _scopes
from .histogram import (_accum_factored_all, _accum_onehot_all,
                        _colf_rows_dyn, _extract_values_T,
                        _factored_out_shape, _fold_factored, _hilo_split,
                        _padded_features, _use_factored, histogram_xla_masked,
                        rows_split_xla)

_LANE = 128
_ALIGN = 32          # u8 sublane tile: dynamic DMA offsets must be 32-row mult
CHUNK = 4096         # rows per streamed DMA tile of the LARGE bucket; also
                     # the row-store padding contract (spare rows past every
                     # window) every variant relies on
SMALL_CHUNK = 1024   # the small-window kernel's single-chunk capacity
T = 128              # rows per placement subtile (one P matmul)
TS = 128             # staging/flush tile (rows per contiguous write-back)
# Round-5 (2M-row window, v5e, full-kernel timings — phase knockouts are
# scheduling-noisy, whole-kernel numbers are stable): the lane-packed
# phase A/B + factored-MXU histogram rewrite took 9.29 -> 4.6 ns/row at
# CHUNK=2048; CHUNK=4096 amortizes the per-chunk totals round-trip to
# 4.12 (8192: 3.98, but doubles the minimum per-split window work that
# small deep-tree leaves pay — round 7 removes that floor with the bucket
# schedule below instead).  T=128 halves the placement one-hot vs 256
# now that dest math is lane-major.
NIN = 3              # input-chunk ring depth: two reads in flight so the
                     # read DMA wait overlaps the previous chunk's phase
                     # A/B matmuls AND the trailing phase C (round 6).  The
                     # ring serves three readers in turn: the window (phases
                     # A-C), the smaller child's block (``hist_pass``) and
                     # the scratch for the copy-back; each awaits every read
                     # it started before the next begins
_MID_MAX = 16384     # bucket bound: windows <= this use the 1024-row chunk

assert T == TS and T % _ALIGN == 0 and T == _LANE
assert NIN >= 2
assert CHUNK % SMALL_CHUNK == 0 and SMALL_CHUNK % T == 0
assert 2 * CHUNK // T <= _LANE       # a chunk's subtile totals fit one lane row


def _flush_run(chunk: int) -> int:
    """Tiles a flush descriptor (PR 40): a stream's finished tiles leave in
    ALIGNED blocks of this many ring slots, one DMA and one semaphore a
    block, started when the block's last tile is complete; a quarter of a
    chunk's subtiles, so a chunk starts about four descriptors where it
    started ``chunk // TS``.  What a window's end leaves short of a block
    goes a tile at a time in the drain."""
    run = max(1, chunk // TS // 4)
    assert run & (run - 1) == 0
    return run


def _ring_depth(chunk: int) -> int:
    """Flush-ring depth per stream, in TS-row tiles: a multiple of
    :func:`_flush_run` (a block never crosses the ring's wrap), and
    ``chunk // TS + 3 * run``.  Before a chunk blends, phase C awaits the
    blocks whose slots the chunk may store into: its subtiles open at most
    ``chunk // TS`` tiles past the open one (single-flush circular staging
    depends on nls <= TS per subtile: at most one tile completed per append)
    so tiles up to ``k1`` must be free, ``k1`` the stream's complete tiles
    after the chunk, and every tile <= ``k1 - depth`` gone.  Up to ``run -
    1`` complete tiles wait in the ring for their block to fill; with ``3 *
    run`` slots of slack every block a chunk has to await was started by
    the chunk before it or earlier, and the two newest started blocks are
    never among them (they stay in flight behind the chunk's blend).
    Retuning one constant without the others silently corrupts the
    partition."""
    return chunk // TS + 3 * _flush_run(chunk)


def _cb_depth(chunk: int) -> int:
    """The copy-back's ring (``stage``), in TS-row tiles: its own since PR
    40, a tile a descriptor and a semaphore, a chunk's tiles and four of
    slack as the flush rings had before."""
    return chunk // TS + 4


def _totk(chunk: int) -> int:
    """Chunks per totals VMEM->SMEM DMA window (round 7): one round-trip per
    ~8192 rows.  The group-banked layout stores ``totk`` chunks' subtile
    totals per bank; phase C trails ``totk`` chunks behind phase A/B, so the
    DMA has a full group of matmuls to land behind (2 for chunk=4096, 8 for
    chunk=1024)."""
    return max(1, 8192 // chunk)


def fused_bucket_plan(n: int) -> tuple:
    """Trace-static dispatch schedule for the fused split pass over an
    ``n``-row store: ``((small, chunk, max_wc), ..., (small, chunk, None))``,
    buckets ascending, last bucket unbounded.  The tree builder selects the
    bucket by the split window's row count (``jnp.searchsorted`` over the
    bounds), so sub-chunk leaves pay the small kernel's single-chunk cost and
    mid windows stop padding to the 4096-row floor; every variant is
    bit-exact vs the others in interpret mode (same accumulation order).

    The small bucket's bound leaves ``_ALIGN`` rows of slack: the kernel
    processes [wb_al, wb_al + SMALL_CHUNK) and the window head offset
    ``wb - wb_al`` can reach _ALIGN - 1."""
    plan = []
    small_max = SMALL_CHUNK - _ALIGN
    if small_max < n:
        plan.append((True, SMALL_CHUNK, small_max))
    if _MID_MAX < n:
        plan.append((False, SMALL_CHUNK, _MID_MAX))
        plan.append((False, CHUNK, None))
    else:
        plan.append((False, SMALL_CHUNK, None))
    return tuple(plan)


def bucket_name(small: bool, chunk: int) -> str:
    """``small`` / ``c1024`` / ``c4096``: a plan entry's name, the suffix of
    its kernel's name (``partition_hist_pallas_<name>``)."""
    return "small" if small else "c%d" % chunk


def bucket_of(window_rows, plan) -> np.ndarray:
    """Index into ``plan`` of the bucket that serves a split window of
    ``window_rows`` rows (host, NumPy): the bounds and the side the tree
    builder's ``jnp.searchsorted`` uses.  A finished tree's
    ``internal_count`` says which kernel variant served each split."""
    bounds = np.asarray([b for (_, _, b) in plan[:-1]], np.int64)
    return np.searchsorted(bounds, np.asarray(window_rows, np.int64))


class _ScalRow:
    """One window's scalar-prefetch row: ``scal[i]`` reads ``scal_ref[i]``
    for the single-window kernels and ``scal_ref[g, i]`` for a grid step of
    the multi-window (level-batched) variants — the kernel bodies and their
    shared building blocks (:func:`_route_tile`, :func:`_hist_tile`) index
    the view identically in both modes, which is what keeps the
    level-batched launch bit-exact against a sequence of single-window
    launches (same op sequence per window)."""

    def __init__(self, ref, g=None):
        self._ref = ref
        self._g = g

    def __getitem__(self, i):
        if self._g is None:
            return self._ref[i]
        return self._ref[self._g, i]


def _route_tile(col, scal_ref, num_bins):
    """go-left decision as a [T, 1] i32 0/1 vector (Mosaic cannot truncate i8
    vectors to i1, so boolean logic stays in i32 arithmetic); scalar split
    description from SMEM (bitset words ride in scal[12:] as i32).  Same
    semantics as tree_learner._route_left (tree.h:262-331)."""
    thr = scal_ref[3]
    default_left = scal_ref[4]
    mt = scal_ref[5]
    nb = scal_ref[6]
    dbin = scal_ref[7]
    is_cat = scal_ref[8] == 1
    use_unfold = scal_ref[10] == 1
    eoff = scal_ref[11]
    # EFB group code -> feature bin (tree_learner._unfold_bin)
    in_range = ((col >= eoff).astype(jnp.int32)
                * (col <= eoff + nb - 2).astype(jnp.int32))
    unfolded = jnp.where(in_range == 1, col - eoff + 1, 0)
    col = jnp.where(use_unfold, unfolded, col)
    is_missing = jnp.where(
        mt == 1, (col == nb - 1).astype(jnp.int32),          # MissingType.NAN
        jnp.where(mt == 2, (col == dbin).astype(jnp.int32),  # MissingType.ZERO
                  jnp.zeros_like(col)))
    num_left = jnp.where(is_missing == 1,
                         jnp.full_like(col, 1) * default_left,
                         (col <= thr).astype(jnp.int32))
    # categorical: bin membership in the left bitset words
    word = jnp.zeros_like(col)
    for wd in range(num_bins // 32):
        word = jnp.where((col >> 5) == wd, scal_ref[12 + wd], word)
    cat_left = (word >> (col & 31)) & 1
    return jnp.where(is_cat, cat_left, num_left)


# ---- phase-A building blocks shared by every kernel variant (round 7) ----
# Bucketed kernels must stay BIT-EXACT against each other in interpret mode
# (the dispatch assigns each window size to exactly one bucket, but the test
# suite pins cross-variant equality so a retune can never shift numerics);
# sharing the op sequence is what guarantees it.


def _extract_col_lanes(ti_i8, gcol, *, W, bpc, packed, npk):
    """ONE i8 x i8 -> i32 MXU dot extracts the split column for a whole
    [npk*128, W] i8 tile, TRANSPOSED ([2, W] @ [R, W]^T) so the result and
    the packed reshape stay lane-major; & 255 undoes the signed-byte wrap.
    Returns the lane-packed [npk, 128] i32 bin codes."""
    lanes_w = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    if packed:
        colsel = (lanes_w == gcol // 2).astype(jnp.int8)
        colsel2 = jnp.zeros((1, W), jnp.int8)
    elif bpc == 2:
        colsel = (lanes_w == 2 * gcol).astype(jnp.int8)
        colsel2 = (lanes_w == 2 * gcol + 1).astype(jnp.int8)
    else:
        colsel = (lanes_w == gcol).astype(jnp.int8)
        colsel2 = jnp.zeros((1, W), jnp.int8)
    wmat = jnp.concatenate([colsel, colsel2], axis=0)        # [2, W]
    extTi = jax.lax.dot_general(
        wmat, ti_i8, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                    # [2, R]
    lo_p = extTi[0:1, :].reshape(npk, _LANE) & 255
    if packed:
        return jnp.where(gcol % 2 == 1, (lo_p >> 4) & 15, lo_p & 15)
    if bpc == 2:
        return lo_p | ((extTi[1:2, :].reshape(npk, _LANE) & 255) << 8)
    return lo_p


def _subtile_prefixes(S_L, S_R, ltri, *, nsub):
    """Per-subtile inclusive prefixes + per-side cumulative totals, all
    lane-resident: S stacks the selection vectors as [2*nsub, T] lane-major
    (row s = left stream of subtile s, row nsub+s = right) so the prefixes
    are ONE [2*nsub, T] @ upper-tri[T, T] MXU dot and the cross-subtile
    cumulative totals one tiny dot more.  Per-subtile totals <= T = 128, so
    the f32/bf16 hop for the tiny triB dot stays exact.
    Returns (pfxU [2*nsub, T] i32, tot_col, incl_col, excl_col [2*nsub, 1]
    f32)."""
    S = jnp.concatenate([S_L, S_R], axis=0).astype(jnp.int8)
    pfxU = jax.lax.dot_general(
        S, ltri[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                    # [2*nsub, T]
    tot_col = pfxU[:, T - 1:T].astype(jnp.float32)
    # per-side cumulative totals (lower-tri within each block)
    iiB = jax.lax.broadcasted_iota(jnp.int32, (2 * nsub, 1), 0)
    jjB = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * nsub), 1)
    triB = ((iiB >= jjB).astype(jnp.int32)
            * ((iiB < nsub) == (jjB < nsub)).astype(jnp.int32)
            ).astype(jnp.bfloat16)
    incl_col = jax.lax.dot_general(
        triB, tot_col.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [2*nsub, 1]
    return pfxU, tot_col, incl_col, incl_col - tot_col


def _subtile_totals_lanes(S_L, S_R, *, nsub):
    """The subtile totals of :func:`_subtile_prefixes` once more, LANE-major
    for the pipelined kernel's VMEM->SMEM totals bank: [2, 2*nsub] i32, row
    0 = per-subtile counts (``tot_col``), row 1 = per-side inclusive running
    counts (``incl_col``) — the same exact integers (0/1 operands, counts
    <= chunk).  The bank's sliced dimension has to be a leading one with
    whole-tile minor dims: Mosaic refuses the DMA of a lane-padded
    [2*nsub, 2] column layout (slice not aligned to the 128 tiling)."""
    S = jnp.concatenate([S_L, S_R], axis=0).astype(jnp.int8)  # [2*nsub, T]
    tot_row = jax.lax.dot_general(
        jnp.ones((1, T), jnp.int8), S, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                    # [1, 2*nsub]
    iiB = jax.lax.broadcasted_iota(jnp.int32, (2 * nsub, 1), 0)
    jjB = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * nsub), 1)
    triBT = ((iiB <= jjB).astype(jnp.int32)
             * ((iiB < nsub) == (jjB < nsub)).astype(jnp.int32)
             ).astype(jnp.bfloat16)
    incl_row = jax.lax.dot_general(
        tot_row.astype(jnp.float32).astype(jnp.bfloat16), triBT,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [1, 2*nsub]
    return jnp.concatenate([tot_row, incl_row.astype(jnp.int32)], axis=0)


# rows of a chunk's bank entry (one [8, 128] i32 vreg a chunk): the subtile
# totals, then what phase C needs of each subtile ready-made (PR 40)
_BANK_ROWS = 8       # six in use
_BK_TOT, _BK_INCL, _BK_FIRST, _BK_CUT, _BK_ADV, _BK_CUR = range(6)
_TS_SHIFT = TS.bit_length() - 1
assert 1 << _TS_SHIFT == TS


def _subtile_scalars_lanes(totals, headL, cumLv, cumRv, slotLv, slotRv, *,
                           nsub, nb_ring):
    """Phase C's per-subtile scalars for a whole chunk, as lane vectors
    (PR 40): from ``totals`` (:func:`_subtile_totals_lanes`, [2, 2*nsub]),
    the streams' fills before the chunk (``headL`` a scalar, ``cumLv`` /
    ``cumRv`` [1, 1]) and the ring slots of their open tiles (``slotLv`` /
    ``slotRv`` [1, 1]: ``(stream position // TS) % nb_ring``, carried and
    never divided), the [6, 2*nsub] i32 bank entry, lane s the left stream
    of subtile s and lane nsub + s the right:

    - ``_BK_TOT``, ``_BK_INCL``: the totals as they came;
    - ``_BK_FIRST``, ``_BK_CUT``: :func:`_rows_mask`'s two scalars of the
      subtile's first tile row ``start`` (``(headL + fill + base) & (TS -
      1)``, ``base`` the side's rows in the chunk's earlier subtiles);
    - ``_BK_ADV``: ``start + n >= TS``, the subtile completes its tile;
    - ``_BK_CUR``: the WORD-ROW offset of the subtile's open tile in its
      stream's ring (``slot * _WPT``).

    Also returns the two streams' open slots after the chunk.  What the
    parent's phase C derived from SMEM with about 46 scalar operations a
    subtile is here a handful of vector operations a chunk
    (tests/test_partition_blend.py holds the two equal)."""
    tot = totals[0:1, :]
    incl = totals[1:2, :]
    left = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * nsub), 1) < nsub
    pos0 = jnp.where(left, headL + cumLv, cumRv)         # before the chunk
    pos = pos0 + incl - tot                              # before subtile s
    start = pos & (TS - 1)
    tile0 = pos0 >> _TS_SHIFT

    def slot_of(p):
        # p's tile is at most nsub tiles past tile0 and nsub < nb_ring
        sl = jnp.where(left, slotLv, slotRv) + (p >> _TS_SHIFT) - tile0
        return jnp.where(sl >= nb_ring, sl - nb_ring, sl)

    bank = jnp.concatenate([
        tot, incl, start - (start & 3), jnp.int32(-1) << (8 * (start & 3)),
        (start + tot >= TS).astype(jnp.int32), slot_of(pos) * _WPT], axis=0)
    after = slot_of(pos + tot)                           # [1, 2*nsub]
    return (bank, after[:, nsub - 1:nsub], after[:, 2 * nsub - 1:2 * nsub])


def _hist_tile(ti_c, hist_ref, scal_ref, start, cnt, *, num_features,
               num_bins, bpc, packed, exact, voff, f_shard,
               quantized=False):
    """One [R, W] i32 row-store tile's histogram += contribution for the
    rows at TILE-RELATIVE positions [start, start + cnt) — the shared
    accumulation op of the streamed hist pass, the small-window kernel and
    the copy-back-free right block (``start``/``cnt`` may be scalars or
    [1, 1] lane vectors; out-of-range rows contribute exact zeros, so the
    accumulated value is independent of the tile height R up to fp-identity
    adds)."""
    rows_n = ti_c.shape[0]
    if _use_factored(num_features, num_bins, quantized):
        # rolled fori_loop over blocks of feature groups (round 6; blocks
        # since PR 37): program size is O(p) in F, so wide-F row stores
        # compile instead of unrolling hundreds of groups
        ti_bf_h = ti_c.astype(jnp.bfloat16)
        posT = jax.lax.broadcasted_iota(jnp.int32, (1, rows_n), 1)
        inwT = ((posT >= start).astype(jnp.float32)
                * (posT < start + cnt).astype(jnp.float32))
        fb = (scal_ref[12 + num_bins // 32] if f_shard else 0)
        v4T = _extract_values_T(ti_bf_h, voff=voff, exact=exact, inwT=inwT,
                                quantized=quantized)
        _accum_factored_all(ti_bf_h, v4T, hist_ref,
                            num_features=num_features, num_bins=num_bins,
                            bpc=bpc, packed=packed, f_base=fb,
                            quantized=quantized)
        return
    # classic fallback (accumulators past the factored 4 MiB gate, i.e.
    # wide F): rolled fori_loop over lane tiles with dynamic-index column
    # extraction; the value path extracts via bf16 dots (it needs bf16
    # operands anyway)
    iota_lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    bwh = [(iota_lane == off).astype(jnp.bfloat16)
           + (iota_lane == off + 1).astype(jnp.bfloat16) * 256
           for off in (voff, voff + 2, voff + 4, voff + 6)]
    wmat_h = jnp.concatenate(bwh, axis=0)                    # [4, W]
    ext_h = jax.lax.dot_general(
        ti_c.astype(jnp.bfloat16), wmat_h,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [R, 4]
    exti_h = ext_h.astype(jnp.int32)
    g = jax.lax.bitcast_convert_type(
        exti_h[:, 0:1] | (exti_h[:, 1:2] << 16), jnp.float32)
    h = jax.lax.bitcast_convert_type(
        exti_h[:, 2:3] | (exti_h[:, 3:4] << 16), jnp.float32)
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows_n, 1), 0)
    inw = ((pos >= start).astype(jnp.float32)
           * (pos < start + cnt).astype(jnp.float32))
    # select, never multiply (see histogram._extract_values_T)
    vals = jnp.concatenate([jnp.where(inw > 0, g, 0.0),
                            jnp.where(inw > 0, h, 0.0)], axis=1)
    v4 = _hilo_split(vals, axis=1, exact=exact, quantized=quantized)
    colf = _colf_rows_dyn(ti_c, bpc=bpc, packed=packed)
    _accum_onehot_all(colf, v4, hist_ref, num_features=num_features,
                      num_bins=num_bins, contract_dim=0)


# ---- the flush rings' packed word view ----
# A [.., TS, W] u8 staging tile is [.., TS // 4, W] 32-bit words, four
# consecutive rows a word, the lowest row in the low byte.  Merging placed
# rows into a tile under a ROW-RANGE mask then takes one word mask
# ([TS // 4, W] i32: 4 vregs at W = 128) and three bit ops a vreg, where a u8
# select under a [TS, 1] i32 mask (16 vregs, one lane in 128 used) has to be
# broadcast over the lanes and packed 32 -> 16 -> 8 bit first: the static
# schedule for the described v5e spent 192 bundles a phase-C subtile on 8
# vregs of payload that way (PERF.md §5, tools/kernel_bundles.py).
_WPT = TS // 4       # word rows per staging tile


class _WordRef:
    """A u8 [.., R, W] VMEM scratch, loaded and stored a tile at a time as
    its [.., R // 4, W] i32 words.  On the chip that is a bitcast of the ref
    (no data moves); interpret mode refuses a store through a bitcast ref,
    so there the same bytes go through the value-level ``pltpu.bitcast`` of
    the u8 tile."""

    def __init__(self, ref, interpret):
        self._interpret = interpret
        self._ref = ref if interpret else ref.bitcast(jnp.int32)

    def _rows(self, w0):
        """The ``_WPT`` word rows from word row ``w0`` (a multiple of
        ``_WPT``), as a slice of the ref's second dimension."""
        if self._interpret:
            return pl.ds(4 * w0, TS)
        return pl.ds(w0, _WPT)

    def load(self, lead):
        if self._interpret:
            return pltpu.bitcast(self._ref[lead, self._rows(0), :],
                                 jnp.int32)
        return self._ref[lead, self._rows(0), :]

    def store(self, lead, words, w0=0):
        if self._interpret:
            self._ref[lead, self._rows(w0), :] = pltpu.bitcast(words,
                                                               jnp.uint8)
        else:
            self._ref[lead, self._rows(w0), :] = words


def _words_of(tr):
    """[TS, W] i32 dot result whose row ``k * _WPT + i`` holds byte k of
    word row i (the one-hot's output rows ordered ``q = (p % 4) * _WPT + p
    // 4`` for tile row p) -> the [TS // 4, W] i32 words: three shifts and
    ors of whole vregs, not a 32 -> 16 -> 8 bit pack; ``& 255`` undoes the
    signed-byte wrap of the i8 x i8 dot."""
    return ((tr[0:_WPT] & 255)
            | ((tr[_WPT:2 * _WPT] & 255) << 8)
            | ((tr[2 * _WPT:3 * _WPT] & 255) << 16)
            | (tr[3 * _WPT:] << 24))


def _byte_major(q):
    """Tile row ``p`` that output row ``q`` of a [TS, .] placement one-hot
    holds in :func:`_words_of`'s order: ``4 * (q % _WPT) + q // _WPT``."""
    return 4 * (q & (_WPT - 1)) + (q >> (_WPT.bit_length() - 1))


def _word_base(W):
    """Loop-invariant base of :func:`_rows_mask`: the first tile row of
    each word, ``4 * word row``, over the lanes."""
    return 4 * jax.lax.broadcasted_iota(jnp.int32, (_WPT, W), 0)


def _rows_mask(base, first, cut):
    """[TS // 4, W] i32 word mask of a staging tile: all ones in the bytes of
    tile rows >= ``start`` (in [0, TS]), zero in the bytes below, from
    ``first = start - (start & 3)`` and ``cut = -1 << (8 * (start & 3))``:
    only the word that holds row ``start`` is cut; the vector work is two
    compares and two selects."""
    return jnp.where(base > first, -1, jnp.where(base == first, cut, 0))


def _rows_from(base, start):
    """:func:`_rows_mask` of a scalar ``start``."""
    return _rows_mask(base, start - (start & 3),
                      jnp.int32(-1) << (8 * (start & 3)))


def _merge_rows(upper, lower, m):
    """Rows >= start from ``upper``, rows below from ``lower`` (words under
    the mask ``m`` of :func:`_rows_mask`)."""
    return lower ^ ((lower ^ upper) & m)


def _next_slot(slot, nb_ring):
    """The ring slot after ``slot`` (a scalar compare, not a divide)."""
    return jnp.where(slot == nb_ring - 1, 0, slot + 1)


def _walk_ring(lo, hi, fn, nb_ring):
    """``fn(m, slot)`` for the items ``m`` of [lo, hi) in turn, with ``slot
    = m % nb_ring`` carried on from one divide a walk, not divided an item:
    the flush loops are scalar-only, so an item's divide is latency nothing
    hides (0.125 ns a window row on the chip, PERF.md §6 PR 31)."""
    def body(m, slot):
        fn(m, slot)
        return _next_slot(slot, nb_ring)

    jax.lax.fori_loop(lo, hi, body, jax.lax.rem(lo, nb_ring))


def _append_placed(streams, base):
    """Merge one subtile's placed rows into the two streams' open tiles.
    Each stream is ``(ring, tile, cur, comp, first, cut, adv)``: ``tile``
    the words of the stream's open tile, carried in registers from subtile
    to subtile, ``cur`` the word-row offset of its slot in ``ring``
    (:class:`_WordRef`), ``comp`` (words) the subtile's ``n`` rows at the
    circular positions [start, start + n) mod TS and ZEROS everywhere else
    (the one-hot placement), ``first`` / ``cut`` :func:`_rows_mask`'s
    scalars of ``start``, ``adv`` nonzero when ``start + n >= TS``.  The
    open tile takes every row >= ``start`` from ``comp``, with no upper
    bound: a tile's rows past its fill point are never read before they are
    filled (the finals mask by their pending count, the copy-back by
    ``nr``).  Rows below ``start`` stay: earlier subtiles', or the prefilled
    head's.  The merged tile goes to its slot every time: when the subtile
    completes it that is the copy the flush reads, and before that a store
    nobody reads, which is cheaper than a branch around it.  A subtile that
    completes its tile leaves ``comp`` as the next open tile: the wrapped
    rows lie below the new tile's fill point ``start + n - TS``, and
    everything above it is past the fill point again.  No load from the
    ring, no branch: a chunk's subtiles are one basic block, the two
    streams' chains in it side by side.  Returns the streams' open tiles
    after the subtile."""
    out = []
    for ring, tile, cur, comp, first, cut, adv in streams:
        merged = _merge_rows(comp, tile, _rows_mask(base, first, cut))
        ring.store(0, merged, w0=cur)
        out.append(jnp.where(adv != 0, comp, merged))
    return tuple(out)


def _make_partition_kernel(*, n_pad, W, num_features, num_bins, voff, bpc,
                           packed, exact, f_shard=False, dbg_skip="",
                           chunk=CHUNK, multiwin=False, quantized=False,
                           interpret=False):
    # f_shard: the histogrammed feature window starts at scal[12 + B//32]
    # (feature-parallel shards build only their own F/d block while routing
    # on the full row store); num_features is then the WINDOW's width
    del n_pad  # shapes come from the refs; kept for cache-key clarity
    nb_ring = _ring_depth(chunk)
    run = _flush_run(chunk)  # tiles a flush descriptor
    run_shift = run.bit_length() - 1
    nbk = nb_ring // run     # flush blocks (and semaphores) a stream's ring
    nb_cb = _cb_depth(chunk)
    assert nb_ring % run == 0
    totk = _totk(chunk)
    ncb = totk + 1           # comp_buf banks: totk chunks awaiting phase C
                             # plus the chunk being placed

    def kernel(scal_ref, rows_in_ref, rows_ref, scratch_ref, hist_ref,
               stats_ref, inbuf, ring_l, ring_r, stage, ltri, rot, tmp,
               comp_buf, totals_vm, totals_sm,
               sem_in, sem_pre, sem_fl, sem_fr, sem_cb, sem_tot):
        # rows_in_ref is the pre-alias view of rows_ref (same buffer); all
        # reads and writes go through rows_ref so ordering is explicit.
        # ring_l / ring_r are the two streams' flush rings, [1, nb_ring*TS,
        # W] each: a stream's finished TS-row tiles wait there for their
        # block's flush (separate buffers, so the compiler knows a store
        # into one never aliases the other); stage is the copy-back's ring
        # of nb_cb tiles.  Flush DMAs are ASYNC — a block of slots'
        # previous flush is awaited only when the ring wraps back to it, so
        # the VPU/MXU never stalls on HBM writes (sync flushes were ~60% of
        # the kernel in round-4 profiles).
        del rows_in_ref
        with jax.named_scope(_scopes.K_PROLOGUE):
            scal = (_ScalRow(scal_ref, pl.program_id(0)) if multiwin
                    else scal_ref)
            wb = scal[0]
            wc = scal[1]
            gcol = scal[2]
            hist_left = scal[9]
            # every merge of placed rows into a staging tile works on the words
            stage_w = _WordRef(stage, interpret)
            rings_w = (_WordRef(ring_l, interpret), _WordRef(ring_r, interpret))
            tmp_w = _WordRef(tmp, interpret)
            wbase = _word_base(W)

            wb_al = pl.multiple_of((wb // _ALIGN) * _ALIGN, _ALIGN)
            headL = wb - wb_al
            nchunks = (headL + wc + chunk - 1) // chunk

            hist_ref[...] = jnp.zeros_like(hist_ref)
            # upper-triangular ones U[j, t] = (j <= t): subtiles are STACKED
            # ALONG M so one [2*nsub, T] @ U dot computes every subtile's local
            # inclusive prefix lane-major — a skinny N=2 prefix matmul is MXU
            # weight-load bound (~2.3us each), and sublane-major prefixes would
            # put every per-row intermediate in 128x-padded [CHUNK, 1] vregs
            ltri[...] = (jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
                         <= jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                         ).astype(jnp.int8)

            def left_dst(nf):
                return pl.multiple_of(wb_al + nf * TS, _ALIGN)

            def ring_tiles(ring, slot, n=1):
                """``n`` tiles of a stream's ring from slot ``slot``."""
                return ring.at[0, pl.ds(pl.multiple_of(slot * TS, TS), n * TS)]

            # the left stream's first open tile starts with the old rows
            # [wb_al, wb), so the first aligned flush preserves the
            # neighbour leaf's rows
            cp = pltpu.make_async_copy(
                rows_ref.at[pl.ds(wb_al, _ALIGN)],
                tmp.at[0, pl.ds(0, _ALIGN)], sem_pre)
            cp.start()
            cp.wait()

            # deepened input ring: NIN - 1 reads in flight, so the chunk-read
            # semaphore wait overlaps the previous chunk's phase A/B matmuls and
            # the trailing phase C (software pipeline below)
            for j in range(NIN - 1):
                @pl.when(j < nchunks)
                def _prologue(j=j):
                    pltpu.make_async_copy(
                        rows_ref.at[pl.ds(
                            pl.multiple_of(wb_al + j * chunk, _ALIGN), chunk)],
                        inbuf.at[j], sem_in.at[j]).start()

            # stage row of each output row of the placement one-hot, the
            # left stream's TS rows then the right's: byte-major a side
            # (:func:`_words_of`), so phase B puts its rows out as words
            q2 = jax.lax.broadcasted_iota(jnp.int32, (2 * TS, 1), 0)
            q_in = q2 & (TS - 1)
            place_row = q2 - q_in + _byte_major(q_in)
        totals_on = "totals" not in dbg_skip and "prefix" not in dbg_skip
        nsub = chunk // T
        npk = chunk // _LANE                   # lane-packed rows (row r ->
                                               # [r // 128, r % 128])

        # A stream (0 left, 1 right) flushes its ring to its destination: the
        # window's front in place, or the scratch.  A block b (tiles [b * run,
        # (b + 1) * run)) leaves its ring slots, bs = b % nbk, with ONE
        # descriptor on one semaphore of its own; a wait consumes exactly
        # what its start signalled (DMAs may land out of order: a shared
        # counting semaphore could not say that the OLDEST block is done)
        rings, sems = (ring_l, ring_r), (sem_fl, sem_fr)

        def stream_dst(side, m, n=1):
            """``n`` tiles of a stream's destination from its tile ``m``."""
            if side:
                return scratch_ref.at[pl.ds(pl.multiple_of(m * TS, _ALIGN),
                                            n * TS)]
            return rows_ref.at[pl.ds(left_dst(m), n * TS)]

        def flush(side, b, bs):
            return pltpu.make_async_copy(
                ring_tiles(rings[side], bs * run, run),
                stream_dst(side, b * run, run), sems[side].at[bs])

        def each_block(side, lo, hi, do):
            _walk_ring(lo, hi, lambda b, bs: do(flush(side, b, bs)), nbk)

        def start(copy):
            copy.start()

        def wait(copy):
            copy.wait()

        # ---- software pipeline (rounds 6-7) ----
        # The round-5 kernel ran A -> B -> totals-DMA-wait -> C per chunk:
        # the VMEM->SMEM totals round-trip and the flush-ring semaphore
        # waits sat on the critical path every chunk (PERF.md measured the
        # residual phase-A cost at ~10x its isolated compute replica — all
        # scheduling).  Round 6 deferred phase C one chunk; round 7 widens
        # the totals window: chunks write their subtile totals into GROUP
        # banks of ``totk`` chunks, ONE DMA per group ships the whole bank
        # to SMEM, and phase C (scalar blends + flushes) trails ``totk``
        # chunks behind phase A/B — the group's first phase C awaits a DMA
        # that has had a full group of matmuls to land.  Phase B never
        # needs the scalar fill counters — the cumulative placed-row counts
        # ride the A/B stage as lane-resident [1, 1] vectors (cumLv/cumRv),
        # bit-equal to the SMEM-derived scalars phase C uses for DMA
        # offsets; since PR 40 phase A also works out, from the same
        # vectors, every scalar phase C needs of a subtile and ships it in
        # the bank (:func:`_subtile_scalars_lanes`).
        def chunk_ab(c, cum):
            cumLv, cumRv, slotLv, slotRv = cum
            slot = jax.lax.rem(c, NIN)
            pltpu.make_async_copy(
                rows_ref.at[pl.ds(pl.multiple_of(wb_al + c * chunk, _ALIGN),
                                  chunk)],
                inbuf.at[slot], sem_in.at[slot]).wait()

            @pl.when(c + NIN - 1 < nchunks)
            def _prefetch():
                nxt = jax.lax.rem(c + NIN - 1, NIN)
                pltpu.make_async_copy(
                    rows_ref.at[pl.ds(
                        pl.multiple_of(wb_al + (c + NIN - 1) * chunk,
                                       _ALIGN), chunk)],
                    inbuf.at[nxt], sem_in.at[nxt]).start()

            abs0 = wb_al + c * chunk
            # ---- phase A (vector): convert, route, per-subtile prefixes.
            # EVERY per-row intermediate lives LANE-PACKED as [chunk/128,
            # 128] — [chunk, 1]-shaped vectors are 128x vreg-padded on v5e
            # and made this phase 2.6 ns/row in the round-5 knockout profile
            # (~90% of phase A); the same math lane-packed is ~30 vregs per
            # chunk.  Per-subtile totals land in SMEM via group DMAs (direct
            # vector->scalar extraction costs ~0.7us EACH and does not
            # pipeline).  The streamed tile is used ONLY through i8 x i8 ->
            # i32 MXU dots (probed exact on v5e), so a zero-cost bitcast
            # VIEW replaces the round-4/5 u8 -> i32 -> bf16 tile converts;
            # signed-byte wrap is undone with & 255 after each dot
            if "convert" in dbg_skip:          # profiling: stream-only floor
                ti_i8 = jnp.zeros((chunk, W), jnp.int8)
            elif "statslot" in dbg_skip:       # profiling: static buffer read
                ti_i8 = jax.lax.bitcast_convert_type(inbuf[0], jnp.int8)
            else:
                ti_i8 = jax.lax.bitcast_convert_type(inbuf[slot], jnp.int8)
            if "extract" in dbg_skip:          # profiling: no extract/route
                col_p = jnp.zeros((npk, _LANE), jnp.int32)
            else:
                col_p = _extract_col_lanes(ti_i8, gcol, W=W, bpc=bpc,
                                           packed=packed, npk=npk)
            gl_p = _route_tile(col_p, scal, num_bins)        # [npk, 128]
            pos_p = (abs0
                     + jax.lax.broadcasted_iota(jnp.int32, (npk, 1), 0)
                     * _LANE
                     + jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1))
            inw_p = ((pos_p >= wb).astype(jnp.int32)
                     * (pos_p < wb + wc).astype(jnp.int32))
            selL_p = gl_p * inw_p                            # i32 0/1
            selR_p = (1 - gl_p) * inw_p
            assert T % _LANE == 0
            if T == _LANE:
                S_L, S_R = selL_p, selR_p
            else:
                S_L = selL_p.reshape(nsub, T)
                S_R = selR_p.reshape(nsub, T)
            # round-7 group banking: chunk c's bank entry lives at bank row
            # gpar*totk + kk, reused by chunk c + 2*totk — whose phase A
            # runs only after this group's DMA was awaited by phase
            # C(c - totk) (C trails totk chunks, so the reuse never races
            # the in-flight copy)
            kk = jax.lax.rem(c, totk)
            gpar = jax.lax.rem(c // totk, 2)
            bankt = gpar * totk + kk
            bankb = jax.lax.rem(c, ncb)
            if "prefix" in dbg_skip:           # profiling: no prefix/totals
                pfxU = jnp.zeros((2 * nsub, T), jnp.int32)
                excl_col = jnp.zeros((2 * nsub, 1), jnp.float32)
                incl_col = jnp.zeros((2 * nsub, 1), jnp.float32)
            else:
                pfxU, _tot, incl_col, excl_col = _subtile_prefixes(
                    S_L, S_R, ltri, nsub=nsub)
                if totals_on:
                    bank, slotLv, slotRv = _subtile_scalars_lanes(
                        _subtile_totals_lanes(S_L, S_R, nsub=nsub), headL,
                        cumLv, cumRv, slotLv, slotRv, nsub=nsub,
                        nb_ring=nb_ring)
                    totals_vm[bankt, 0:bank.shape[0], 0:2 * nsub] = bank

                    @pl.when((kk == totk - 1) | (c == nchunks - 1))
                    def _start_totals():
                        # ONE DMA ships the whole group's entries (partial
                        # final groups ship stale tail rows phase C never
                        # reads); awaited by phase C of the group's FIRST
                        # chunk, a full ``totk`` chunks of matmuls later,
                        # so the round-trip is off the critical path
                        base = pl.multiple_of(gpar * totk, totk)
                        pltpu.make_async_copy(
                            totals_vm.at[pl.ds(base, totk)],
                            totals_sm.at[pl.ds(base, totk)],
                            sem_tot.at[gpar]).start()

            # ---- phase B (vector, back-to-back with phase A — the totals
            # DMA and the trailing phase C overlap it): place every
            # subtile into this chunk's comp_buf bank.  The placement
            # one-hot is built TRANSPOSED — dest as a [1, T] lane vector
            # against a [2TS, 1] iota — so the dest math is lane-packed
            # too; the [2TS, T] @ [T, W] dot then lands rows directly in
            # staging order, byte-major a stream, and the words phase C
            # merges are three shifts and ors of whole vregs
            # (:func:`_words_of`, as the copy-back makes its own).  The
            # cross-chunk fill counters enter as the lane-resident
            # cumLv/cumRv (phase B never reads SMEM).
            for s in range(nsub) if "phaseB" not in dbg_skip else []:
                selLs = S_L[s:s + 1, :]                      # [1, T] i32
                selRs = S_R[s:s + 1, :]
                pfxLs = pfxU[s:s + 1, :]                     # [1, T] i32
                pfxRs = pfxU[nsub + s:nsub + s + 1, :]
                bL = excl_col[s:s + 1, 0:1].astype(jnp.int32)
                bR = excl_col[nsub + s:nsub + s + 1, 0:1].astype(jnp.int32)
                destL = jax.lax.rem(headL + cumLv + bL + pfxLs - 1, TS)
                destR = TS + jax.lax.rem(cumRv + bR + pfxRs - 1, TS)
                dest = jnp.where(selLs == 1, destL,
                                 jnp.where(selRs == 1, destR, 2 * TS))
                Pt = (dest == place_row).astype(jnp.int8)        # [2TS, T]
                comp_i = jax.lax.dot_general(
                    Pt, ti_i8[s * T:(s + 1) * T, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)            # [2TS, W]
                for side in range(2):
                    comp_buf[bankb, pl.ds((2 * s + side) * _WPT, _WPT), :] = (
                        _words_of(comp_i[side * TS:(side + 1) * TS]))

            # per-side chunk totals ride the carry as [1, 1] vectors (exact:
            # counts <= chunk << 2^24, and the bf16 operands of the incl dot
            # are exact 0/1 and <= 128 values)
            totL = incl_col[nsub - 1:nsub, 0:1].astype(jnp.int32)
            totR = incl_col[2 * nsub - 1:2 * nsub, 0:1].astype(jnp.int32)
            return cumLv + totL, cumRv + totR, slotLv, slotRv

        def chunk_c(c, cc):
            # phase C for chunk c (scalar blends + flushes), running ``totk``
            # CHUNKS behind phase A/B: the group's banked totals DMA has had
            # a full group of matmuls to land, so the once-per-group wait
            # below is free in steady state.  Every part of the carry is a
            # pair, left stream then right: the rows placed so far, the flush
            # blocks started, the blocks awaited, the open tiles' words.
            fill, started, awaited, opens = cc
            kk = jax.lax.rem(c, totk)
            gpar = jax.lax.rem(c // totk, 2)
            bankt = gpar * totk + kk
            bankb = jax.lax.rem(c, ncb)
            if totals_on:
                @pl.when(kk == 0)
                def _await_totals():
                    base = pl.multiple_of(gpar * totk, totk)
                    pltpu.make_async_copy(
                        totals_vm.at[pl.ds(base, totk)],
                        totals_sm.at[pl.ds(base, totk)],
                        sem_tot.at[gpar]).wait()
                fill = tuple(
                    fill[side] + totals_sm[bankt, _BK_INCL,
                                           (side + 1) * nsub - 1]
                    for side in range(2))
            # the streams' tiles complete after chunk c
            k1 = ((headL + fill[0]) >> _TS_SHIFT, fill[1] >> _TS_SHIFT)

            # await the blocks whose ring slots this chunk may store into:
            # tiles up to k1, so every tile <= k1 - nb_ring has to be gone
            if "flush" not in dbg_skip:
                done = tuple(
                    jnp.maximum(awaited[side], jnp.maximum(
                        k1[side] - nb_ring + run, 0) >> run_shift)
                    for side in range(2))
                for side in range(2):
                    each_block(side, awaited[side], done[side], wait)
                awaited = done

            for s in range(nsub) if "phaseC" not in dbg_skip else []:
                # subtile s's rows into the two streams' open tiles, every
                # scalar of it ready-made in the bank
                streams = []
                for side, j in ((0, s), (1, nsub + s)):
                    if totals_on:
                        cur, first, cut, adv = (
                            totals_sm[bankt, r, j]
                            for r in (_BK_CUR, _BK_FIRST, _BK_CUT, _BK_ADV))
                        cur = pl.multiple_of(cur, _WPT)
                    else:                      # "prefix"/"totals" knockouts:
                        cur, first, cut, adv = 0, 0, -1, 0   # an empty bank
                    streams.append((
                        rings_w[side], opens[side], cur,
                        comp_buf[bankb, pl.ds((2 * s + side) * _WPT, _WPT), :],
                        first, cut, adv))
                opens = _append_placed(streams, wbase)

            # start the flushes of the blocks this chunk completed
            # (scalar-only loops, a descriptor a block)
            if "flush" not in dbg_skip:
                whole = tuple(k >> run_shift for k in k1)
                for side in range(2):
                    each_block(side, started[side], whole[side], start)
                started = whole

            return fill, started, awaited, opens

        zero = jnp.int32(0)
        zv = jnp.zeros((1, 1), jnp.int32)

        def pipe_body(c, carry):
            # steady state: A/B of chunk c overlaps the in-flight totals DMA
            # of the previous group, whose phase C trails ``totk`` chunks
            # behind (the inner fori_loop has exactly one trip for
            # c >= totk and zero before)
            cum, cc = carry
            cum = chunk_ab(c, cum)
            cc = jax.lax.fori_loop(jnp.maximum(c - totk, 0),
                                   jnp.maximum(c - totk + 1, 0), chunk_c, cc)
            return cum, cc

        with jax.named_scope(_scopes.K_PLACE):
            # the streams' open tiles as words: the left one starts with the
            # prefilled head (rows past it are past the fill point)
            opens = (tmp_w.load(0), jnp.zeros((_WPT, W), jnp.int32))
            _, cc = jax.lax.fori_loop(
                0, nchunks, pipe_body,
                ((zv, zv, zv, zv), ((zero, zero),) * 3 + (opens,)))
        with jax.named_scope(_scopes.K_DRAIN):
            # pipeline epilogue: the trailing ``totk`` chunks' phase C
            (nl, nr), started, awaited, opens = jax.lax.fori_loop(
                jnp.maximum(nchunks - totk, 0), nchunks, chunk_c, cc)
            stats_ref[...] = jnp.full(stats_ref.shape, nl, jnp.int32)
            # the streams' complete tiles, and the rows of their open ones
            nf = ((headL + nl) >> _TS_SHIFT, nr >> _TS_SHIFT)
            pend_l = headL + nl - nf[0] * TS
            pend_r = nr - nf[1] * TS

            # the complete tiles past a stream's last whole block (under
            # ``run`` of them) go a tile at a time, all on one semaphore of
            # the stream's: equal sizes, and every one of them is awaited
            def each_tail(side, do):
                _walk_ring(
                    started[side] * run, nf[side],
                    lambda m, sl: do(pltpu.make_async_copy(
                        ring_tiles(rings[side], sl), stream_dst(side, m),
                        sems[side].at[0])), nb_ring)

            if "flush" not in dbg_skip:
                for side in range(2):          # the outstanding blocks
                    each_block(side, awaited[side], started[side], wait)
                for side in range(2):
                    each_tail(side, start)

            # ---- final right partial flush (scratch is all ours: no RMW,
            # garbage tail rows are masked by nr during copy-back) ----
            @pl.when(pend_r > 0)
            def _final_right():
                tmp_w.store(0, opens[1])
                cpf = pltpu.make_async_copy(tmp.at[0], stream_dst(1, nf[1]),
                                            sem_pre)
                cpf.start()
                cpf.wait()

            # ---- final left partial flush (read-modify-write) ----
            @pl.when(pend_l > 0)
            def _final_left():
                cpa = pltpu.make_async_copy(stream_dst(0, nf[0]), tmp.at[0],
                                            sem_pre)
                cpa.start()
                cpa.wait()
                tmp_w.store(0, _merge_rows(tmp_w.load(0), opens[0],
                                           _rows_from(wbase, pend_l)))
                cpb = pltpu.make_async_copy(tmp.at[0], stream_dst(0, nf[0]),
                                            sem_pre)
                cpb.start()
                cpb.wait()

            if "flush" not in dbg_skip:
                for side in range(2):
                    each_tail(side, wait)

        # ---- smaller child's histogram from its CONTIGUOUS block ----
        # Post-partition the smaller child is contiguous (left block in
        # rows_ref, right block in scratch).  With the factored hi/lo build
        # (histogram._accum_factored_block: one extraction dot a block of
        # feature groups, then a group's select-weighted hi one-hots
        # [128, chunk] as the MXU's weights under its lo one-hots, into the
        # lane-dense [G*p*nlo, 128] accumulator) the per-row cost is nhi +
        # nlo compares per feature instead of B — near-independent of
        # max_bin; wide-F datasets fall back to the classic packed one-hot
        # tiles.
        if "hist" not in dbg_skip:
            def hist_pass(src_ref, base_al, head, cnt):
                nh = (head + cnt + chunk - 1) // chunk

                for j in range(NIN - 1):
                    @pl.when(j < nh)
                    def _pro(j=j):
                        pltpu.make_async_copy(
                            src_ref.at[pl.ds(
                                pl.multiple_of(base_al + j * chunk, _ALIGN),
                                chunk)],
                            inbuf.at[j], sem_in.at[j]).start()

                def hbody(c, _):
                    slot = jax.lax.rem(c, NIN)
                    pltpu.make_async_copy(
                        src_ref.at[pl.ds(
                            pl.multiple_of(base_al + c * chunk, _ALIGN),
                            chunk)],
                        inbuf.at[slot], sem_in.at[slot]).wait()

                    @pl.when(c + NIN - 1 < nh)
                    def _pre():
                        nxt = jax.lax.rem(c + NIN - 1, NIN)
                        pltpu.make_async_copy(
                            src_ref.at[pl.ds(
                                pl.multiple_of(base_al + (c + NIN - 1)
                                               * chunk, _ALIGN), chunk)],
                            inbuf.at[nxt], sem_in.at[nxt]).start()

                    ti_c = inbuf[slot].astype(jnp.int32)
                    _hist_tile(ti_c, hist_ref, scal,
                               head - c * chunk, cnt,
                               num_features=num_features, num_bins=num_bins,
                               bpc=bpc, packed=packed, exact=exact,
                               voff=voff, f_shard=f_shard,
                               quantized=quantized)
                    return 0

                jax.lax.fori_loop(0, nh, hbody, 0)

            with jax.named_scope(_scopes.K_HIST):
                @pl.when(hist_left == 1)
                def _hist_left_block():
                    hist_pass(rows_ref, wb_al, headL, nl)

                @pl.when(hist_left != 1)
                def _hist_right_block():
                    hist_pass(scratch_ref, 0, 0, nr)

        # ---- copy right block back: scratch[0:nr] -> rows[wb+nl ...) ----
        # Same streamed-append machinery (chunk reads through the input ring,
        # the nb_cb-deep async flush ring ``stage``), with a constant
        # row rotation by the destination's 32-row phase.  One window on the
        # chip (2,097,152 rows, F = 28, histogram knocked out, PERF.md §6
        # PR 33): 1.031 ns a right row, whole kernel every row right less
        # every row left; 2.944 with ONE 16 KB read of the scratch in flight
        # a 128-row tile, which the loop waited for about 0.38 us a tile.
        def _copy_back():
            d0 = wb + nl
            d_al = pl.multiple_of((d0 // _ALIGN) * _ALIGN, _ALIGN)
            ph = d0 - d_al
            # constant row-rotation one-hot: source row j -> stage row
            # p = (j + ph) % TS, which the dot puts out byte-major
            # (:func:`_words_of`): byte k of every word as one contiguous
            # [TS // 4, W] block, so the words are three shifts and ors of
            # whole vregs and not a 32 -> 16 -> 8 bit pack
            # (rot is [q, j]: a plain [TS, TS] @ [TS, W] dot, no transpose
            # of the constant in every trip)
            rot[...] = (jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, (1, TS), 1) + ph, TS)
                == _byte_major(
                    jax.lax.broadcasted_iota(jnp.int32, (TS, 1), 0))
            ).astype(jnp.int8)
            # ph is the loop's constant: rows >= ph of a tile come from the
            # source tile that fills it, rows below from the one before
            m_ph = _rows_from(wbase, ph)
            # head prefill: keep rows [d_al, d0) (tail of the left block)
            cph = pltpu.make_async_copy(
                rows_ref.at[pl.ds(d_al, _ALIGN)],
                stage.at[0, pl.ds(0, _ALIGN)], sem_pre)
            cph.start()
            ncbk = (nr + TS - 1) // TS          # 128-row tiles in all
            ncc = (nr + chunk - 1) // chunk     # chunk reads of the scratch

            # The scratch comes in a chunk at a time through the input ring,
            # idle since ``hist_pass`` awaited its last read, NIN - 1 reads
            # in flight (the same reads ``hist_pass(scratch_ref, 0, 0, nr)``
            # makes, so inside the store's padding contract); the tile loop
            # takes TS-row tiles out of VMEM, so the one wait a chunk lands
            # behind chunk // TS tiles of work.  The tile loop is rolled: 32
            # tiles unrolled read 0.11 ns a right row slower on the chip.
            def read_cb(c, slot):
                return pltpu.make_async_copy(
                    scratch_ref.at[pl.ds(pl.multiple_of(c * chunk, _ALIGN),
                                         chunk)],
                    inbuf.at[slot], sem_in.at[slot])

            for j in range(NIN - 1):
                @pl.when(j < ncc)
                def _prologue_cb(j=j):
                    read_cb(j, j).start()
            cph.wait()

            def cb_tile(slot, left, kk, carry):
                # kk: the tile within its chunk read; left: the scratch's
                # rows from that read's first tile on
                fill, nf, cur = carry          # cur: tile nf's ring slot
                tr = jax.lax.dot_general(
                    rot[...],
                    jax.lax.bitcast_convert_type(
                        inbuf[slot, pl.ds(pl.multiple_of(kk * TS, TS), TS), :],
                        jnp.int8),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                comp = _words_of(tr)                             # [TS//4, W]
                # the last tile's rows past nvs are the scratch's garbage
                # tail: they land past the fill point, which _final_cb masks
                nvs = jnp.minimum(left - kk * TS, TS)
                nxt = _next_slot(cur, nb_cb)
                stage_w.store(cur, _merge_rows(comp, stage_w.load(cur), m_ph))
                cross = ph + nvs >= TS

                @pl.when(cross)
                def _flush_cb():
                    @pl.when(nf >= nb_cb - 1)
                    def _await_prev():
                        pltpu.make_async_copy(
                            stage.at[nxt],
                            rows_ref.at[pl.ds(pl.multiple_of(
                                d_al + (nf - (nb_cb - 1)) * TS, _ALIGN),
                                TS)],
                            sem_cb.at[nxt]).wait()
                    pltpu.make_async_copy(
                        stage.at[cur],
                        rows_ref.at[pl.ds(
                            pl.multiple_of(d_al + nf * TS, _ALIGN), TS)],
                        sem_cb.at[cur]).start()
                    # rows >= ph are the next trip's, or past the fill
                    stage_w.store(nxt, comp)

                return (fill + nvs, nf + jnp.where(cross, 1, 0),
                        jnp.where(cross, nxt, cur))

            def cb_chunk(c, carry):
                fill, nf, cur, slot = carry    # slot: chunk c's, c % NIN
                read_cb(c, slot).wait()

                @pl.when(c + NIN - 1 < ncc)
                def _prefetch_cb():
                    # chunk c + NIN - 1 lands where chunk c - 1 was
                    read_cb(c + NIN - 1,
                            jnp.where(slot == 0, NIN - 1, slot - 1)).start()

                # the last chunk is partial: ncbk tiles in all, not chunk //
                # TS of every chunk (past them is the scratch's garbage, and
                # a tile of it merged in would take the last tile's rows)
                k0 = c * (chunk // TS)
                fill, nf, cur = jax.lax.fori_loop(
                    0, jnp.minimum(chunk // TS, ncbk - k0),
                    functools.partial(cb_tile, slot, nr - k0 * TS),
                    (fill, nf, cur))
                return fill, nf, cur, _next_slot(slot, NIN)

            fill, nf, cur, _ = jax.lax.fori_loop(
                0, ncc, cb_chunk, (zero, zero, zero, zero))
            for j in range(1, nb_cb):
                @pl.when(nf - j >= 0)
                def _drain_cb(j=j):
                    idx = nf - j
                    sl = jax.lax.rem(idx, nb_cb)
                    pltpu.make_async_copy(
                        stage.at[sl],
                        rows_ref.at[pl.ds(pl.multiple_of(
                            d_al + idx * TS, _ALIGN), TS)],
                        sem_cb.at[sl]).wait()
            pend = ph + fill - nf * TS

            @pl.when(pend > 0)
            def _final_cb():
                src = pl.multiple_of(d_al + nf * TS, _ALIGN)
                cpa = pltpu.make_async_copy(rows_ref.at[pl.ds(src, TS)],
                                            tmp.at[0], sem_pre)
                cpa.start()
                cpa.wait()
                tmp_w.store(0, _merge_rows(
                    tmp_w.load(0), stage_w.load(cur),
                    _rows_from(wbase, pend)))
                cpb = pltpu.make_async_copy(tmp.at[0],
                                            rows_ref.at[pl.ds(src, TS)],
                                            sem_pre)
                cpb.start()
                cpb.wait()

        with jax.named_scope(_scopes.K_COPY_BACK):
            pl.when(nr > 0)(_copy_back)

    return kernel


def _make_small_partition_kernel(*, n_pad, W, num_features, num_bins, voff,
                                 bpc, packed, exact, f_shard=False,
                                 dbg_skip="", sc=SMALL_CHUNK, multiwin=False,
                                 quantized=False):
    """Round-7 small-window variant: the whole window fits ONE ``sc``-row
    chunk (dispatch bound: wc <= sc - _ALIGN), so the entire streaming
    apparatus disappears — no input ring, no flush rings, no deferred phase
    C, no scratch output, and crucially NO totals VMEM->SMEM round-trip: the
    per-subtile prefixes stay lane-resident and drive an in-register
    permutation ([sc, T] one-hot dots accumulated into one [sc, W] tile),
    the smaller child's histogram masks the same tile, and a single DMA
    writes the window back.  Two DMAs + ~3*nsub matmuls total per split —
    the fixed cost a sub-chunk deep-tree leaf actually pays.

    Phase A (extract/route/prefix) and the histogram accumulation reuse the
    pipelined kernel's building blocks verbatim, so results are bit-exact
    against the full kernel on the same window (pinned by
    tests/test_partition_buckets.py)."""
    del n_pad
    assert dbg_skip in ("", "hist"), \
        "the small-window kernel only supports the 'hist' knockout"
    nsub = sc // T
    npk = sc // _LANE

    def kernel(scal_ref, rows_in_ref, rows_ref, hist_ref, nl_ref,
               inbuf, outbuf, ltri, sem):
        del rows_in_ref
        scal = (_ScalRow(scal_ref, pl.program_id(0)) if multiwin
                else scal_ref)
        wb = scal[0]
        wc = scal[1]
        gcol = scal[2]
        hist_left = scal[9]
        wb_al = pl.multiple_of((wb // _ALIGN) * _ALIGN, _ALIGN)
        headL = wb - wb_al

        hist_ref[...] = jnp.zeros_like(hist_ref)
        nl_ref[...] = jnp.zeros_like(nl_ref)

        # empty windows (dead leaf-wise iterations, level-batched slots
        # whose window belongs to another bucket class) skip the read,
        # permutation and write-back entirely: the partition of an empty
        # window is the identity and its histogram is the zeros above, so
        # skipping is bit-exact AND makes the per-slot cost of a
        # class-mismatched window just the grid-step bookkeeping — which is
        # what lets a level launch carry every frontier slot in every class
        @pl.when(wc > 0)
        def _run_window():
            with jax.named_scope(_scopes.K_PLACE):
                ltri[...] = (jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
                             <= jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                             ).astype(jnp.int8)

                # one read covers the whole window (+ head slack); rows past the
                # window are carried through the identity permutation and written
                # back byte-identical, so the RMW is safe for the neighbour leaf
                cp = pltpu.make_async_copy(rows_ref.at[pl.ds(wb_al, sc)],
                                           inbuf, sem)
                cp.start()
                cp.wait()
                ti_i8 = jax.lax.bitcast_convert_type(inbuf[...], jnp.int8)

                # ---- phase A: shared extract/route/prefix, lane-resident ----
                col_p = _extract_col_lanes(ti_i8, gcol, W=W, bpc=bpc,
                                           packed=packed, npk=npk)
                gl_p = _route_tile(col_p, scal, num_bins)        # [npk, 128]
                pos_p = (wb_al
                         + jax.lax.broadcasted_iota(jnp.int32, (npk, 1), 0)
                         * _LANE
                         + jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1))
                inw_p = ((pos_p >= wb).astype(jnp.int32)
                         * (pos_p < wb + wc).astype(jnp.int32))
                selL_p = gl_p * inw_p
                selR_p = (1 - gl_p) * inw_p
                if T == _LANE:
                    S_L, S_R = selL_p, selR_p
                else:
                    S_L = selL_p.reshape(nsub, T)
                    S_R = selR_p.reshape(nsub, T)
                pfxU, _tot, incl_col, excl_col = _subtile_prefixes(S_L, S_R,
                                                                   ltri,
                                                                   nsub=nsub)
                nlv = incl_col[nsub - 1:nsub, 0:1].astype(jnp.int32)  # [1, 1]

                # ---- placement: window-global destinations, no staging ring --
                # dest is a permutation of [0, sc): left rows compact to
                # [headL, headL + nl), right rows to [headL + nl, headL + wc),
                # out-of-window rows keep their own position — one [sc, T]
                # one-hot dot per subtile accumulates the permuted tile (each
                # output row receives exactly one contribution)
                iota_sc = jax.lax.broadcasted_iota(jnp.int32, (sc, 1), 0)
                iota_lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                comp_i = jnp.zeros((sc, W), jnp.int32)
                for s in range(nsub):
                    selLs = S_L[s:s + 1, :]
                    selRs = S_R[s:s + 1, :]
                    pfxLs = pfxU[s:s + 1, :]
                    pfxRs = pfxU[nsub + s:nsub + s + 1, :]
                    bL = excl_col[s:s + 1, 0:1].astype(jnp.int32)
                    bR = excl_col[nsub + s:nsub + s + 1, 0:1].astype(jnp.int32)
                    destL = headL + bL + pfxLs - 1
                    destR = headL + nlv + bR + pfxRs - 1
                    own = s * T + iota_lane
                    dest = jnp.where(selLs == 1, destL,
                                     jnp.where(selRs == 1, destR, own))
                    Pt = (dest == iota_sc).astype(jnp.int8)          # [sc, T]
                    comp_i = comp_i + jax.lax.dot_general(
                        Pt, ti_i8[s * T:(s + 1) * T, :],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)            # [sc, W]
                outbuf[...] = (comp_i & 255).astype(jnp.uint8)

                # left count out via a plain VMEM write — no SMEM totals DMA
                # and no vector->scalar extraction anywhere in this variant
                nl_ref[0, 0:1, 0:1] = nlv

            # ---- smaller child's histogram from the SAME resident tile --
            if "hist" not in dbg_skip:
                with jax.named_scope(_scopes.K_HIST):
                    ti_c = outbuf[...].astype(jnp.int32)
                    start = jnp.where(hist_left == 1,
                                      jnp.full((1, 1), 1, jnp.int32) * headL,
                                      headL + nlv)
                    cnt = jnp.where(hist_left == 1, nlv, wc - nlv)
                    _hist_tile(ti_c, hist_ref, scal, start, cnt,
                               num_features=num_features, num_bins=num_bins,
                               bpc=bpc, packed=packed, exact=exact, voff=voff,
                               f_shard=f_shard, quantized=quantized)

            with jax.named_scope(_scopes.K_PLACE):
                # ---- single write-back DMA ----
                cpo = pltpu.make_async_copy(outbuf,
                                            rows_ref.at[pl.ds(wb_al, sc)],
                                            sem)
                cpo.start()
                cpo.wait()

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "num_features", "num_bins", "voff", "bpc", "packed", "exact", "interpret",
    "dbg_skip", "chunk", "small", "quantized"))
def partition_hist_pallas(rows: jax.Array, scal: jax.Array,
                          *, num_features: int,
                          num_bins: int, voff: int, bpc: int = 1,
                          packed: bool = False, exact: bool = False,
                          interpret: bool = False, dbg_skip: str = "",
                          chunk: int = CHUNK, small: bool = False,
                          quantized: bool = False):
    """Fused split pass over a combined row store.

    ``dbg_skip``: comma-joined phase knockouts for device profiling only
    ("hist", "phaseB", "phaseC", "flush", "convert", "extract", "prefix",
    "totals", "statslot") — outputs are WRONG when set ("prefix"/"totals"
    additionally zero the chunk fill counters, so even row counts lie).
    Knockout timings are scheduling-sensitive (zeroed inputs constant-fold
    downstream phases); trust whole-kernel A/B timings over deltas.  Since
    PR 40: "phaseB" leaves ``comp_buf``'s words unwritten, "phaseC" hands the
    streams' open tiles through unmerged, "flush" drops the block flushes,
    their waits and the drain's tail, "totals" / "prefix" leave the bank
    unshipped and phase C reads an empty one (slot 0, nothing completes).

    ``chunk``/``small`` (round 7): size-bucketed kernel variants.  ``chunk``
    sets the streamed tile height of the pipelined kernel (1024 or 4096 —
    must divide the module CHUNK padding contract); ``small=True`` selects
    the single-chunk small-window kernel, valid ONLY for windows with
    ``wc <= chunk - _ALIGN`` (the dispatch schedule from
    :func:`fused_bucket_plan` guarantees it; direct callers must too).
    Every variant is bit-exact against the others in interpret mode.

    rows: [N_pad, W] u8 row store, N_pad a multiple of CHUNK.  CONTRACT: the
      caller must keep every window end <= N_pad - CHUNK (the streaming loop
      reads and the copy-back RMW writes up to a CHUNK past the window end);
      the tree builder guarantees it by always padding a full spare CHUNK.
    scal: i32 [12 + num_bins//32] (+1 optional): (window_begin,
      window_count, group_col, threshold_bin, default_left, missing_type,
      num_bin_f, default_bin, is_cat, hist_left_side, use_unfold,
      efb_offset, *cat_bitset_words[, hist_feature_begin]).  The optional
      trailing element selects a feature WINDOW for the histogram
      ([f_begin, f_begin + num_features)) — feature-parallel shards build
      only their own block (feature_parallel_tree_learner.cpp:33-52);
      routing always uses the full store.  Requires the factored path.

    Returns (rows_new [N_pad, W] u8 — the window stably partitioned in place,
    hist_raw f32 — smaller child's histogram in the kernel's accumulator
    layout (factored [G*p*nlo, 128] or classic [4, f_pad*num_bins]; fold
    with :func:`fold_hist`), nl [1, 1] i32 — left-child row count).
    """
    return _partition_call(rows, scal, num_features=num_features,
                           num_bins=num_bins, voff=voff, bpc=bpc,
                           packed=packed, exact=exact, interpret=interpret,
                           dbg_skip=dbg_skip, chunk=chunk, small=small,
                           quantized=quantized)


# Left-child count output: one whole (8, 128) i32 tile per window, the count
# at [0, 0].  A (1, 1) block of an (nwin, 1) array is refused at lowering for
# nwin > 1 (last two block dims must be tile multiples or the array's).
_NL_TILE = (8, _LANE)
_NL_BLOCK = pl.BlockSpec((1,) + _NL_TILE, lambda g, s: (g, 0, 0))


def _partition_call(rows, scal, *, num_features, num_bins, voff, bpc,
                    packed, exact, interpret, dbg_skip, chunk, small,
                    quantized=False):
    """Shared pallas_call plumbing for the single-window
    (:func:`partition_hist_pallas`, ``scal`` 1-D) and multi-window
    (:func:`partition_hist_level_pallas`, ``scal`` [G, S]) launches: the
    window count is the grid, the per-window scalar row is selected by
    ``pl.program_id`` inside the kernel, and the hist/nl outputs are blocked
    per grid step.  A single window is exactly the G=1 blocking, so both
    entry points run the same kernels — which is what makes a level launch
    bit-exact against a sequence of per-split launches."""
    n_pad, W = rows.shape
    multiwin = scal.ndim == 2
    nwin = scal.shape[0] if multiwin else 1
    scal_width = scal.shape[-1]
    assert n_pad % CHUNK == 0, "pad the row store to a multiple of CHUNK"
    assert CHUNK % chunk == 0 and chunk % T == 0, \
        "bucketed chunk must divide the CHUNK padding contract"
    assert num_bins >= 32 and num_bins % 32 == 0, \
        "num_bins must be the >=32 kernel-block width (_pad_bins_pow2); " \
        "nibble-packed 16-bin data still scans at 32 lanes"
    f_shard = scal_width == 13 + num_bins // 32
    assert not (exact and quantized), \
        "hist_precision=quantized is incompatible with LIGHTGBM_TPU_EXACT_HIST"
    if _use_factored(num_features, num_bins, quantized):
        hist_shape = _factored_out_shape(num_features, num_bins, quantized)
    else:
        assert not f_shard, \
            "the histogram feature window needs the factored path"
        hist_shape = (2 if quantized else 4,
                      _padded_features(num_features, num_bins) * num_bins)
    h0, h1 = hist_shape
    # the kernel's name is what a profiler trace prints: one per size bucket
    # (``bucket_name``), the level-batched launches apart
    name = "partition_hist%s_pallas_%s" % ("_level" if multiwin else "",
                                           bucket_name(small, chunk))

    if small:
        kernel = _make_small_partition_kernel(
            n_pad=n_pad, W=W, num_features=num_features, num_bins=num_bins,
            voff=voff, bpc=bpc, packed=packed, exact=exact, f_shard=f_shard,
            dbg_skip=dbg_skip, sc=chunk, multiwin=multiwin,
            quantized=quantized)
        rows_new, hist, nl = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(nwin,),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),       # rows
                ],
                out_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),       # rows (aliased)
                    pl.BlockSpec((h0, h1), lambda g, s: (g, 0)),  # hist
                    _NL_BLOCK,                                # nl
                ],
                scratch_shapes=[
                    pltpu.VMEM((chunk, W), jnp.uint8),       # window tile in
                    pltpu.VMEM((chunk, W), jnp.uint8),       # permuted tile
                    pltpu.VMEM((T, T), jnp.int8),            # upper-tri ones
                    pltpu.SemaphoreType.DMA,                 # read/write-back
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((n_pad, W), jnp.uint8),
                jax.ShapeDtypeStruct((nwin * h0, h1), jnp.float32),
                jax.ShapeDtypeStruct((nwin,) + _NL_TILE, jnp.int32),
            ],
            input_output_aliases={1: 0},
            interpret=interpret,
            name=name,
        )(scal, rows)
        if multiwin:
            hist = hist.reshape(nwin, h0, h1)
        return rows_new, hist, nl[:, 0, 0:1]

    nb_ring = _ring_depth(chunk)
    nbk = nb_ring // _flush_run(chunk)
    totk = _totk(chunk)
    nsub = chunk // T
    kernel = _make_partition_kernel(
        n_pad=n_pad, W=W, num_features=num_features, num_bins=num_bins,
        voff=voff, bpc=bpc, packed=packed, exact=exact, f_shard=f_shard,
        dbg_skip=dbg_skip, chunk=chunk, multiwin=multiwin,
        quantized=quantized, interpret=interpret)
    # VMEM the kernel declares: the chunk ring, the placed words' banks, the
    # three tile rings and the histogram block (twice: it is pipelined out).
    # Twice that leaves the compiler its temporaries; at W = 128 it is under
    # the 16 MiB a kernel gets anyway, a table of more than 108 byte columns
    # (W = 256) needs the room asked for
    vmem = ((NIN * chunk + (totk + 1) * 2 * TS * nsub
             + (2 * nb_ring + _cb_depth(chunk)) * TS) * W + 2 * 4 * h0 * h1)
    rows_new, _scratch, hist, nl = pl.pallas_call(
        kernel,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, 2 * vmem)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nwin,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),       # rows
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),       # rows out (aliased)
                pl.BlockSpec(memory_space=pl.ANY),       # right-block scratch
                pl.BlockSpec((h0, h1), lambda g, s: (g, 0)),  # hist
                _NL_BLOCK,                               # nl
            ],
            scratch_shapes=[
                pltpu.VMEM((NIN, chunk, W), jnp.uint8),  # streamed chunk ring
                                                         # (window, hist, cb)
                pltpu.VMEM((1, nb_ring * TS, W), jnp.uint8),  # left flush ring
                pltpu.VMEM((1, nb_ring * TS, W), jnp.uint8),  # right flush ring
                pltpu.VMEM((_cb_depth(chunk), TS, W), jnp.uint8),  # copy-back's
                pltpu.VMEM((T, T), jnp.int8),            # upper-tri prefix ones
                pltpu.VMEM((TS, TS), jnp.int8),          # copy-back rotation
                pltpu.VMEM((1, TS, W), jnp.uint8),       # finals' RMW bounce
                pltpu.VMEM((totk + 1, 2 * _WPT * nsub, W),
                           jnp.int32),                   # placed words, banks
                pltpu.VMEM((2 * totk, _BANK_ROWS, _LANE), jnp.int32),  # bank
                pltpu.SMEM((2 * totk, _BANK_ROWS, _LANE), jnp.int32),  # lands
                pltpu.SemaphoreType.DMA((NIN,)),         # chunk ring reads
                pltpu.SemaphoreType.DMA,                 # prefills + finals
                pltpu.SemaphoreType.DMA((nbk,)),         # left flush blocks
                pltpu.SemaphoreType.DMA((nbk,)),         # right flush blocks
                pltpu.SemaphoreType.DMA((_cb_depth(chunk),)),  # copy-back ring
                pltpu.SemaphoreType.DMA((2,)),           # totals group banks
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, W), jnp.uint8),
            jax.ShapeDtypeStruct((n_pad, W), jnp.uint8),
            jax.ShapeDtypeStruct((nwin * h0, h1), jnp.float32),
            jax.ShapeDtypeStruct((nwin,) + _NL_TILE, jnp.int32),
        ],
        input_output_aliases={1: 0},
        interpret=interpret,
        name=name,
    )(scal, rows)
    if multiwin:
        hist = hist.reshape(nwin, h0, h1)
    return rows_new, hist, nl[:, 0, 0:1]


def level_plan(n: int) -> tuple:
    """Bucket-class schedule for LEVEL-batched dispatch (round 12): the same
    size-bucket ladder as :func:`fused_bucket_plan`, reused as the per-level
    class set.  A level's frontier windows are binned into these classes by
    row count and each class gets at most ONE multi-window launch per level
    (every frontier slot rides every class launch; out-of-class slots carry
    ``wc = 0`` and are skipped in-kernel), so a tree costs at most
    ``levels * len(level_plan(n))`` launches instead of one per split."""
    return fused_bucket_plan(n)


@functools.partial(jax.jit, static_argnames=(
    "num_features", "num_bins", "voff", "bpc", "packed", "exact", "interpret",
    "chunk", "small", "quantized"))
def partition_hist_level_pallas(rows: jax.Array, scals: jax.Array,
                                *, num_features: int, num_bins: int,
                                voff: int, bpc: int = 1,
                                packed: bool = False, exact: bool = False,
                                interpret: bool = False,
                                chunk: int = CHUNK, small: bool = False,
                                quantized: bool = False):
    """Multi-window fused split pass: ONE Pallas launch partitions + child-
    histograms every window of ``scals`` ([G, S] — one
    :func:`partition_hist_pallas` scalar row per window, same layout).

    Windows must be pairwise disjoint (distinct leaves of one tree level
    are, by construction); each is processed by its own grid step of the
    SAME kernel the single-window entry point runs, so outputs are bit-exact
    against G sequential single-window launches (pinned by
    tests/test_partition_buckets.py).  Windows with ``wc = 0`` are skipped
    in-kernel (identity partition, zero histogram) — the level dispatcher
    masks out-of-class windows to 0 instead of compacting, keeping the grid
    size trace-static.

    Returns (rows_new [N_pad, W] u8, hist_raw [G, ...] f32 — per-window
    smaller-child histograms in the kernel accumulator layout (fold each
    with :func:`fold_hist`), nl [G, 1] i32 left-child counts)."""
    return _partition_call(rows, scals, num_features=num_features,
                           num_bins=num_bins, voff=voff, bpc=bpc,
                           packed=packed, exact=exact, interpret=interpret,
                           dbg_skip="", chunk=chunk, small=small,
                           quantized=quantized)


def fold_hist(hist_raw: jax.Array, num_features: int,
              num_bins: int, quantized: bool = False) -> jax.Array:
    """Kernel histogram accumulator -> [F, 2, B] f32 (factored or classic
    layout, matching partition_hist_pallas's choice)."""
    if _use_factored(num_features, num_bins, quantized):
        return _fold_factored(hist_raw, num_features, num_bins, quantized)
    f_pad = _padded_features(num_features, num_bins)
    folded = hist_raw[0:2] if quantized else hist_raw[0:2] + hist_raw[2:4]
    return folded.reshape(2, f_pad, num_bins).transpose(1, 0, 2)[:num_features]


def partition_hist_xla(rows: jax.Array, scal, *,
                       num_features: int, num_bins: int, voff: int,
                       bpc: int = 1, packed: bool = False):
    """Reference implementation of the kernel's contract in plain XLA ops
    (full-array mask + cumsum + scatter).  Used by tests and as the
    documentation of the output semantics; the production non-TPU path stays
    on the bucketed-switch builder."""
    assert num_bins >= 32 and num_bins % 32 == 0, \
        "num_bins must be the >=32 kernel-block width (_pad_bins_pow2)"
    n, W = rows.shape
    wb, wc, gcol, thr, dleft, mt, nb, dbin, is_cat, hist_left, use_unfold, \
        eoff = [scal[i] for i in range(12)]
    bitset_words = scal[None, 12:12 + num_bins // 32]
    ri = rows.astype(jnp.int32)
    if packed:
        byte = jnp.take_along_axis(
            ri, jnp.full((n, 1), gcol // 2, jnp.int32), axis=1)[:, 0]
        col = jnp.where(gcol % 2 == 1, (byte >> 4) & 15, byte & 15)
    elif bpc == 2:
        lo = jnp.take_along_axis(ri, jnp.full((n, 1), 2 * gcol, jnp.int32),
                                 axis=1)[:, 0]
        hi = jnp.take_along_axis(ri, jnp.full((n, 1), 2 * gcol + 1,
                                              jnp.int32), axis=1)[:, 0]
        col = lo | (hi << 8)
    else:
        col = jnp.take_along_axis(ri, jnp.full((n, 1), gcol, jnp.int32),
                                  axis=1)[:, 0]
    unfolded = jnp.where((col >= eoff) & (col <= eoff + nb - 2),
                         col - eoff + 1, 0)
    col = jnp.where(use_unfold == 1, unfolded, col)
    is_missing = jnp.where(mt == 1, col == nb - 1,
                           jnp.where(mt == 2, col == dbin, False))
    num_left = jnp.where(is_missing, dleft == 1, col <= thr)
    word = bitset_words[0][jnp.clip(col >> 5, 0, bitset_words.shape[1] - 1)]
    cat_left = ((word.astype(jnp.uint32)
                 >> (col & 31).astype(jnp.uint32)) & 1) == 1
    gl = jnp.where(is_cat == 1, cat_left, num_left)

    iota = jnp.arange(n, dtype=jnp.int32)
    inw = (iota >= wb) & (iota < wb + wc)
    selL = gl & inw
    selR = (~gl) & inw
    nl = jnp.sum(selL, dtype=jnp.int32)
    cl = jnp.cumsum(selL, dtype=jnp.int32)
    cr = jnp.cumsum(selR, dtype=jnp.int32)
    dest = jnp.where(selL, wb + cl - 1,
                     jnp.where(selR, wb + nl + cr - 1, iota))
    rows_new = jnp.zeros_like(rows).at[dest].set(rows, unique_indices=True)

    side = jnp.where(hist_left == 1, selL, selR)
    bins, values = rows_split_xla(rows, num_features, voff, bpc, packed)
    hist = histogram_xla_masked(bins, values * side.astype(jnp.float32)[None],
                                num_bins, jnp.int32(0), jnp.int32(n))
    return rows_new, hist, nl
