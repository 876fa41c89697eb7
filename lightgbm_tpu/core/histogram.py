"""Feature-histogram construction — the hottest op (SURVEY.md §3.1).

Counterpart of the reference's histogram kernels: the CPU ``Bin::ConstructHistogram``
family (src/io/dense_bin.hpp:48, src/io/dataset.cpp:1265,1370) and the OpenCL
``histogram256`` kernels (src/treelearner/ocl/histogram256.cl:317).

TPU-first design: TPUs have no fast scatter-add, so instead of per-workgroup local
histograms with float atomics (histogram256.cl:100-130) the histogram is computed as
a one-hot contraction on the MXU.  Filling the systolic array is everything:

- The left operand carries FOUR rows — (grad_hi, hess_hi, grad_lo, hess_lo) — a
  bf16 hi/lo split of the f32 values.  bf16 one-hot entries are exact, products
  accumulate in f32, and hi + lo recovers ~f32 precision (relative error ~2^-16),
  all in a SINGLE MXU pass instead of the 6-pass f32 emulation.
- The right operand packs ``128 // num_bins`` features per 128-lane output tile
  (their one-hots OR'd into disjoint lane ranges), so a 64-bin dataset computes
  two features per contraction and a 4-bit-packed (16→32-bin) dataset four —
  the lane dimension is fully used instead of 2/128.  The same role the
  reference's GPU learner plays with its 4-features-per-DWORD packing
  (gpu_tree_learner.cpp:317-344).

Accumulation order is fixed by the sequential TPU grid, so results are
deterministic (unlike the reference GPU path's atomic adds).

Two channels per bin — (sum_grad, sum_hess) — matching the reference's 16-byte
histogram entry (bin.h:41 ``HistogramSumReducer``); bin counts are derived from
hessians downstream exactly like feature_histogram.hpp:535 ``cnt_factor``.

Per-leaf windows ride scalar prefetch: the window (start, count) is prefetched
into SMEM and drives the input index_map, so row tiles fully outside the leaf's
window skip both the HBM fetch and the compute — cost scales with the leaf's
row count, not the slice size (the reference's ordered-index histograms,
dense_bin.hpp:48 ConstructHistogram over ``data_indices`` begin..end).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import scopes as _scopes
from ..plan import device_specs as _device_specs
from ..plan import state as _plan_state

_LANE = 128


def _exact_hist() -> bool:
    """Parity-debugging escape hatch: accumulate histograms with f32 HIGHEST
    contractions instead of the bf16 hi/lo split (~2^-16 relative error).
    Roughly 2x slower; flip when chasing near-tie split divergences vs the
    reference's double-precision accumulation."""
    return os.environ.get("LIGHTGBM_TPU_EXACT_HIST", "0") == "1"


def _pad_bins(num_bins: int) -> int:
    """Lane-padded width for per-feature threshold scans (VPU)."""
    return max(_LANE, -(-num_bins // _LANE) * _LANE)


def _pad_bins_pow2(num_bins: int) -> int:
    """Histogram-kernel bin width: next power of two, min 32 (so bitset words
    and feature packing stay well-formed).  Small widths let several features
    share one 128-lane MXU output tile."""
    b = 32
    while b < num_bins:
        b *= 2
    return b


def histogram_xla(bins: jax.Array, values: jax.Array, num_bins: int) -> jax.Array:
    """Reference implementation via segment-sum; runs on any backend.

    bins: [N, F] integer; values: [2, N] f32 (grad, hess; pre-masked,
    channel-major so lanes run along rows on TPU).
    Returns [F, 2, num_bins] f32.
    """
    n, f = bins.shape
    ids = bins.astype(jnp.int32) + jnp.arange(f, dtype=jnp.int32)[None, :] * num_bins
    vals = jnp.broadcast_to(values.T[:, None, :], (n, f, 2)).reshape(n * f, 2)
    hist = jax.ops.segment_sum(vals, ids.reshape(-1), num_segments=f * num_bins)
    return hist.reshape(f, num_bins, 2).transpose(0, 2, 1)


def _features_per_tile(num_bins: int) -> int:
    return max(1, _LANE // num_bins)


def _padded_features(num_features: int, num_bins: int) -> int:
    fp = _features_per_tile(num_bins)
    return -(-num_features // fp) * fp


def _hilo_split(vals, axis, exact: bool = False, quantized: bool = False):
    """f32 -> (hi, lo) bf16 concatenated on ``axis``: bf16 products against a
    0/1 one-hot are exact and hi+lo recovers ~f32 precision (relative error
    ~2^-16) in a single MXU pass instead of the 6-pass f32 emulation.

    ``exact``: keep f32 and pad with zeros (the contraction then runs at
    HIGHEST precision — see :func:`_exact_hist`).

    ``quantized`` (round 22): the values are already small integers
    (core/quant.py stochastic rounding, |v| <= 255) — exact in bf16, so the
    lo rows and the hi+lo fold disappear: the operand keeps its 2 rows and
    the MXU pass runs at HALF the rows of the hi/lo split."""
    if quantized:
        return vals.astype(jnp.bfloat16)
    if exact:
        return jnp.concatenate([vals, jnp.zeros_like(vals)], axis=axis)
    hi = vals.astype(jnp.bfloat16)
    lo = (vals - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, lo], axis=axis)


def _accum_onehot_tiles(col, v4, out_ref, *, num_features: int,
                        num_bins: int, contract_dim: int):
    """The shared MXU tile loop: build each 128-lane one-hot tile (packing
    ``128 // num_bins`` features per tile, or splitting one feature over
    ``num_bins // 128`` tiles) and accumulate the [4, 128] contraction of the
    (grad_hi, hess_hi, grad_lo, hess_lo) operand ``v4``."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    B = num_bins
    fp = _features_per_tile(B)
    tpf = max(1, B // _LANE)                 # lane tiles per feature (B > 128)
    num_tiles = out_ref.shape[1] // _LANE
    for t in range(num_tiles):
        if B >= _LANE:
            oh = (col(t // tpf) - (t % tpf) * _LANE) == iota
        else:
            oh = None
            for j in range(fp):
                f = t * fp + j
                if f >= num_features:
                    break
                m = (col(f) + j * B) == iota
                oh = m if oh is None else oh | m
        exact = v4.dtype == jnp.float32
        acc = jax.lax.dot_general(
            v4, oh.astype(v4.dtype), (((contract_dim,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST if exact else None)  # [4, 128]
        out_ref[:, t * _LANE:(t + 1) * _LANE] += acc


def _accum_onehot_tile_dyn(colf_dyn, v4, out_ref, t, *, num_features: int,
                           num_bins: int, contract_dim: int):
    """One 128-lane tile's classic one-hot contraction with a TRACED tile
    index ``t`` — grid-over-tiles / fori-over-tiles building block of the
    classic packed-tile histogram (wide-F shapes past the factored path's
    4 MiB accumulator bound unrolled hundreds of tiles here and blew the
    compile; program size is now O(1) in F).

    colf_dyn(f) -> per-row bin code of feature f (traced f; [Nt, 1] for
    contract_dim=0, [1, Nt] lane-major for contract_dim=1)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    B = num_bins
    fp = _features_per_tile(B)
    tpf = max(1, B // _LANE)
    if B >= _LANE:
        oh = (colf_dyn(t // tpf) - jax.lax.rem(t, tpf) * _LANE) == iota
    else:
        oh = None
        for j in range(fp):
            f = t * fp + j
            m = ((colf_dyn(f) + j * B) == iota) & (f < num_features)
            oh = m if oh is None else oh | m
    exact = v4.dtype == jnp.float32
    acc = jax.lax.dot_general(
        v4, oh.astype(v4.dtype), (((contract_dim,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if exact else None)  # [4, 128]
    off = pl.multiple_of(t * _LANE, _LANE)
    out_ref[:, pl.ds(off, _LANE)] += acc


def _colf_rows_dyn(w, *, bpc: int, packed: bool):
    """Dynamic-index bin-code extraction from an [Nt, W] row-store tile:
    a weighted lane reduction (single-lane masks are Mosaic-safe where the
    shifted-slice OR chain is not, see _f32_from_bytes) so the feature index
    may be traced.

    ``w`` may be i32 or bf16 (byte values 0..255 are exact in bf16; the
    classic grid kernel stages its tile as bf16 to halve the VMEM scratch
    at the wide-W shapes this path exists for) — the single-nonzero lane
    reduction is exact either way, and integer bit math happens on the
    reduced [Nt, 1] column."""
    W = w.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    floaty = w.dtype != jnp.int32

    def pick(col_idx):
        if floaty:
            m = (lanes == col_idx).astype(w.dtype)
            return jnp.sum(w * m, axis=1, keepdims=True).astype(jnp.int32)
        return jnp.sum(w * (lanes == col_idx), axis=1, keepdims=True)

    def colf(f):
        if packed:
            return (pick(f // 2) >> (4 * jax.lax.rem(f, 2))) & 15
        if bpc == 2:
            return pick(2 * f) | (pick(2 * f + 1) << 8)
        return pick(f)

    return colf


def _accum_onehot_all(colf_dyn, v4, out_ref, *, num_features: int,
                      num_bins: int, contract_dim: int):
    """Rolled fori_loop over every 128-lane tile (fused-kernel classic
    path; the standalone kernel puts tiles on the grid)."""
    num_tiles = out_ref.shape[1] // _LANE

    def body(t, _):
        _accum_onehot_tile_dyn(colf_dyn, v4, out_ref, t,
                               num_features=num_features, num_bins=num_bins,
                               contract_dim=contract_dim)
        return 0

    jax.lax.fori_loop(0, num_tiles, body, 0)


def _hist_kernel_mxu(win_ref, bins_ref, vals_ref, out_ref, *,
                     num_features: int, num_bins: int, row_tile: int,
                     packed: bool, exact: bool = False):
    """One row tile's contribution to the histogram of rows in
    [win[0], win[0]+win[1]).  out_ref: [4, F_pad * num_bins] f32 — rows are
    (grad_hi, hess_hi, grad_lo, hess_lo); the caller folds hi+lo."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    start, count = win_ref[0], win_ref[1]
    base = i * row_tile

    @pl.when((base < start + count) & (base + row_tile > start))
    def _accum():
        rows = base + jax.lax.broadcasted_iota(jnp.int32, (1, row_tile), 1)
        in_w = ((rows >= start) & (rows < start + count)).astype(jnp.float32)
        v4 = _hilo_split(vals_ref[...] * in_w, axis=0, exact=exact)  # [4, Nt]
        bins = bins_ref[...].astype(jnp.int32)

        def col(f):
            if packed:
                return (bins[:, f // 2:f // 2 + 1] >> (4 * (f % 2))) & 15
            return bins[:, f:f + 1]

        _accum_onehot_tiles(col, v4, out_ref, num_features=num_features,
                            num_bins=num_bins, contract_dim=1)


@functools.partial(jax.jit, static_argnames=("num_bins", "row_tile",
                                             "num_cols", "interpret", "exact"))
def histogram_pallas_masked(bins: jax.Array, values: jax.Array, num_bins: int,
                            start: jax.Array, count: jax.Array,
                            row_tile: int = 2048, num_cols: int = 0,
                            interpret: bool = False,
                            exact: bool = False) -> jax.Array:
    """Histogram over rows [start, start+count) of a (bucket-sized) slice.

    bins: [R, F] int (or [R, ceil(F/2)] nibble-packed when ``num_cols`` = F);
    values: [2, R] f32 channel-major (NOT pre-masked); start/count: i32
    scalars relative to the slice.  R must be a multiple of row_tile.
    Returns [F, 2, num_bins]."""
    n, width = bins.shape
    f = num_cols or width
    assert n % row_tile == 0, "pad rows to a multiple of row_tile"
    assert _LANE % num_bins == 0 or num_bins % _LANE == 0, (
        "num_bins must divide or be a multiple of 128 (use _pad_bins_pow2); "
        "got %d" % num_bins)
    f_pad = _padded_features(f, num_bins)
    lanes = f_pad * num_bins
    win = jnp.stack([start.astype(jnp.int32), count.astype(jnp.int32)])
    kernel = functools.partial(_hist_kernel_mxu, num_features=f,
                               num_bins=num_bins, row_tile=row_tile,
                               packed=bool(num_cols), exact=exact)

    def _in_idx(i, win_ref):
        # tiles outside the window revisit block 0: Mosaic elides the re-fetch
        active = ((i * row_tile < win_ref[0] + win_ref[1])
                  & ((i + 1) * row_tile > win_ref[0]))
        return (jnp.where(active, i, 0), 0)

    def _vals_idx(i, win_ref):
        active = ((i * row_tile < win_ref[0] + win_ref[1])
                  & ((i + 1) * row_tile > win_ref[0]))
        return (0, jnp.where(active, i, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // row_tile,),
        in_specs=[
            pl.BlockSpec((row_tile, width), _in_idx),
            pl.BlockSpec((2, row_tile), _vals_idx),
        ],
        out_specs=pl.BlockSpec((4, lanes), lambda i, w: (0, 0)),
    )
    raw = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((4, lanes), jnp.float32),
        interpret=interpret,
        name="histogram_pallas_masked",
    )(win, bins, values)
    folded = raw[0:2] + raw[2:4]
    return folded.reshape(2, f_pad, num_bins).transpose(1, 0, 2)[:f]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_tile", "interpret",
                                    "exact"))
def histogram_pallas(bins: jax.Array, values: jax.Array, num_bins: int,
                     row_tile: int = 2048, interpret: bool = False,
                     exact: bool = False) -> jax.Array:
    """Pallas TPU histogram over ALL rows (values pre-masked).

    bins: [N, F] int (any small int dtype); values: [2, N] f32 channel-major.
    Returns [F, 2, num_bins] f32.  N must be a multiple of row_tile (pad with
    zero-valued rows)."""
    n = bins.shape[0]
    return histogram_pallas_masked(bins, values, num_bins, jnp.int32(0),
                                   jnp.int32(n), row_tile=row_tile,
                                   interpret=interpret, exact=exact)


def _hilo_factors(num_bins: int):
    """num_bins = nhi * nlo (both powers of two, nlo <= 32): the bin index
    factors as ``bin = hi * nlo + lo``, so a B-lane one-hot becomes the outer
    product of an nhi-lane and an nlo-lane one-hot — built with nhi + nlo
    compares per (row, feature) instead of B, with the outer product riding
    the histogram contraction itself on the MXU (see _accum_factored_group)."""
    nlo = 1
    while nlo * nlo < num_bins:
        nlo *= 2
    nlo = min(nlo, 32)
    return num_bins // nlo, nlo


def _hist_channels(quantized: bool = False) -> int:
    """Value rows per histogram operand: 4 for the bf16 hi/lo split
    (grad_hi, hess_hi, grad_lo, hess_lo — also the exact-f32 layout, zero
    padded), 2 for quantized integer gradients (no lo rows)."""
    return 2 if quantized else 4


def _factored_geometry(num_features: int, num_bins: int,
                       quantized: bool = False):
    """(p, G): features per MXU group and group count.  A group's two
    operands are p features' value-weighted hi one-hots stacked as
    [p*nch*nhi = 128, R] (nch = 4, or 2 quantized — the integer operand
    packs TWICE the features per group) and their lo one-hots [p*nlo, R];
    the contraction over R puts the LO rows on the result's sublanes and the
    128 weighted-hi rows on its lanes (see _accum_factored_group)."""
    nhi, _ = _hilo_factors(num_bins)
    p = max(1, _LANE // (_hist_channels(quantized) * nhi))
    return p, -(-num_features // p)


# bin columns one extraction dot pulls out of a tile: one i32 sublane tile,
# so the [8, R] codes and their hi / lo parts cost what ONE row of them did
_BLOCK_ROWS = 8


def _group_block(num_features: int, num_bins: int,
                 quantized: bool = False):
    """(k, blocks): feature groups a BLOCK and the block count.  A block is
    the groups whose k * p <= 8 bin columns one extraction dot makes (four
    groups at 256 bins, two at 64 bins or quantized, one where p = 8: the
    nibble-packed 32-lane geometry) — a constant number of groups, so the
    program stays O(p) in F; every kernel runs the blocks in a rolled loop
    (_accum_factored_all)."""
    p, G = _factored_geometry(num_features, num_bins, quantized)
    k = max(1, min(_BLOCK_ROWS // p, G))
    return k, -(-G // k)


def _use_factored(num_features: int, num_bins: int,
                  quantized: bool = False) -> bool:
    """Factored vs classic packed-tile histogram.

    The classic one-hot costs ~2.5 VPU lane-ops per (row, feature, bin) —
    ruinous for wide F x large B (F=968, B=256: ~620k lane-ops per row).
    The factored path costs nhi + nlo compares + a 4*nhi-lane weighting per
    (row, feature) plus a p x p all-pairs MXU block per feature group (only
    the diagonal is read) — per-feature cost near-independent of B, so it
    wins essentially everywhere the accumulator fits on-chip.  The bound
    below caps the [G*p*nlo, 128] f32 accumulator at the device's
    accumulator budget — a quarter of VMEM, 4 MiB on the 16 MiB v5e
    (``plan/device_specs.py``, round 18: previously a literal here) — so
    it fits alongside the partition kernel's ~5 MiB of round-6 pipelined
    streaming scratch (NIN=3 input ring + double-banked placement tiles).
    The accumulator's minor dimension is a whole 128 lanes, so the product
    below is the bytes VMEM really holds: the [G*128, p*nlo] layout before
    PR 37 had the same element count and was lane-padded to four times it
    at 256 bins (917 KB at F = 28, where this counts 229).

    A PINNED kernel plan (``plan/state.py``, tests and the autotuner)
    overrides the choice outright; the layout is baked into compiled
    programs, so the override is engage-time-only by contract — never
    flipped under a live jit cache."""
    override = _plan_state.hist_layout_override(num_features, num_bins)
    if override is not None:
        return override
    if num_bins < 32:
        return False
    out = _factored_out_shape(num_features, num_bins, quantized)
    # budget keyed by the ATTACHED device (memoized probe) so the gate
    # agrees with the budget analytic_plan records into Plan/artifacts.
    # Quantized accumulators have HALF the rows, so twice the feature
    # width passes the same budget (round 22).
    budget = _device_specs.hist_accum_budget_bytes(
        _device_specs.current_device_kind())
    return out[0] * out[1] * 4 <= budget


def _accum_factored_group(hi, lo, v32, out_ref, g, *, oh_t, num_bins: int):
    """ONE feature group's factored-MXU histogram accumulation, with the
    group index ``g`` a TRACED scalar.

    hi, lo: [p, R] i32 — the group's bin codes split as ``bin = hi * nlo +
    lo``, ``lo`` at -1 (no one-hot row) for a feature past the last
    (:func:`_accum_factored_block` makes both for a block of groups at
    once); v32: [nch, R] f32, the value rows of :func:`_extract_values_T`
    widened once a block; ``oh_t``: the operands' type (bf16; f32 in exact
    mode); out_ref: [G*p*nlo, p*nch*nhi = 128] f32 — the group's
    [p*nlo, 128] block is += accumulated at a dynamic sublane offset.

    The one-hot build costs nhi + nlo compares per (row, feature) —
    near-independent of B.  The weighted hi one-hot is a SELECT of the value
    row on the compare's own mask (v5e has no bf16 ALU: a bf16 multiply is
    two unpacks, an f32 multiply and a pack; the select makes the same
    operand, x * 1 and x * 0 of a finite x, to the sign of a zero), and the
    contraction ``lo_big[p*nlo, R] . a_big[128, R]^T`` is lane-dense: a
    128-row K-chunk pops p*nlo / 8 = 4 result vregs of 128 useful lanes,
    where ``a_big . lo_big^T`` popped 16 of 32.  Its p x p feature
    cross-blocks are discarded except the diagonal (see _fold_factored)."""
    nhi, nlo = _hilo_factors(num_bins)
    p, nch = hi.shape[0], v32.shape[0]
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (nhi, 1), 0)
    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (nlo, 1), 0)
    a_blocks = []
    lo_blocks = []
    for q in range(p):
        hi_mask = hi[q:q + 1, :] == iota_hi                   # [nhi, R]
        for c in range(nch):
            a_blocks.append(jnp.where(hi_mask, v32[c:c + 1, :], 0.0))
        lo_blocks.append(jnp.where(lo[q:q + 1, :] == iota_lo, 1.0, 0.0))
    # stacked in f32, where a block is whole (8, 128) tiles, and packed to
    # the operands' type once: blocks packed one by one are laid out again
    # by the concatenation (an unpack and a pack a vreg)
    a_big = jnp.concatenate(a_blocks, axis=0).astype(oh_t)   # [128, R]
    lo_big = jnp.concatenate(lo_blocks, axis=0).astype(oh_t)  # [p*nlo, R]
    acc = jax.lax.dot_general(
        lo_big, a_big, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if oh_t == jnp.float32
                   else None))                             # [p*nlo, 128]
    rows = p * nlo
    off = pl.multiple_of(g * rows, rows)
    out_ref[pl.ds(off, rows), :] += acc


def _accum_factored_block(ti_bf, v4T, out_ref, gb, *, num_features: int,
                          num_bins: int, bpc: int, packed: bool, f_base=0,
                          quantized: bool = False):
    """One BLOCK of k feature groups (:func:`_group_block`), the block index
    ``gb`` a TRACED scalar — the body of the rolled loop every kernel runs
    (the standalone one a row tile, the fused ones a chunk).  The round-5
    layout unrolled a Python loop over all G groups (and an extraction
    matrix with one row per FEATURE), which at wide F (Bosch F=968) blew
    Mosaic compiles past 10 minutes; here program size is O(p) regardless
    of F.

    ti_bf: [R, W] bf16 row-store tile (byte values exact in bf16); v4T:
    [nch, R] (grad_hi, hess_hi, grad_lo, hess_lo) from
    :func:`_extract_values_T`.

    ONE ``E[k*p, W] @ ti_bf^T`` dot pulls the block's k * p bin codes out
    of the tile (the tile crosses the MXU once a block; a dot a group
    pushed all of it for p = 2 columns), every table kind a row a feature:
    a two-byte code is both its bytes weighted 1 and 256 in E, a nibble-
    packed one its byte, shifted out below.  The selection rows ride a
    broadcasted iota compared against the traced first feature, so the dot
    serves any F.  Groups past G in the last block are skipped."""
    _, nlo = _hilo_factors(num_bins)
    p, G = _factored_geometry(num_features, num_bins, quantized)
    k, blocks = _group_block(num_features, num_bins, quantized)
    W = ti_bf.shape[1]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    r = jax.lax.broadcasted_iota(jnp.int32, (k * p, 1), 0)
    g0 = gb * k
    f = f_base + g0 * p + r               # each row's absolute feature
    if packed:
        # callers keep f_base even and p is even for every packed geometry
        # (p = 32 // nhi, nhi <= 8 at the 32-lane packed block), so a
        # feature's nibble parity is its row's
        E = (iota_w == f // 2).astype(jnp.bfloat16)
    elif bpc == 2:
        E = ((iota_w == 2 * f).astype(jnp.bfloat16)
             + (iota_w == 2 * f + 1).astype(jnp.bfloat16) * 256)
    else:
        E = (iota_w == f).astype(jnp.bfloat16)              # [k*p, W]
    codes = jax.lax.dot_general(
        E, ti_bf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)   # [k*p, R]
    if packed:
        codes = (codes >> (4 * jax.lax.rem(r, 2))) & 15
    # num_features is the histogrammed WINDOW's width (f_base is the
    # absolute byte offset of its first feature), so validity is local: the
    # last group's tail features get lo = -1, which matches no iota row, so
    # their (diagonal) blocks accumulate zeros.  The mask rides the i32 bin
    # codes: Mosaic has no select on i1 vectors, so masking the boolean
    # one-hots themselves does not legalize on the chip.
    hi = codes >> (nlo.bit_length() - 1)
    lo = jnp.where(g0 * p + r < num_features, codes & (nlo - 1), -1)
    v32 = v4T.astype(jnp.float32)
    for j in range(k):
        def _group(j=j):
            _accum_factored_group(hi[j * p:(j + 1) * p], lo[j * p:(j + 1) * p],
                                  v32, out_ref, g0 + j, oh_t=v4T.dtype,
                                  num_bins=num_bins)
        if j < G - (blocks - 1) * k:      # in every block, the last too
            _group()
        else:
            pl.when(g0 + j < G)(_group)


def _accum_factored_all(ti_bf, v4T, out_ref, *, num_features: int,
                        num_bins: int, bpc: int, packed: bool, f_base=0,
                        quantized: bool = False):
    """Rolled loop over every block of feature groups: the fused partition
    kernels' in-kernel histogram of a chunk, the standalone kernel's of a
    row tile."""
    _, blocks = _group_block(num_features, num_bins, quantized)

    def body(gb, _):
        _accum_factored_block(ti_bf, v4T, out_ref, gb,
                              num_features=num_features, num_bins=num_bins,
                              bpc=bpc, packed=packed, f_base=f_base,
                              quantized=quantized)
        return 0

    jax.lax.fori_loop(0, blocks, body, 0)


def _fold_factored(raw, num_features: int, num_bins: int,
                   quantized: bool = False):
    """[G*p*nlo, p*nch*nhi] factored accumulator -> [F, 2, B] f32 (grad =
    hi + lo value channels, hess likewise; bin = hi * nlo + lo: the
    accumulator holds a group as (feature, lo) x (feature, channel, hi), of
    which the feature diagonal is the histogram).  Quantized accumulators
    already carry exactly the 2 (grad, hess) integer channels — no fold,
    just the diagonal gather."""
    nhi, nlo = _hilo_factors(num_bins)
    p, G = _factored_geometry(num_features, num_bins, quantized)
    nch = _hist_channels(quantized)
    d = raw.reshape(G, p, nlo, p, nch, nhi)
    idx = jnp.arange(p)
    diag = d[:, idx, :, idx, :, :]          # [p, G, nlo, nch, nhi]
    h = diag.transpose(1, 0, 3, 4, 2).reshape(G * p, nch, nhi * nlo)
    h = h[:num_features]
    if quantized:
        return h
    return h[:, 0:2, :] + h[:, 2:4, :]


def _factored_out_shape(num_features: int, num_bins: int,
                        quantized: bool = False):
    nhi, nlo = _hilo_factors(num_bins)
    p, G = _factored_geometry(num_features, num_bins, quantized)
    return (G * p * nlo, p * _hist_channels(quantized) * nhi)


def _extract_values_T(ti_bf, *, voff: int, exact: bool, inwT=None,
                      quantized: bool = False):
    """Transposed g/h extraction from a [R, W] bf16 row-store tile: ONE
    [4, W] @ [R, W]^T dot pulls the four 16-bit halves, the f32s are rebuilt
    via i32 OR (the wrap restores the sign bit; the OBVIOUS shifted-slice OR
    chain is miscompiled on v5e — see _f32_from_bytes), and the hi/lo bf16
    split makes the v4T operand of :func:`_accum_factored_block`.

    The bin extraction is the block step's (dynamic block index); values
    are extracted ONCE per tile and reused by every group.  Keeping every
    per-row intermediate LANE-major ([k, R]) matters as much as the dot:
    sliced [R, 1] intermediates are 128x vreg-padded."""
    W = ti_bf.shape[1]
    f32 = jnp.float32
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    rows = [(iota_w == off) * 1 + (iota_w == off + 1) * 256
            for off in (voff, voff + 2, voff + 4, voff + 6)]
    E = jnp.concatenate(rows, axis=0).astype(jnp.bfloat16)   # [4, W]
    allTi = jax.lax.dot_general(
        E, ti_bf, (((1,), (1,)), ((), ())),
        preferred_element_type=f32).astype(jnp.int32)        # [4, R]
    g_w = jax.lax.bitcast_convert_type(
        allTi[0:1, :] | (allTi[1:2, :] << 16), f32)
    h_w = jax.lax.bitcast_convert_type(
        allTi[2:3, :] | (allTi[3:4, :] << 16), f32)
    if inwT is not None:
        # select, never multiply: the fused kernel's right-block pass reads
        # its last chunk past the rows it wrote into the scratch buffer, and
        # what the chip left there can be NaN/Inf bit patterns (NaN * 0 is
        # NaN, and one such row poisons every bin through the contraction)
        g_w = jnp.where(inwT > 0, g_w, 0.0)
        h_w = jnp.where(inwT > 0, h_w, 0.0)
    if quantized:
        # integer-valued f32 (core/quant.py, |v| <= 255): exact in bf16,
        # no lo rows — the 2-row operand of the halved MXU pass
        return jnp.concatenate([g_w, h_w], axis=0).astype(jnp.bfloat16)
    if exact:
        return jnp.concatenate(
            [g_w, h_w, jnp.zeros_like(g_w), jnp.zeros_like(h_w)], axis=0)
    g_hi = g_w.astype(jnp.bfloat16)
    h_hi = h_w.astype(jnp.bfloat16)
    g_lo = (g_w - g_hi.astype(f32)).astype(jnp.bfloat16)
    h_lo = (h_w - h_hi.astype(f32)).astype(jnp.bfloat16)
    return jnp.concatenate([g_hi, h_hi, g_lo, h_lo], axis=0)


def _f32_from_bytes(ti, off: int):
    """Little-endian f32 from 4 byte-lanes of an i32-converted row tile.

    Implemented as ONE weighted lane reduction (weights 1, 2^8, 2^16, 2^24;
    i32 wrap-around reproduces the high byte's sign bit exactly since the four
    terms have disjoint bits).  The obvious form — OR-ing four shifted
    single-lane slices — is MISCOMPILED by Mosaic on real TPUs (intermittent
    zeroed bytes per row; verified on v5e, and the cause of a silent ~28%
    histogram mass loss in the round-3 kernel).  Single-lane slices alone are
    fine; the fused shift/OR chain is not.  Do not "simplify" this back.
    """
    w = ti.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    weight = ((lanes == off) * 1 + (lanes == off + 1) * (1 << 8)
              + (lanes == off + 2) * (1 << 16)
              + (lanes == off + 3) * (1 << 24))
    word = jnp.sum(ti * weight, axis=1, keepdims=True)
    return jax.lax.bitcast_convert_type(word, jnp.float32)


def _hist_kernel_rows(win_ref, rows_ref, out_ref, w_sc, v4_sc, *,
                      num_features: int, num_bins: int, row_tile: int,
                      packed: bool, voff: int, bpc: int,
                      exact: bool = False, quantized: bool = False):
    """Combined-row-store histogram, classic packed tiles, GRID over lane
    tiles: grid = (row tiles, output tiles).  ``rows`` is [Nt, W] u8 with
    bin codes in bytes [0, num_cols*bpc), grad/hess f32 little-endian at
    byte offsets voff/voff+4.  One operand means the partitioned tree
    builder carries ONE unpadded byte matrix (128-lane rows) instead of
    separate bins/values arrays whose small-minor-dim layouts XLA pads
    4-64x.

    The tile index is pl.program_id(1) — program size is O(1) in F, which is
    what lets wide-F x 256-bin shapes (Bosch past the factored 4 MiB gate)
    compile in minutes instead of not at all.  The i32 tile and the hi/lo
    value operand are computed once per row tile (at t == 0) into VMEM
    scratch and reused by every output tile."""
    i = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when((i == 0) & (t == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    start, count = win_ref[0], win_ref[1]
    base = i * row_tile
    active = (base < start + count) & (base + row_tile > start)

    def _stage_tile():
        w = rows_ref[...].astype(jnp.int32)              # [Nt, W]
        # bf16 staging: byte values are exact in bf16 and the scratch is
        # half the i32 footprint — at the wide-W shapes this kernel exists
        # for (F=968 x 256 bins: W=1024) an i32 stage alone would be 8 MiB
        # of the ~16 MiB VMEM
        w_sc[...] = w.astype(jnp.bfloat16)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (row_tile, 1), 0)
        in_w = (pos >= start) & (pos < start + count)
        zero = jnp.float32(0.0)
        g = jnp.where(in_w, _f32_from_bytes(w, voff), zero)
        h = jnp.where(in_w, _f32_from_bytes(w, voff + 4), zero)
        vals = jnp.concatenate([g, h], axis=1)           # [Nt, 2] f32
        v4_sc[...] = _hilo_split(vals, axis=1, exact=exact,
                                 quantized=quantized)    # [Nt, 4|2]

    def _accum():
        # the feature window (win_ref[2]) is only supported on the factored
        # path; the learner only shards histogram construction when the
        # sharded width passes _use_factored, else it falls back to a
        # replicated build with a sharded scan
        colf = _colf_rows_dyn(w_sc[...], bpc=bpc, packed=packed)
        _accum_onehot_tile_dyn(colf, v4_sc[...], out_ref, t,
                               num_features=num_features,
                               num_bins=num_bins, contract_dim=0)

    # regions a grid step (obs/scopes.py KERNEL_REGIONS): the grid is the
    # kernel's only loop over rows, so a region is a row tile's
    with jax.named_scope(_scopes.K_STAGE):
        pl.when(active & (t == 0))(_stage_tile)
    with jax.named_scope(_scopes.K_GROUPS):
        pl.when(active)(_accum)


def _hist_kernel_rows_fac(win_ref, rows_ref, out_ref, *,
                          num_features: int, num_bins: int, row_tile: int,
                          packed: bool, voff: int, bpc: int,
                          exact: bool = False, quantized: bool = False):
    """Factored-MXU variant of _hist_kernel_rows, grid = (row tiles,): a
    step makes the tile's bf16 copy and its v4T value operand once and
    runs every block of feature groups over them in the fused kernels'
    rolled loop (see _accum_factored_all).  The blocks are not a grid axis
    because a grid step costs beyond its bundles: one window on the chip
    at F = 28 reads 2.328 ns a row with them there, 2.216 so (PERF.md §5).
    out_ref: [G*p*nlo, 128] f32 — fold with _fold_factored.  win_ref[2] is
    the feature-window base (feature-parallel shards)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    start, count = win_ref[0], win_ref[1]
    base = i * row_tile

    @pl.when((base < start + count) & (base + row_tile > start))
    def _accum():
        with jax.named_scope(_scopes.K_STAGE):
            tib = rows_ref[...].astype(jnp.int32).astype(jnp.bfloat16)
            posT = base + jax.lax.broadcasted_iota(jnp.int32,
                                                   (1, row_tile), 1)
            inwT = ((posT >= start).astype(jnp.float32)
                    * (posT < start + count).astype(jnp.float32))
            v4T = _extract_values_T(tib, voff=voff, exact=exact, inwT=inwT,
                                    quantized=quantized)
        with jax.named_scope(_scopes.K_GROUPS):
            _accum_factored_all(tib, v4T, out_ref,
                                num_features=num_features,
                                num_bins=num_bins, bpc=bpc, packed=packed,
                                f_base=win_ref[2], quantized=quantized)


@functools.partial(jax.jit, static_argnames=("num_features", "num_bins",
                                             "voff", "bpc", "row_tile",
                                             "packed", "interpret", "exact",
                                             "quantized"))
def histogram_pallas_rows(rows: jax.Array, num_bins: int, start: jax.Array,
                          count: jax.Array, *, num_features: int, voff: int,
                          bpc: int = 1, packed: bool = False,
                          row_tile: int = 2048,
                          interpret: bool = False,
                          exact: bool = False,
                          quantized: bool = False,
                          f_begin=0) -> jax.Array:
    """Histogram over rows [start, start+count) of a combined row store.

    rows: [R, W] u8 — bins bytes + f32 grad/hess at voff/voff+4 (see
    _hist_kernel_rows).  ``f_begin``/``num_features`` select the feature
    window (feature-parallel shards histogram only their own block).
    Returns [num_features, 2, num_bins] f32."""
    n, width = rows.shape
    assert n % row_tile == 0, "pad rows to a multiple of row_tile"
    assert _LANE % num_bins == 0 or num_bins % _LANE == 0, (
        "num_bins must divide or be a multiple of 128 (use _pad_bins_pow2); "
        "got %d" % num_bins)
    assert not (exact and quantized), \
        "hist_precision=quantized is incompatible with LIGHTGBM_TPU_EXACT_HIST"
    # a feature window is only honored by the factored kernel; the classic
    # fallback would silently histogram columns [0, F) mislabeled as the
    # window, so reject the combination here rather than in a distant caller
    assert _use_factored(num_features, num_bins, quantized) or (
        isinstance(f_begin, int) and f_begin == 0), \
        "f_begin needs the factored histogram path"
    win = jnp.stack([start.astype(jnp.int32), count.astype(jnp.int32),
                     jnp.asarray(f_begin, jnp.int32)])
    nch = _hist_channels(quantized)
    v4_dtype = jnp.float32 if exact else jnp.bfloat16

    def _in_idx(i, *rest):
        # tiles outside the window revisit block 0 (Mosaic elides the
        # re-fetch); the classic path's lane-tile grid axis never moves the
        # input block
        win_ref = rest[-1]
        active = ((i * row_tile < win_ref[0] + win_ref[1])
                  & ((i + 1) * row_tile > win_ref[0]))
        return (jnp.where(active, i, 0), 0)

    if _use_factored(num_features, num_bins, quantized):
        out_shape = _factored_out_shape(num_features, num_bins, quantized)
        kernel = functools.partial(
            _hist_kernel_rows_fac, num_features=num_features,
            num_bins=num_bins, row_tile=row_tile, packed=packed, voff=voff,
            bpc=bpc, exact=exact, quantized=quantized)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // row_tile,),
            in_specs=[pl.BlockSpec((row_tile, width), _in_idx)],
            out_specs=pl.BlockSpec(out_shape, lambda i, w: (0, 0)),
        )
        raw = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
            interpret=interpret,
            name="histogram_pallas_rows_factored",
        )(win, rows)
        return _fold_factored(raw, num_features, num_bins, quantized)

    # classic path: in practice only wide-F shapes land here (kernel bin
    # widths are padded to >= 32, so every narrow-F accumulator passes the
    # factored 4 MiB gate); at wide W keep the VMEM budget sane by
    # shrinking the row tile (input block + bf16 stage scale with both)
    if width > 512:
        while row_tile > 1024 and n % (row_tile // 2) == 0:
            row_tile //= 2
    f_pad = _padded_features(num_features, num_bins)
    lanes = f_pad * num_bins
    kernel = functools.partial(_hist_kernel_rows, num_features=num_features,
                               num_bins=num_bins, row_tile=row_tile,
                               packed=packed, voff=voff, bpc=bpc,
                               exact=exact, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // row_tile, lanes // _LANE),
        in_specs=[pl.BlockSpec((row_tile, width), _in_idx)],
        out_specs=pl.BlockSpec((nch, lanes), lambda i, t, w: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((row_tile, width), jnp.bfloat16),      # staged tile
            pltpu.VMEM((row_tile, nch), v4_dtype),            # hi/lo values
        ],
    )
    raw = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nch, lanes), jnp.float32),
        interpret=interpret,
        name="histogram_pallas_rows_classic",
    )(win, rows)
    folded = raw[0:2] if quantized else raw[0:2] + raw[2:4]
    return folded.reshape(2, f_pad, num_bins).transpose(1, 0, 2)[:num_features]


def _f32_col(w, off):
    """Little-endian f32 from 4 byte columns of an i32-converted store
    (XLA-side; the Mosaic slice-OR miscompile is kernel-specific)."""
    word = (w[:, off] | (w[:, off + 1] << 8) | (w[:, off + 2] << 16)
            | (w[:, off + 3] << 24))
    return jax.lax.bitcast_convert_type(word, jnp.float32)


def rows_split_xla(rows: jax.Array, num_features: int, voff: int,
                   bpc: int = 1, packed: bool = False):
    """Backend-agnostic unpack of a combined row store ->
    (bins [N, F], values [2, N])."""
    w = rows.astype(jnp.int32)
    if packed:
        bins = unpack_nibbles(rows[:, :(num_features + 1) // 2], num_features)
    elif bpc == 2:
        bins = w[:, 0:2 * num_features:2] | (w[:, 1:2 * num_features:2] << 8)
    else:
        bins = rows[:, :num_features]
    values = jnp.stack([_f32_col(w, voff), _f32_col(w, voff + 4)], axis=0)
    return bins, values


def histogram_rows(rows: jax.Array, num_bins: int, start, count, *,
                   num_features: int, voff: int, bpc: int = 1,
                   packed: bool = False,
                   use_pallas: bool | None = None,
                   f_begin=0, interpret: bool = False,
                   quantized: bool = False) -> jax.Array:
    """Masked histogram over a combined row store; Pallas on TPU.

    ``f_begin``: feature-window base (may be traced) — feature-parallel
    shards histogram only columns [f_begin, f_begin + num_features).
    ``interpret``: run the Pallas path in interpret mode (CPU tests of the
    fused builder).
    ``quantized``: the stored grad/hess are integer-valued (core/quant.py)
    — the Pallas kernels run the 2-row integer operand; the XLA fallback
    needs no change (an f32 segment-sum of small integers is exact), so
    both return the same exact integer sums."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        if rows.shape[0] % 2048:
            raise ValueError(
                "the Pallas row-store histogram needs rows padded to a "
                "multiple of 2048 (the learner pads them); got %d"
                % rows.shape[0])
        return histogram_pallas_rows(rows, num_bins, start, count,
                                     num_features=num_features, voff=voff,
                                     bpc=bpc, packed=packed,
                                     exact=_exact_hist(), f_begin=f_begin,
                                     quantized=quantized,
                                     interpret=interpret)
    if isinstance(f_begin, int) and f_begin == 0:
        bins, values = rows_split_xla(rows, num_features, voff, bpc, packed)
        return histogram_xla_masked(bins, values, num_bins, start, count)
    # windowed XLA fallback: bins via a dynamic column slice, g/h from the
    # fixed value columns
    assert not packed, "feature windows are not used with nibble packing"
    w = rows.astype(jnp.int32)
    if bpc == 2:
        sl = jax.lax.dynamic_slice_in_dim(
            w, 2 * f_begin, 2 * num_features, axis=1)
        bins = sl[:, 0::2] | (sl[:, 1::2] << 8)
    else:
        bins = jax.lax.dynamic_slice_in_dim(w, f_begin, num_features, axis=1)
    values = jnp.stack([_f32_col(w, voff), _f32_col(w, voff + 4)], axis=0)
    return histogram_xla_masked(bins, values, num_bins, start, count)


def unpack_nibbles(packed: jax.Array, num_cols: int) -> jax.Array:
    """[N, ceil(C/2)] nibble-packed u8 -> [N, C] bin codes."""
    lo = packed & 15
    hi = (packed >> 4) & 15
    out = jnp.stack([lo, hi], axis=2).reshape(packed.shape[0], -1)
    return out[:, :num_cols]


def pack_nibbles(bins) -> "np.ndarray":
    """Host: [N, C] codes (< 16) -> [N, ceil(C/2)] nibble-packed u8."""
    import numpy as np
    bins = np.asarray(bins, dtype=np.uint8)
    n, c = bins.shape
    if c % 2:
        bins = np.concatenate([bins, np.zeros((n, 1), np.uint8)], axis=1)
    return (bins[:, 0::2] | (bins[:, 1::2] << 4)).astype(np.uint8)


def histogram_xla_masked(bins: jax.Array, values: jax.Array, num_bins: int,
                         start: jax.Array, count: jax.Array,
                         num_cols: int = 0) -> jax.Array:
    """Backend-agnostic masked histogram over a slice (full scan)."""
    if num_cols:
        bins = unpack_nibbles(bins, num_cols)
    pos = jnp.arange(bins.shape[0], dtype=jnp.int32)
    in_w = ((pos >= start) & (pos < start + count)).astype(values.dtype)
    return histogram_xla(bins, values * in_w[None, :], num_bins)


def partition_buckets(n: int, row_tile: int = 2048) -> tuple:
    """Static window-slice sizes (rows): geometric in row_tile, plus n.

    Per-split partition/histogram cost scales with the BUCKET covering the
    window, so tighter spacing buys back the slack (2x spacing: <=2x the
    window; 4x spacing averaged ~2.5x) at the price of more compiled switch
    branches.  Small datasets (tests, CPU) use 4x spacing — there the cost is
    compile time, not slack."""
    spacing = 2 if n > (1 << 17) else 4
    sizes = []
    b = row_tile
    while b < n:
        sizes.append(b)
        b *= spacing
    sizes.append(n)
    return tuple(sizes)
