"""Leaf-wise (best-first) tree growth as a single compiled XLA program.

Counterpart of the reference ``SerialTreeLearner`` (src/treelearner/
serial_tree_learner.cpp:150-197): per split — pick the leaf with the best cached
split, perform it, build the smaller child's histogram, derive the larger child by
subtraction (:347-356 histogram trick), and cache both children's best splits.

TPU-first departures from the reference:
- The whole tree builds inside one ``jax.lax.fori_loop`` — no host round-trips
  between splits.  All shapes are static: leaf-state arrays are sized
  ``num_leaves``, rows carry a ``row_leaf`` assignment instead of the reference's
  ``DataPartition`` index lists (data_partition.hpp:20-237), and early stopping is
  a sticky ``cont`` flag (the reference ``break`` at serial_tree_learner.cpp:176).
- Histograms are built by masking grad/hess with leaf membership and scanning all
  rows (static shapes) rather than gathering per-leaf indices; the subtraction
  trick halves that cost exactly as in the reference.
- Routing rows through a split uses the binned comparison semantics of
  ``Tree::NumericalDecisionInner`` (tree.h:262-277): missing-typed bins follow the
  stored default direction.

The builder returns flat tree arrays which ``host_tree`` converts into a
:class:`lightgbm_tpu.core.tree.Tree` (bin thresholds -> real-valued thresholds via
the BinMappers, like Dataset::RealThreshold).
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .histogram import (histogram_rows, pack_nibbles,
                        partition_buckets, _exact_hist, _pad_bins,
                        _pad_bins_pow2, _use_factored)
from .partition import (CHUNK as _PCHUNK, fold_hist, fused_bucket_plan,
                        partition_hist_level_pallas, partition_hist_pallas)
from .quant import quantize_gradients
from .row_state import advance_row_state, f32_col, i32_col, leaf_windows
from .split import (BestSplit, FeatureInfo, SplitParams, best_split_numerical,
                    cat_scan_steps, dequantize_hist, group_best, group_lanes, group_scans,
                    per_feature_best, per_feature_best_combined,
                    reduce_feature_best, sync_best, K_MIN_SCORE)
from .tree import Tree
from ..io.binning import BinType, MissingType
from ..io.dataset import BinnedDataset
from ..obs import categorical as _cat_counters
from ..obs import efb as _efb_counters
from ..obs import scopes as _scopes
from ..obs.spans import span as _span


class Comm(NamedTuple):
    """Static collective-communication strategy for multi-chip tree growth.

    Replaces the reference's ``Network`` singleton calls (SURVEY.md §2.3) with
    XLA collectives inside the compiled tree build; every parallel learner
    composes over :func:`build_tree_partitioned` (``comm_mode`` below), the
    same way the reference composes its parallel learners over the serial
    base via templates (tree_learner.cpp:24-33):

    - ``rs``: rows sharded; ``psum_scatter`` shards the *global* histogram
      over features so each chip scans only F/d features, then an
      allreduce-argmax of the per-shard bests — the exact comm structure of
      ``DataParallelTreeLearner`` (data_parallel_tree_learner.cpp:149-240).
    - ``psum``: rows sharded; full-histogram allreduce per split.
    - ``feature``: rows replicated; each shard BUILDS histograms only for
      its own F/d features (feature_parallel_tree_learner.cpp:33-52 — the
      dominant cost) and scans them; only the tiny best-split allreduce
      crosses chips.  The row store still keeps every routable column on
      every chip (rows are replicated, partitioning is identical
      everywhere), unlike the reference's vertical column shards.  Wide-F
      configurations where the TPU kernel's factored histogram cannot take
      a dynamic feature window fall back to a replicated build with a
      sharded scan.
    - ``voting``: rows sharded; per-shard top-k feature election + global
      vote, then psum of only the elected features' histograms
      (voting_parallel_tree_learner.cpp:170-366).
    """
    axis_name: str = ""
    mode: str = "serial"
    num_shards: int = 1
    top_k: int = 20


class TreeArrays(NamedTuple):
    """Flat on-device tree (L = num_leaves budget; node i valid for i < num_leaves-1)."""
    split_feature: jax.Array    # [L] i32, inner feature index
    threshold_bin: jax.Array    # [L] i32
    split_gain: jax.Array       # [L] f32
    default_left: jax.Array     # [L] bool
    left_child: jax.Array       # [L] i32 (~leaf encoding)
    right_child: jax.Array      # [L] i32
    internal_value: jax.Array   # [L] f32
    internal_weight: jax.Array  # [L] f32
    internal_count: jax.Array   # [L] f32
    leaf_value: jax.Array       # [L] f32
    leaf_weight: jax.Array      # [L] f32
    leaf_count: jax.Array       # [L] f32
    leaf_parent: jax.Array      # [L] i32
    leaf_depth: jax.Array       # [L] i32
    cat_bitset: jax.Array       # [L, B//32] u32 left-bin sets (categorical)
    num_leaves: jax.Array       # scalar i32
    row_leaf: jax.Array         # [N] i32 final leaf of every row


def _bests_update(bests: BestSplit, idx, new: BestSplit) -> BestSplit:
    return BestSplit(*[f.at[idx].set(n) for f, n in zip(bests, new)])


def _unfold_bin(col, f_id, feat: FeatureInfo):
    """EFB group code -> feature bin: codes [off, off+nb-2] hold bins
    1..nb-1, anything else means the feature sits at bin 0 (its default).
    Singleton groups use offset 1, making this the identity."""
    if feat.offset is None:
        return col
    off = feat.offset[f_id]
    nb = feat.num_bin[f_id]
    return jnp.where((col >= off) & (col <= off + nb - 2), col - off + 1, 0)


def _feature_column(f_id, feat: FeatureInfo):
    """The binned-matrix column holding feature f (its group's column)."""
    return f_id if feat.group is None else feat.group[f_id]


def _route_left(col, threshold, default_left, mt, nb, dbin,
                is_cat=None, bitset=None):
    """Decision on binned values: NumericalDecisionInner (tree.h:262-277) or,
    for categorical splits, membership of the bin in the left bitset
    (tree.h:283-331 CategoricalDecisionInner; the NaN bin is never a member,
    so missing goes right)."""
    is_missing = jnp.where(mt == int(MissingType.NAN), col == nb - 1,
                           jnp.where(mt == int(MissingType.ZERO), col == dbin,
                                     False))
    num_left = jnp.where(is_missing, default_left, col <= threshold)
    if is_cat is None:
        return num_left
    if bitset.ndim == 1:          # one bitset for all rows (tree build)
        word = bitset[col >> 5]
    else:                         # per-row bitsets (routing through many nodes)
        word = jnp.take_along_axis(bitset, (col >> 5)[:, None], axis=1)[:, 0]
    cat_left = ((word >> (col & 31).astype(jnp.uint32)) & 1) == 1
    return jnp.where(is_cat, cat_left, num_left)


class _PState(NamedTuple):
    tree: TreeArrays
    hist: jax.Array             # [L, F, 2, B]
    bests: BestSplit            # arrays [L]
    cont: jax.Array             # scalar bool
    cmin: jax.Array             # [L] monotone lower bounds
    cmax: jax.Array             # [L] upper bounds
    begin: jax.Array            # [L] i32 window start (physical, partitioned)
    wcount: jax.Array           # [L] i32 window length (physical rows)
    rows: jax.Array             # [N, W] u8 combined row store (leaf-
                                # partitioned): bin bytes + f32 grad/hess +
                                # s32 original-row order per row, W a
                                # multiple of 128.
    # Physically partitioned copies beat gather-by-index: window slices and
    # write-backs are contiguous DMAs at full HBM bandwidth while row gathers
    # cost ~5 ns/row in DMA descriptors (measured 4.7 ms vs 0.1 ms on a 512k
    # window).  One unpadded byte matrix instead of separate bins/values/
    # order carries: XLA lane-padded the small-minor-dim layouts 4-64x,
    # which made its per-split buffer unification copies dominate.
    lsum_g: jax.Array           # [L] leaf gradient totals (forced splits)
    lsum_h: jax.Array           # [L] leaf hessian totals
    feat_used: jax.Array        # [F] bool: feature split somewhere (CEGB)
    force_on: jax.Array         # scalar bool: forced schedule still aligned
    fbc: object                 # FeatureBest arrays [L, F] — per-(leaf,
                                # feature) cached candidates for the CEGB
                                # coupled refund (() when CEGB is off)
    slot_of: jax.Array          # [L] i32 histogram-pool slot per leaf, -1 =
                                # evicted (() when the pool is unbounded)
    stamps: jax.Array           # [K] i32 LRU stamps per pool slot (())


def _ffill_nonzero(x: jax.Array) -> jax.Array:
    """Forward-fill zeros with the last nonzero value (log-doubling)."""
    n = x.shape[0]
    shift = 1
    while shift < n:
        shifted = jnp.concatenate([jnp.zeros((shift,), x.dtype), x[:-shift]])
        x = jnp.where(x > 0, x, shifted)
        shift *= 2
    return x


def group_search_applies(grouped: bool, has_categorical: bool,
                         has_cegb: bool) -> bool:
    """Whether a learner's split search runs on the group histogram's own
    lanes: decided by what the table and the configuration are."""
    return grouped and not has_categorical and not has_cegb


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "max_depth", "params", "num_bins",
                     "use_pallas", "has_categorical", "has_monotone",
                     "feat_num_bins", "packed_cols", "axis_name",
                     "comm_mode", "num_shards", "carried", "top_k",
                     "hist_pool_slots", "bucket_plan", "pallas_interpret",
                     "tree_grow_mode", "hist_precision", "grad_fn"))
def build_tree_partitioned(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                           num_data: jax.Array, feature_mask: jax.Array,
                           feat: FeatureInfo, *, num_leaves: int,
                           max_depth: int, params: SplitParams, num_bins: int,
                           use_pallas: bool = False,
                           has_categorical: bool = False,
                           has_monotone: bool = False,
                           feat_num_bins: int = 0,
                           unpack_lanes=None,
                           forced=None, cegb=None, paid_bits=None,
                           packed_cols: int = 0,
                           axis_name: str = "",
                           comm_mode: str = "psum",
                           num_shards: int = 1,
                           carried: bool = False,
                           top_k: int = 20,
                           hist_pool_slots: int = 0,
                           bucket_plan=None,
                           pallas_interpret: bool = False,
                           tree_grow_mode: str = "leaf",
                           hist_precision: str = "exact",
                           quant_it=None, quant_seed=0,
                           rows_carry=None, extra=None, score_rate=None,
                           grad_fn=None, root_sums=None):
    """Leaf-wise growth with per-leaf physical row partitions.

    The TPU counterpart of the reference's ``DataPartition``
    (data_partition.hpp:20-237): rows are kept physically grouped by leaf in a
    working copy of the binned matrix, every split stable-partitions only the
    parent leaf's window (a bucketed dynamic slice, so cost scales with the
    window), and the smaller child's histogram streams only its own rows
    (serial_tree_learner.cpp:347-356 subtraction trick for the sibling).
    Split semantics identical to the reference's serial leaf-wise growth;
    per-split histogram/partition cost scales with the split leaf's window
    rather than the full data.  With ``axis_name`` set this runs under
    ``jax.shard_map`` with rows sharded: each shard partitions its own rows
    (windows are shard-local), child histograms are ``psum``'d into global
    histograms — the data-parallel comm structure of
    data_parallel_tree_learner.cpp with the partitioned builder's per-leaf
    cost.  The histogrammed side is chosen by the replicated estimated counts
    (serial_tree_learner.cpp:347-356), so every shard streams the same child.

    ``forced``: optional (leaf_ids [S], features [S], threshold_bins [S]) BFS
    schedule of forced splits (serial_tree_learner.cpp:458 ForceSplits) — the
    first S splits are taken at those positions when valid, stats gathered at
    the given threshold; growth then continues best-first.
    ``bucket_plan``: trace-static fused-kernel dispatch schedule (round 7;
    see :func:`lightgbm_tpu.core.partition.fused_bucket_plan`) — sub-chunk
    leaf windows select the single-chunk small-window kernel and mid windows
    a 1024-row-chunk pipeline instead of padding every split to the
    4096-row floor; ``None`` derives the schedule from the row count.
    ``pallas_interpret`` runs every Pallas kernel in interpret mode so the
    fused path (incl. this dispatch) is testable off-TPU.
    ``tree_grow_mode`` (round 12): ``"leaf"`` (default) is the reference's
    best-first growth — one fused split launch per grown leaf, L-1 launches
    per tree.  ``"level"`` replays a ``max_depth``-driven BFS: each level's
    whole frontier is split by at most ONE multi-window Pallas launch per
    bucket class (:func:`lightgbm_tpu.core.partition.level_plan`), so a
    depth-D tree costs <= D * len(plan) launches.  Frontier leaves are
    processed in ascending leaf-id order; when the ``num_leaves`` budget
    cannot cover a whole frontier, the lowest leaf ids win (with
    ``max_depth <= 0`` the level schedule defaults to ceil(log2(L)) levels
    — a complete tree exactly fills the leaf budget).  Level mode requires
    the fused Pallas path and is incompatible with forced splits, CEGB,
    histogram pooling and sharded growth (asserted at trace time).
    ``cegb``: optional (penalty_split [scalar], coupled [F], used0 [F]) cost
    penalties (cost_effective_gradient_boosting.hpp:50-61 DetlaGain):
    candidate gains lose tradeoff*penalty_split*num_data_in_leaf plus the
    coupled per-feature penalty until the feature's first use.  Unlike the
    reference — which refunds cached candidate gains of other leaves when a
    feature becomes used (:63-79 UpdateLeafBestSplits) — cached leaf bests
    here keep their original penalty until the leaf is re-evaluated.
    ``carried``: the store also holds each row's aux value and running score
    (``extra`` when it is constructed, from ``grad``/``hess`` in original row
    order; a ``num_leaves=1`` build with no ``grad_fn`` does only that and
    returns ``(tree, rows)``).  A build handed ``rows_carry`` takes its
    gradients from the store and their totals from ``root_sums``, and ends
    with the hand-over pass (core/row_state.py): scores take the tree's leaf
    values times ``score_rate`` and ``grad_fn(score, aux, order, quant_it +
    1)`` (``quant_it``: this tree's boosting iteration) writes the next
    tree's gradients; it returns ``(tree, rows, root_sums)``
    for the next build.
    """
    if pallas_interpret and jax.default_backend() == "tpu":
        # with a chip attached the override would quietly swap the compiled
        # kernels for the interpreter
        raise RuntimeError(
            "pallas_interpret (LIGHTGBM_TPU_PALLAS_INTERPRET=1) on a tpu "
            "backend: interpret mode is for hosts without a chip")
    n, ncols = bins.shape
    f = feat.num_bin.shape[0]          # features may outnumber group columns
    L = num_leaves
    B = feat_num_bins or num_bins      # per-feature scan width
    f32 = jnp.float32
    buckets = partition_buckets(n)
    bsizes = jnp.asarray(buckets, dtype=jnp.int32)

    # ---- combined row store ----
    # One [N, W] u8 matrix carries bin bytes + f32 grad/hess + the s32 row
    # order, W a multiple of 128 so the {1,0:T(8,128)(4,1)} layout has NO
    # lane padding: every slice/permute/write-back of partition state moves
    # exactly the stored bytes.  Separate bins/values/order carries got
    # 4-64x lane-padded layouts, which turned XLA's per-split buffer
    # unification copies into the dominant cost of the whole tree build.
    bpc = 2 if bins.dtype == jnp.uint16 else 1
    f_cols = packed_cols or ncols      # histogrammed bin columns
    nbytes_bins = ncols * bpc
    voff = -(-nbytes_bins // 4) * 4
    # CEGB lazy penalties track which rows already paid each feature's cost
    # (feature_used_in_data_, cost_effective_gradient_boosting.hpp:47): one
    # bit per (row, feature), carried as extra bytes IN the row store so the
    # partition moves them for free
    lazy_on = cegb is not None and cegb[3] is not None
    assert not (carried and lazy_on), \
        "carried row-store training and lazy CEGB are mutually exclusive"
    # carried mode appends two f32 columns after the order: the objective's
    # per-row aux value (voff + 12) and the running score (voff + 16) — the
    # whole boosting state then rides the partition permutation and no per-row
    # gather/scatter is needed between iterations (see
    # ObjectiveFunction.carry_aux; core/row_state.py advances it per tree)
    bitoff = voff + (20 if carried else 12)
    bitbytes = -(-f // 8) if lazy_on else 0
    W = -(-(bitoff + bitbytes) // 128) * 128
    # The fused Pallas split pass (partition_hist_pallas) replaces the
    # bucketed-switch partition on TPU: window contract requires a spare
    # CHUNK of rows past every window end, appended with valid unique
    # order bytes so the final row_leaf reconstruction scatter stays 1:1.
    if use_pallas and n % _PCHUNK:
        raise ValueError(
            "use_pallas needs the row count padded to a multiple of %d "
            "(SerialTreeLearner pads it); got %d" % (_PCHUNK, n))
    fused = use_pallas and not lazy_on
    # ---- round 22: quantized-gradient training (hist_precision) ----
    # Stochastically round grad/hess to small integers BEFORE the row-store
    # byte pack, so every histogram consumer — the standalone row kernels,
    # the fused split kernels' phase B, and the XLA fallback — reads
    # integer-valued f32 automatically.  The rounding offset is a stateless
    # hash of (iteration, ORIGINAL row id, seed): the same determinism
    # contract as the bagging mask, so checkpoint resume and fused
    # chunk-boundary replay see bit-identical integers, and a contiguously
    # row-sharded build (global ids + pmax'd scales) quantizes the exact
    # serial stream.
    quantized = hist_precision == "quantized"
    if hist_precision not in ("exact", "quantized"):
        raise ValueError("unknown hist_precision %r" % (hist_precision,))
    qscale = None
    if quantized:
        it_q = (jnp.asarray(quant_it, jnp.int32) if quant_it is not None
                else jnp.int32(0))
        if rows_carry is not None:
            # carried mode: the store holds the real-valued gradients the
            # last pass wrote, in the PERMUTED row order; key the stream by
            # the original ids riding the store's order bytes
            grad = f32_col(rows_carry[:n], voff)
            hess = f32_col(rows_carry[:n], voff + 4)
            rid = i32_col(rows_carry[:n], voff + 8)
        else:
            rid = jnp.arange(n, dtype=jnp.int32)
        if axis_name and comm_mode != "feature":
            # contiguous row sharding: shard s holds global rows
            # [s*n, (s+1)*n); feature mode replicates rows, so local ids
            # ARE global there
            rid = rid + jax.lax.axis_index(axis_name) * n
        grad, hess, qscale = quantize_gradients(
            grad, hess, rid, it_q, quant_seed,
            axis_name=axis_name if comm_mode != "feature" else "")
    # The program's phases as named scopes: metadata only (the jaxpr's
    # equations are the same with and without them), but they survive into
    # the compiled HLO's op_name, which is how a profiler trace's
    # compiler-made instruction names are read back as tree.store / root /
    # pick_leaf / split / find_split / state_update / finish
    # (obs/scopes.py).
    with jax.named_scope("tree.store"):
        if rows_carry is not None:
            # the whole boosting state already lives (permuted) in the store,
            # this tree's gradients included: the last tree's hand-over pass
            # (core/row_state.py) wrote them
            n_arr = n + (_PCHUNK if fused else 0)
            assert rows_carry.shape == (n_arr, W), \
                f"carried row store shape {rows_carry.shape} != {(n_arr, W)}"
            rows0 = rows_carry
            if quantized:
                # the integers need a global scale first, so they are stored
                # here, over the real values they were rounded from
                ghb = jnp.concatenate(
                    [jax.lax.bitcast_convert_type(grad.astype(f32), jnp.uint8),
                     jax.lax.bitcast_convert_type(hess.astype(f32), jnp.uint8)],
                    axis=1)
                rows0 = rows0.at[:n, voff:voff + 8].set(ghb)
        else:
            if bpc == 2:
                bins_u8 = jax.lax.bitcast_convert_type(
                    bins, jnp.uint8).reshape(n, nbytes_bins)
            else:
                bins_u8 = bins.astype(jnp.uint8)
            parts = [bins_u8]
            if voff > nbytes_bins:
                parts.append(jnp.zeros((n, voff - nbytes_bins), jnp.uint8))
            state = [grad.astype(f32), hess.astype(f32),
                     jnp.arange(n, dtype=jnp.int32)]
            if carried:
                # the five columns as ONE [n, 20] part, each byte selected
                # and shifted out of its column's word: every part of the
                # concatenation costs the compiler a store-sized temporary,
                # and this construction is the chunk program's peak memory
                # (8 stores with seven parts, 4 with three; a bitcast and
                # reshape of the stacked words is as lean but compiles to a
                # relayout program that grows by 3 B a row)
                state += [x.astype(f32) for x in extra]     # aux, score
                lane = jnp.arange(20, dtype=jnp.int32)[None, :]
                cols = [jax.lax.bitcast_convert_type(x, jnp.int32)[:, None]
                        for x in state]
                word = cols[4]
                for c in (3, 2, 1, 0):
                    word = jnp.where(lane // 4 == c, cols[c], word)
                parts.append(((word >> (8 * (lane % 4))) & 255
                              ).astype(jnp.uint8))
            else:
                parts += [jax.lax.bitcast_convert_type(x, jnp.uint8)
                          for x in state]
            if lazy_on:
                # rows that already paid lazy feature costs in EARLIER trees
                # (feature_used_in_data_ lives for the whole training,
                # cost_effective_gradient_boosting.hpp:47)
                parts.append(paid_bits if paid_bits is not None
                             else jnp.zeros((n, bitbytes), jnp.uint8))
            if W > bitoff + bitbytes:
                parts.append(jnp.zeros((n, W - bitoff - bitbytes), jnp.uint8))
            rows0 = jnp.concatenate(parts, axis=1)
            if fused:
                pad_order = jax.lax.bitcast_convert_type(
                    jnp.arange(n, n + _PCHUNK, dtype=jnp.int32), jnp.uint8)
                pad_block = jnp.zeros((_PCHUNK, W), jnp.uint8).at[
                    :, voff + 8:voff + 12].set(pad_order)
                rows0 = jnp.concatenate([rows0, pad_block], axis=0)

    def hist_rows(rows_mat, start, count):
        # hist_fc/hist_f0 are set below once the comm mode is known:
        # feature-parallel shards histogram only their own F/d block
        # (feature_parallel_tree_learner.cpp:33-52)
        return histogram_rows(rows_mat, num_bins, start, count,
                              num_features=hist_fc, voff=voff, bpc=bpc,
                              packed=bool(packed_cols),
                              use_pallas=use_pallas, f_begin=hist_f0,
                              interpret=pallas_interpret,
                              quantized=quantized)

    def col_from_rows(wi, gcol):
        """Dynamic bin-column extract from [R, W] i32 row-store bytes."""
        lanes = jnp.arange(W, dtype=jnp.int32)
        if packed_cols:
            byte = jnp.sum(wi * (lanes == gcol // 2), axis=1)
            return (byte >> (4 * (gcol % 2))) & 15
        if bpc == 2:
            lo = jnp.sum(wi * (lanes == 2 * gcol), axis=1)
            hi = jnp.sum(wi * (lanes == 2 * gcol + 1), axis=1)
            return lo | (hi << 8)
        return jnp.sum(wi * (lanes == gcol), axis=1)

    def unpack(h, sg, sh):
        """Group-column histogram [G, 2, Bg] -> per-feature [F, 2, B] with the
        shared default bin recovered by subtraction from the leaf totals
        (dataset.h:501 FixHistogram)."""
        if unpack_lanes is None:
            return h
        lidx, lmask, _ = unpack_lanes
        # a scope of its own inside tree.root / tree.find_split, entered on
        # the grouped path only: an ungrouped table's program is unchanged
        with jax.named_scope("tree.unpack"):
            hf = jnp.take_along_axis(h[feat.group], lidx[:, None, :], axis=2)
            hf = hf * lmask[:, None, :]
            rest = jnp.sum(hf, axis=2)
            return hf.at[:, 0, 0].set(sg - rest[:, 0]).at[:, 1, 0].set(
                sh - rest[:, 1])

    # Collective comm modes over ``axis_name`` (rows sharded unless noted):
    # - "rs": the reference DataParallelTreeLearner structure
    #   (data_parallel_tree_learner.cpp:149-240) — per-split ICI volume is
    #   F*B/d per shard, each shard stores/scans only the GLOBAL histograms
    #   of its own F/d features, winner by allreduce-argmax
    #   (SyncUpGlobalBestSplit, parallel_tree_learner.h:190-213)
    # - "psum": full-histogram allreduce per split (simple data parallel)
    # - "feature": rows REPLICATED; every shard partitions identically and
    #   holds the full local=global histogram but scans only its own F/d
    #   features; only the tiny best-split allreduce crosses chips
    #   (feature_parallel_tree_learner.cpp:33-71)
    # - "voting": rows sharded, histograms kept LOCAL; per-shard top-k
    #   candidate election + global vote, then psum of only the 2*top_k
    #   elected features' histograms
    #   (voting_parallel_tree_learner.cpp:170-366)
    rs = bool(axis_name) and comm_mode == "rs"
    feat_mode = bool(axis_name) and comm_mode == "feature"
    vote_mode = bool(axis_name) and comm_mode == "voting"
    if rs or feat_mode:
        assert unpack_lanes is None and forced is None and cegb is None, \
            "feature-sharded scans need one column per feature and the full " \
            "histogram block for forced splits / CEGB"
        assert f % num_shards == 0, "pad features to a multiple of the mesh"
        chunk_f = f // num_shards
        off_f = jax.lax.axis_index(axis_name) * chunk_f

        def _slc(a):
            return jax.lax.dynamic_slice_in_dim(a, off_f, chunk_f, axis=0)
        feat_c = FeatureInfo(*[None if a is None else _slc(a) for a in feat])
        mask_c = _slc(feature_mask)
        ids_c = off_f + jnp.arange(chunk_f, dtype=jnp.int32)
    if vote_mode:
        assert unpack_lanes is None and forced is None and cegb is None, \
            "voting elects by feature id; EFB unpacking, forced splits and " \
            "CEGB need the full histogram block"
        # local candidate search scales the per-leaf minimums by 1/d
        # (voting_parallel_tree_learner.cpp:57-59)
        vote_params = params._replace(
            min_data_in_leaf=max(params.min_data_in_leaf // num_shards, 1),
            min_sum_hessian_in_leaf=(params.min_sum_hessian_in_leaf
                                     / num_shards))

    hist_fc, hist_f0 = f_cols, 0
    if feat_mode and (not use_pallas or _use_factored(f // num_shards,
                                                      num_bins, quantized)):
        # shard histogram CONSTRUCTION, not just the scan; the TPU kernel
        # needs the factored path for a dynamic feature window, so wide-F
        # configurations keep the replicated build (scan still sharded)
        hist_fc, hist_f0 = chunk_f, off_f

    def reduce_hist(h):
        if quantized:
            # round 22: the collective payload rides bf16 — HALF the bytes
            # of the f32 allreduce (int16 cannot hold the ~2^27 per-shard
            # bin sums; bf16 never overflows and its rounding is charged to
            # the declared quant budgets).  EVERY branch then dequantizes by
            # the iteration's scales, so all stored histogram state
            # (subtraction trick, FixHistogram, split scans) stays
            # real-valued f32 and downstream code is unchanged.
            if axis_name and not feat_mode and not vote_mode:
                hb = h.astype(jnp.bfloat16)
                with jax.named_scope("comm.hist_reduce"):
                    if rs:
                        hb = jax.lax.psum_scatter(hb, axis_name,
                                                  scatter_dimension=0,
                                                  tiled=True)
                    else:
                        hb = jax.lax.psum(hb, axis_name)
                h = hb.astype(jnp.float32)
            return dequantize_hist(h, qscale)
        if not axis_name or feat_mode or vote_mode:
            # feature: rows replicated, local histogram IS global;
            # voting: histograms stay local, only elected rows are summed
            return h
        with jax.named_scope("comm.hist_reduce"):
            if rs:
                return jax.lax.psum_scatter(h, axis_name,
                                            scatter_dimension=0, tiled=True)
            return jax.lax.psum(h, axis_name)

    if fused:
        # Round-7 size-bucketed fused dispatch: the split window's row count
        # picks a kernel variant (single-chunk small-window kernel for
        # sub-chunk leaves — the majority of splits at num_leaves=255 on
        # <=1M rows — a 1024-row-chunk pipeline for mid windows, the
        # 4096-row streaming pipeline above that), so per-split fixed cost
        # scales with the leaf window instead of paying the one-size CHUNK
        # pipeline every split.  The variant set is trace-static (static
        # ``bucket_plan`` or derived from the static row count), so the
        # fused lax.scan boosting path compiles once; the selector is the
        # traced window size.  Variants are bit-exact against each other
        # (partition.py round 7), so the bucket boundaries never shift
        # numerics.  No collectives live inside the switch — shards may
        # take different branches under shard_map.
        plan = bucket_plan if bucket_plan is not None else fused_bucket_plan(n)

        def _mk_fused(small_k, chunk_k):
            def br(rows_m, scal_v):
                return partition_hist_pallas(
                    rows_m, scal_v, num_features=hist_fc, num_bins=num_bins,
                    voff=voff, bpc=bpc, packed=bool(packed_cols),
                    exact=_exact_hist(), chunk=chunk_k, small=small_k,
                    interpret=pallas_interpret, quantized=quantized)
            return br

        fused_branches = [_mk_fused(s, c) for (s, c, _) in plan]
        fused_bounds = (None if len(plan) == 1 else
                        jnp.asarray([b for (_, _, b) in plan[:-1]],
                                    jnp.int32))

        def _fused_split(rows_m, scal_v, wcount):
            if fused_bounds is None:
                return fused_branches[0](rows_m, scal_v)
            which = jnp.searchsorted(fused_bounds, wcount).astype(jnp.int32)
            return jax.lax.switch(which, fused_branches, rows_m, scal_v)

    grow_level = tree_grow_mode == "level"
    if tree_grow_mode not in ("leaf", "level"):
        raise ValueError("unknown tree_grow_mode %r" % (tree_grow_mode,))
    if grow_level:
        assert fused, \
            "tree_grow_mode=level needs the fused Pallas split path " \
            "(TPU backend or pallas_interpret) and a CHUNK-padded row store"
        assert forced is None and cegb is None, \
            "tree_grow_mode=level is incompatible with forced splits / CEGB"
        assert hist_pool_slots == 0, \
            "tree_grow_mode=level needs the unbounded per-leaf histogram " \
            "cache (histogram_pool_size is leaf-wise only)"
        assert not axis_name, \
            "tree_grow_mode=level runs on the serial learner only"

    contri = (jnp.maximum(jnp.asarray(params.feature_contri, f32), 0.0)
              if params.feature_contri else None)

    def _apply_contri(fb, ids):
        """gain[i] = max(0, feature_contri[i]) * gain[i] (config.h:432-436),
        applied before the cross-feature argmax (and before CEGB's penalty
        subtraction); ``ids`` maps the scan's positions to global inner
        feature indices so sharded/elected scans index the full vector."""
        if contri is None:
            return fb
        return fb._replace(gain=jnp.where(
            fb.gain > K_MIN_SCORE, fb.gain * contri[ids], fb.gain))

    # A bundled table with no categorical feature and no CEGB is searched in
    # group space (split.group_best); the categorical search sorts a
    # feature's bins and CEGB caches every feature's candidate, so both keep
    # the per-feature block that ``unpack`` makes.
    group_search = group_search_applies(unpack_lanes is not None,
                                        has_categorical, cegb is not None)
    if group_search:
        lanes = unpack_lanes[2]
        lanes_valid = lanes.valid & feature_mask[
            jnp.minimum(lanes.feature, f - 1)]
        lane_contri = (None if contri is None else
                       contri[jnp.minimum(lanes.feature2, f - 1)])

    def best_of(h, sg, sh, cnt, cmn, cmx, used=None, ucnt=None):
        """Best split of a leaf; with CEGB also returns the per-feature
        candidates (the reference's splits_per_leaf_ cache,
        cost_effective_gradient_boosting.hpp:35)."""
        if rs or feat_mode:
            sharded = rs or hist_fc != f_cols
            hc = h if sharded else jax.lax.dynamic_slice_in_dim(
                h, off_f, chunk_f, axis=0)
            fb = per_feature_best_combined(
                hc, feat_c, mask_c, sg, sh, cnt, params,
                any_categorical=has_categorical,
                cmin=cmn if has_monotone else None,
                cmax=cmx if has_monotone else None)
            fb = _apply_contri(fb, ids_c)
            return sync_best(reduce_feature_best(fb, ids_c), axis_name)
        if vote_mode:
            # per-shard candidate search on LOCAL histograms with scaled
            # minimums, 2*top_k election, psum of only the elected features
            local = jnp.sum(h[0], axis=-1)   # every row hits one bin of f0
            lg, lh = local[0], local[1]
            lcnt = cnt.astype(f32) * lh / (sh + 1e-15)
            fb_local = per_feature_best_combined(
                h, feat, feature_mask, lg, lh, lcnt, vote_params,
                any_categorical=has_categorical,
                cmin=cmn if has_monotone else None,
                cmax=cmx if has_monotone else None)
            fb_local = _apply_contri(fb_local, jnp.arange(f, dtype=jnp.int32))
            kk = min(top_k, f)
            top_gain, top_ids = jax.lax.top_k(fb_local.gain, kk)
            with jax.named_scope("comm.best_split"):
                all_ids = jax.lax.all_gather(top_ids, axis_name).reshape(-1)
                all_ok = jax.lax.all_gather(top_gain, axis_name
                                            ).reshape(-1) > K_MIN_SCORE
            votes = jax.ops.segment_sum(all_ok.astype(f32), all_ids,
                                        num_segments=f)
            key = votes - jnp.arange(f, dtype=f32) / (f + 1.0)  # ties: low id
            elected = jnp.sort(
                jax.lax.top_k(key, min(2 * kk, f))[1]).astype(jnp.int32)
            with jax.named_scope("comm.hist_reduce"):
                he = jax.lax.psum(h[elected], axis_name)
            feat_e = FeatureInfo(*[None if a is None else a[elected]
                                   for a in feat])
            fb = per_feature_best_combined(
                he, feat_e, feature_mask[elected], sg, sh, cnt, params,
                any_categorical=has_categorical,
                cmin=cmn if has_monotone else None,
                cmax=cmx if has_monotone else None)
            return reduce_feature_best(_apply_contri(fb, elected), elected)
        if group_search:
            # a bundled table: the search runs on the group histogram's own
            # lanes; tree.unpack holds what took the unbundling's place
            with jax.named_scope("tree.unpack"):
                scans = group_scans(h, lanes, sg, sh, cnt)
            return group_best(scans, lanes, lanes_valid, sg, sh, cnt, params, B,
                              cmin=cmn if has_monotone else None,
                              cmax=cmx if has_monotone else None,
                              lane_contri=lane_contri)
        fb = per_feature_best_combined(
            unpack(h, sg, sh), feat, feature_mask, sg, sh, cnt, params,
            any_categorical=has_categorical,
            cmin=cmn if has_monotone else None,
            cmax=cmx if has_monotone else None)
        fb = _apply_contri(fb, jnp.arange(f, dtype=jnp.int32))
        if cegb is not None:
            # DetlaGain (cost_effective_gradient_boosting.hpp:50-61):
            # split penalty + coupled (until first use) + lazy on-demand
            # cost for rows that have not paid the feature yet
            split_pen, coupled, _, lazy = cegb
            with jax.named_scope(_scopes.FIND_GAIN):
                penalty = (split_pen * cnt.astype(jnp.float32)
                           + jnp.where(used, 0.0, coupled))
                if lazy_on:
                    penalty = penalty + lazy * jnp.maximum(
                        cnt.astype(jnp.float32) - ucnt, 0.0)
                fb = fb._replace(gain=jnp.where(fb.gain > K_MIN_SCORE,
                                                fb.gain - penalty, fb.gain))
            return reduce_feature_best(fb, jnp.arange(f, dtype=jnp.int32)), fb
        return reduce_feature_best(fb, jnp.arange(f, dtype=jnp.int32))

    def unpack_one(h, ffeat, sg, sh):
        """One feature's [1, 2, B] histogram from a group-column block
        (avoids unpacking all F features in the growth loop)."""
        if unpack_lanes is None:
            return jax.lax.dynamic_index_in_dim(h, ffeat, axis=0)
        lidx, lmask, _ = unpack_lanes
        hg = jax.lax.dynamic_index_in_dim(h, feat.group[ffeat], axis=0,
                                          keepdims=False)      # [2, Bg]
        hf = jnp.take(hg, lidx[ffeat], axis=1) * lmask[ffeat][None, :]
        rest = jnp.sum(hf, axis=1)
        return hf.at[0, 0].set(sg - rest[0]).at[1, 0].set(
            sh - rest[1])[None]

    def forced_best(st, k):
        """Stats of the k-th forced split (GatherInfoForThreshold semantics):
        per_feature_best with the candidate set restricted to one threshold.
        Valid only while every earlier forced split applied (st.force_on) —
        otherwise leaf ids in the schedule no longer line up."""
        s_max = forced[0].shape[0]
        idx = jnp.minimum(k - 1, s_max - 1)
        fleaf = forced[0][idx]
        ffeat = forced[1][idx]
        fthr = forced[2][idx]
        sg = st.lsum_g[fleaf]
        sh = st.lsum_h[fleaf]
        cnt = st.tree.leaf_count[fleaf]
        hf = unpack_one(st.hist[fleaf], ffeat, sg, sh)
        feat1 = FeatureInfo(*[None if a is None else
                              jax.lax.dynamic_index_in_dim(a, ffeat)
                              for a in feat])
        tmask = jnp.arange(B, dtype=jnp.int32) == fthr
        fb = per_feature_best(hf, feat1, jnp.ones((1,), bool), sg, sh, cnt,
                              params,
                              cmin=st.cmin[fleaf] if has_monotone else None,
                              cmax=st.cmax[fleaf] if has_monotone else None,
                              threshold_mask=tmask)
        best = reduce_feature_best(fb, ffeat[None])
        valid = (k <= s_max) & (best.gain > K_MIN_SCORE) & st.force_on
        if max_depth > 0:   # forced splits still honor the depth cap
            valid = valid & (st.tree.leaf_depth[fleaf] < max_depth)
        in_sched = k <= s_max
        return fleaf, best, valid, in_sched

    if cegb is not None:
        vmapped_best = jax.vmap(best_of, in_axes=(0, 0, 0, 0, 0, 0, None, 0))
    else:
        vmapped_best = jax.vmap(best_of, in_axes=(0, 0, 0, 0, 0, 0, None))

    def make_branch(R):
        """Partition the parent window (size <= R) of the row store and
        histogram the smaller child.

        Cost scales with the bucket size R: one contiguous slice, a
        stable-partition row scatter of the slice (the reference's
        DataPartition::Split, data_partition.hpp:113 — grad/hess/order bytes
        ride along in the same rows), one contiguous write-back, and a
        histogram whose out-of-window tiles are skipped."""

        def branch(rows, b, c, feat_id, thr, default_left,
                   is_cat, bitset, left_smaller):
            s0 = jnp.clip(b, 0, n - R)
            rel_b = b - s0
            w = jax.lax.dynamic_slice(rows, (s0, 0), (R, W))
            iota = jnp.arange(R, dtype=jnp.int32)
            colw = col_from_rows(w.astype(jnp.int32),
                                 _feature_column(feat_id, feat))
            colw = _unfold_bin(colw, feat_id, feat)
            glw = _route_left(colw, thr, default_left,
                              feat.missing_type[feat_id],
                              feat.num_bin[feat_id],
                              feat.default_bin[feat_id],
                              is_cat=is_cat, bitset=bitset)
            inw = (iota >= rel_b) & (iota < rel_b + c)
            gl = glw & inw
            nl = jnp.sum(gl, dtype=jnp.int32)
            cl = jnp.cumsum(gl, dtype=jnp.int32)
            cr = jnp.cumsum(inw & ~gl, dtype=jnp.int32)
            dest = jnp.where(gl, rel_b + cl - 1,
                             jnp.where(inw, rel_b + nl + cr - 1, iota))
            if lazy_on:
                # every row of the split leaf has now paid feat_id's lazy
                # cost: set its bit (UpdateLeafBestSplits' InsertBitset loop)
                lanes = jnp.arange(W, dtype=jnp.int32)
                bit_col = bitoff + feat_id // 8
                bit_val = (jnp.uint8(1) << (feat_id % 8).astype(jnp.uint8))
                w = jnp.where((lanes[None, :] == bit_col) & inw[:, None],
                              w | bit_val, w)
            w = jnp.zeros_like(w).at[dest].set(w, unique_indices=True)
            rows = jax.lax.dynamic_update_slice(rows, w, (s0, 0))
            # smaller child's histogram from the permuted window; the side is
            # chosen from replicated global estimates so every shard streams
            # the same child (required for the psum below)
            rel_s = jnp.where(left_smaller, rel_b, rel_b + nl)
            cnt_s = jnp.where(left_smaller, nl, c - nl)
            hist_small = hist_rows(w, rel_s, cnt_s)
            if not lazy_on:
                return rows, hist_small, nl
            # per-child per-feature counts of rows whose bit is set (the
            # CalculateOndemandCosts scan, amortized to one pass per split)
            fi = np.arange(f)
            bitmat = ((w[:, bitoff + fi // 8].astype(jnp.int32)
                       >> jnp.asarray(fi % 8)) & 1).astype(f32)   # [R, F]
            in_left = ((iota >= rel_b) & (iota < rel_b + nl)).astype(f32)
            in_right = ((iota >= rel_b + nl)
                        & (iota < rel_b + c)).astype(f32)
            used_l = jnp.sum(bitmat * in_left[:, None], axis=0)
            used_r = jnp.sum(bitmat * in_right[:, None], axis=0)
            return rows, hist_small, nl, used_l, used_r

        return branch

    branches = [] if fused else [make_branch(R) for R in buckets]

    # ---- root ----
    with jax.named_scope("tree.root"):
        hist0 = hist_rows(rows0, jnp.int32(0), jnp.int32(n))
        if grad is None:
            sum_g, sum_h = root_sums    # the hand-over pass summed them
        else:
            sum_g = jnp.sum(grad)
            sum_h = jnp.sum(hess)
        # reduce_hist also DEQUANTIZES under hist_precision=quantized, so it
        # runs unconditionally (identity for the serial exact path)
        hist0 = reduce_hist(hist0)
        if axis_name and not feat_mode:
            # root aggregate Allreduce (data_parallel_tree_learner.cpp:99-146);
            # feature mode replicates the rows, so local sums are already global
            with jax.named_scope("comm.sums"):
                sum_g = jax.lax.psum(sum_g, axis_name)
                sum_h = jax.lax.psum(sum_h, axis_name)
        if quantized:
            # root totals were summed over the INTEGER gradients: scale them
            # back so leaf outputs / gains live in the real-valued domain
            sum_g = sum_g * qscale[0]
            sum_h = sum_h * qscale[1]
        no_min = jnp.float32(-np.inf)
        no_max = jnp.float32(np.inf)
        used0 = (cegb[2] if cegb is not None else jnp.zeros((f,), bool))
        if lazy_on:
            # rows that pre-paid each feature's lazy cost in earlier trees
            fi0 = np.arange(f)
            pb0 = rows0[:, bitoff + fi0 // 8].astype(jnp.int32)
            ucnt0 = jnp.sum(((pb0 >> jnp.asarray(fi0 % 8)) & 1).astype(f32),
                            axis=0)
            if axis_name:
                with jax.named_scope("comm.sums"):
                    ucnt0 = jax.lax.psum(ucnt0, axis_name)
        else:
            ucnt0 = jnp.zeros((f,), f32)
        if cegb is not None:
            best0, fb0 = best_of(hist0, sum_g, sum_h, num_data, no_min, no_max,
                                 used0, ucnt0)
            fbc0 = type(fb0)(*[
                jnp.full((L,) + x.shape,
                         K_MIN_SCORE if name == "gain" else 0,
                         dtype=x.dtype).at[0].set(x)
                for name, x in zip(type(fb0)._fields, fb0)])
        else:
            best0 = best_of(hist0, sum_g, sum_h, num_data, no_min, no_max)
            fbc0 = ()

        def zl(dtype=f32):
            return jnp.zeros((L,), dtype=dtype)

        tree = TreeArrays(
            split_feature=zl(jnp.int32), threshold_bin=zl(jnp.int32),
            split_gain=zl(), default_left=zl(bool),
            left_child=zl(jnp.int32), right_child=zl(jnp.int32),
            internal_value=zl(), internal_weight=zl(), internal_count=zl(),
            leaf_value=zl(), leaf_weight=zl().at[0].set(sum_h),
            leaf_count=zl().at[0].set(num_data.astype(f32)),
            leaf_parent=jnp.full((L,), -1, dtype=jnp.int32), leaf_depth=zl(jnp.int32),
            cat_bitset=jnp.zeros((L, B // 32), dtype=jnp.uint32),
            num_leaves=jnp.int32(1), row_leaf=jnp.zeros((n,), dtype=jnp.int32))

        # Histogram state: unbounded keeps one slot per leaf ([L, F, 2, B], the
        # round-3 behavior); histogram_pool_size > 0 bounds it to K LRU slots
        # (the reference's HistogramPool, feature_histogram.hpp:687) — an evicted
        # parent is REBUILT by streaming its window, which post-partition still
        # holds exactly the parent's rows.
        pooled = hist_pool_slots > 0
        if pooled:
            assert forced is None and cegb is None, \
                "histogram_pool_size needs the full per-leaf cache for forced " \
                "splits / CEGB candidate bookkeeping"
            K_slots = max(2, min(hist_pool_slots, L))
            hist = jnp.zeros((K_slots,) + hist0.shape, dtype=f32).at[0].set(hist0)
            slot_of0 = jnp.full((L,), -1, jnp.int32).at[0].set(0)
            stamps0 = jnp.full((K_slots,), -1, jnp.int32).at[0].set(0)
        else:
            hist = jnp.zeros((L,) + hist0.shape, dtype=f32).at[0].set(hist0)
            slot_of0 = ()
            stamps0 = ()
        bests = BestSplit(*[jnp.broadcast_to(x, (L,) + x.shape).astype(x.dtype)
                            for x in best0])
        state = _PState(tree=tree, hist=hist, bests=bests, cont=jnp.bool_(True),
                        cmin=jnp.full((L,), -np.inf, dtype=f32),
                        cmax=jnp.full((L,), np.inf, dtype=f32),
                        begin=zl(jnp.int32),
                        wcount=zl(jnp.int32).at[0].set(n),
                        rows=rows0,
                        lsum_g=zl().at[0].set(sum_g),
                        lsum_h=zl().at[0].set(sum_h),
                        feat_used=used0,
                        force_on=jnp.bool_(True),
                        fbc=fbc0,
                        slot_of=slot_of0,
                        stamps=stamps0)

    def body(k, st: _PState) -> _PState:
        with jax.named_scope("tree.pick_leaf"):
            node = k - 1
            t = st.tree
            gains = jnp.where(jnp.arange(L) < t.num_leaves, st.bests.gain, K_MIN_SCORE)
            if max_depth > 0:
                gains = jnp.where(t.leaf_depth < max_depth, gains, K_MIN_SCORE)
            leaf = jnp.argmax(gains).astype(jnp.int32)
            ok = (gains[leaf] > 0.0) & st.cont
            force_now = None
            if forced is not None:
                fleaf, fbest, fvalid, in_sched = forced_best(st, k)
                leaf = jnp.where(fvalid, fleaf, leaf)
                ok = jnp.where(fvalid, st.cont, ok)
                force_now = (fbest, fvalid)
                # one failed entry invalidates the rest of the schedule's leaf ids
                st = st._replace(force_on=st.force_on & (~in_sched | fvalid))

            # The split always executes — a dead iteration (ok=False) partitions
            # an EMPTY window of the smallest bucket (identity permutation, zero
            # histogram) and every state write below is masked by ``ok``.  An
            # actual lax.cond around the split forced XLA to materialize
            # unification copies of the partitioned matrices every iteration.
            t = st.tree
            b = BestSplit(*[x[leaf] for x in st.bests])
            if force_now is not None:
                fbest, fvalid = force_now
                b = BestSplit(*[jnp.where(fvalid, fx, x)
                                for fx, x in zip(fbest, b)])
            wb = jnp.where(ok, st.begin[leaf], 0)
            wc = jnp.where(ok, st.wcount[leaf], 0)
            left_smaller = b.left_count <= b.right_count
        with jax.named_scope("tree.split"):
            if fused:
                # one fused Pallas pass: route + stable partition + smaller-child
                # histogram, cost proportional to the window (core/partition.py)
                fid = b.feature
                if feat.offset is None:
                    unf = jnp.int32(0)
                    eoff = jnp.int32(0)
                else:
                    unf = jnp.int32(1)
                    eoff = feat.offset[fid].astype(jnp.int32)
                head = jnp.stack([
                    wb, wc, _feature_column(fid, feat).astype(jnp.int32),
                    b.threshold.astype(jnp.int32),
                    b.default_left.astype(jnp.int32),
                    feat.missing_type[fid].astype(jnp.int32),
                    feat.num_bin[fid].astype(jnp.int32),
                    feat.default_bin[fid].astype(jnp.int32),
                    feat.is_categorical[fid].astype(jnp.int32),
                    left_smaller.astype(jnp.int32), unf, eoff])
                nw = num_bins // 32
                bw = jax.lax.bitcast_convert_type(b.cat_bitset, jnp.int32)
                if bw.shape[0] < nw:
                    bw = jnp.concatenate(
                        [bw, jnp.zeros((nw - bw.shape[0],), jnp.int32)])
                scal = jnp.concatenate([head, bw[:nw]])
                if hist_fc != f_cols:
                    scal = jnp.concatenate(
                        [scal, jnp.reshape(jnp.asarray(hist_f0, jnp.int32),
                                           (1,))])
                rows_new, hist4, nl_arr = _fused_split(st.rows, scal, wc)
                hist_small = fold_hist(hist4, hist_fc, num_bins, quantized)
                nl = nl_arr[0, 0]
                used_l = used_r = jnp.zeros((f,), f32)
            else:
                which = jnp.searchsorted(bsizes, wc).astype(jnp.int32)
                branch_out = jax.lax.switch(
                    which, branches, st.rows, wb, wc,
                    b.feature, b.threshold, b.default_left,
                    feat.is_categorical[b.feature], b.cat_bitset, left_smaller)
                if lazy_on:
                    rows_new, hist_small, nl, used_l, used_r = branch_out
                else:
                    rows_new, hist_small, nl = branch_out
                    used_l = used_r = jnp.zeros((f,), f32)
        with jax.named_scope("tree.find_split"):
            # per-split Allreduce (psum) or ReduceScatter (rs) of the smaller
            # child's histogram (data_parallel_tree_learner.cpp:161
            # ReduceScatter); unconditional so the quantized path dequantizes
            # on the serial learner too
            hist_small = reduce_hist(hist_small)
            if axis_name and lazy_on:
                with jax.named_scope("comm.sums"):
                    used_l = jax.lax.psum(used_l, axis_name)
                    used_r = jax.lax.psum(used_r, axis_name)

        def sel(new, old):
            """Masked state write: keep ``old`` on dead iterations."""
            return jnp.where(ok, new, old)

        with jax.named_scope("tree.find_split"), \
                jax.named_scope(_scopes.FIND_HIST_CACHE):
            if pooled:
                # parent histogram from its LRU slot, or rebuilt by streaming the
                # window (post-partition it still holds exactly the parent rows —
                # HistogramPool::Get miss, feature_histogram.hpp:687).
                # INVARIANT under comm_mode='rs': slot_of/stamps are REPLICATED
                # across shards, so every shard takes the same cond branch and
                # the psum_scatter inside _miss is executed collectively; a
                # shard-local divergence of this state would deadlock the
                # collective.  (Replication holds because slot bookkeeping is
                # derived only from replicated best-split decisions.)
                ps = st.slot_of[leaf]

                def _hit(_):
                    return st.hist[jnp.maximum(ps, 0)]

                def _miss(_):
                    return reduce_hist(hist_rows(rows_new, wb, wc))

                parent_hist = jax.lax.cond(ps >= 0, _hit, _miss, 0)
                hist_larger = parent_hist - hist_small
                hist_left = jnp.where(left_smaller, hist_small, hist_larger)
                hist_right = jnp.where(left_smaller, hist_larger, hist_small)
                # left child inherits the parent's slot (or the LRU slot on a
                # miss); right child evicts the next-least-recently-used slot
                sL = jnp.where(ps >= 0, ps, jnp.argmin(st.stamps).astype(jnp.int32))
                sR = jnp.argmin(st.stamps.at[sL].set(2 ** 30)).astype(jnp.int32)
                hist_new = st.hist.at[sL].set(sel(hist_left, st.hist[sL])) \
                                  .at[sR].set(sel(hist_right, st.hist[sR]))
                stamps_new = st.stamps.at[sL].set(k).at[sR].set(k)
                slot_upd = jnp.where((st.slot_of == sL) | (st.slot_of == sR),
                                     -1, st.slot_of)
                slot_upd = slot_upd.at[leaf].set(sL).at[k].set(sR)
            else:
                hist_larger = st.hist[leaf] - hist_small
                hist_left = jnp.where(left_smaller, hist_small, hist_larger)
                hist_right = jnp.where(left_smaller, hist_larger, hist_small)
                hist_new = st.hist.at[leaf].set(sel(hist_left, st.hist[leaf])) \
                                  .at[k].set(sel(hist_right, st.hist[k]))
                stamps_new = st.stamps
                slot_upd = st.slot_of

        with jax.named_scope("tree.state_update"):
            begin = st.begin.at[k].set(wb + nl)
            wcount = st.wcount.at[leaf].set(nl).at[k].set(wc - nl)

            # monotone constraint propagation
            # (monotone_constraints.hpp UpdateConstraints)
            pmin, pmax = st.cmin[leaf], st.cmax[leaf]
            if has_monotone and feat.monotone is not None:
                mono_f = feat.monotone[b.feature]
            else:
                mono_f = jnp.int32(0)
            is_num = ~feat.is_categorical[b.feature]
            mid = (b.left_output + b.right_output) * 0.5
            lmin = jnp.where(is_num & (mono_f < 0), jnp.maximum(pmin, mid), pmin)
            lmax = jnp.where(is_num & (mono_f > 0), jnp.minimum(pmax, mid), pmax)
            rmin = jnp.where(is_num & (mono_f > 0), jnp.maximum(pmin, mid), pmin)
            rmax = jnp.where(is_num & (mono_f < 0), jnp.minimum(pmax, mid), pmax)
            cmin_new = st.cmin.at[leaf].set(lmin).at[k].set(rmin)
            cmax_new = st.cmax.at[leaf].set(lmax).at[k].set(rmax)

            feat_used = (st.feat_used | (jnp.arange(f) == b.feature)
                         if cegb is not None else st.feat_used)
        with jax.named_scope("tree.find_split"):
            if cegb is not None:
                # coupled-penalty refund (UpdateLeafBestSplits,
                # cost_effective_gradient_boosting.hpp:63-79): the first split on
                # a feature makes its coupled cost sunk, so every other leaf's
                # cached candidate for that feature gets the penalty back and is
                # promoted when it now beats the leaf's cached best.  (The
                # reference adds the refund to the PRE-penalty cached gain — a
                # quirk that inflates promoted gains; here the cache holds
                # penalized gains so the refund yields the intended value.)
                with jax.named_scope(_scopes.FIND_BESTS):
                    coupled_arr = cegb[1]
                    fnew = b.feature
                    newly = ok & ~st.feat_used[fnew]
                    refund = jnp.where(newly, coupled_arr[fnew], 0.0)
                    fbc = st.fbc._replace(
                        gain=st.fbc.gain.at[:, fnew].add(refund))
                    cand_gain = jnp.take(fbc.gain, fnew, axis=1)      # [L]
                    promote = (newly & (st.bests.gain > K_MIN_SCORE)
                               & (cand_gain > st.bests.gain))

                    def pick(cand_field, old_field):
                        cand_col = jnp.take(cand_field, fnew, axis=1)
                        shape_tail = (1,) * (old_field.ndim - 1)
                        return jnp.where(promote.reshape((-1,) + shape_tail),
                                         cand_col, old_field)

                    promoted = BestSplit(
                        gain=jnp.where(promote, cand_gain, st.bests.gain),
                        feature=jnp.where(promote, fnew, st.bests.feature),
                        threshold=pick(fbc.threshold, st.bests.threshold),
                        default_left=pick(fbc.default_left, st.bests.default_left),
                        left_sum_grad=pick(fbc.left_sum_grad,
                                           st.bests.left_sum_grad),
                        left_sum_hess=pick(fbc.left_sum_hess,
                                           st.bests.left_sum_hess),
                        left_count=pick(fbc.left_count, st.bests.left_count),
                        right_sum_grad=pick(fbc.right_sum_grad,
                                            st.bests.right_sum_grad),
                        right_sum_hess=pick(fbc.right_sum_hess,
                                            st.bests.right_sum_hess),
                        right_count=pick(fbc.right_count, st.bests.right_count),
                        left_output=pick(fbc.left_output, st.bests.left_output),
                        right_output=pick(fbc.right_output, st.bests.right_output),
                        cat_bitset=pick(fbc.cat_bitset, st.bests.cat_bitset))
                child_best, child_fb = vmapped_best(
                    jnp.stack([hist_left, hist_right]),
                    jnp.stack([b.left_sum_grad, b.right_sum_grad]),
                    jnp.stack([b.left_sum_hess, b.right_sum_hess]),
                    jnp.stack([b.left_count, b.right_count]),
                    jnp.stack([lmin, rmin]), jnp.stack([lmax, rmax]),
                    feat_used, jnp.stack([used_l, used_r]))
                fbc = type(fbc)(*[x.at[leaf].set(c[0]).at[k].set(c[1])
                                  for x, c in zip(fbc, child_fb)])
                with jax.named_scope(_scopes.FIND_BESTS):
                    bests = _bests_update(
                        promoted, leaf,
                        BestSplit(*[x[0] for x in child_best]))
            else:
                fbc = st.fbc
                child_best = vmapped_best(
                    jnp.stack([hist_left, hist_right]),
                    jnp.stack([b.left_sum_grad, b.right_sum_grad]),
                    jnp.stack([b.left_sum_hess, b.right_sum_hess]),
                    jnp.stack([b.left_count, b.right_count]),
                    jnp.stack([lmin, rmin]), jnp.stack([lmax, rmax]),
                    feat_used)
                with jax.named_scope(_scopes.FIND_BESTS):
                    bests = _bests_update(
                        st.bests, leaf,
                        BestSplit(*[x[0] for x in child_best]))
            with jax.named_scope(_scopes.FIND_BESTS):
                bests = _bests_update(
                    bests, k, BestSplit(*[x[1] for x in child_best]))

        with jax.named_scope("tree.state_update"):
            # parent child-pointer fixup (tree.h:338-346)
            parent = t.leaf_parent[leaf]
            pidx = jnp.maximum(parent, 0)
            lc = t.left_child
            rc = t.right_child
            lc = lc.at[pidx].set(jnp.where((parent >= 0) & (lc[pidx] == ~leaf),
                                           node, lc[pidx]))
            rc = rc.at[pidx].set(jnp.where((parent >= 0) & (rc[pidx] == ~leaf),
                                           node, rc[pidx]))

            tree_new = TreeArrays(
                split_feature=t.split_feature.at[node].set(b.feature),
                threshold_bin=t.threshold_bin.at[node].set(b.threshold),
                split_gain=t.split_gain.at[node].set(b.gain),
                default_left=t.default_left.at[node].set(b.default_left),
                left_child=lc.at[node].set(~leaf),
                right_child=rc.at[node].set(~k),
                internal_value=t.internal_value.at[node].set(t.leaf_value[leaf]),
                internal_weight=t.internal_weight.at[node].set(t.leaf_weight[leaf]),
                internal_count=t.internal_count.at[node].set(
                    b.left_count + b.right_count),
                leaf_value=t.leaf_value.at[leaf].set(
                    jnp.nan_to_num(b.left_output)).at[k].set(
                    jnp.nan_to_num(b.right_output)),
                leaf_weight=t.leaf_weight.at[leaf].set(
                    b.left_sum_hess).at[k].set(b.right_sum_hess),
                leaf_count=t.leaf_count.at[leaf].set(
                    b.left_count).at[k].set(b.right_count),
                leaf_parent=t.leaf_parent.at[leaf].set(node).at[k].set(node),
                leaf_depth=t.leaf_depth.at[k].set(
                    t.leaf_depth[leaf] + 1).at[leaf].add(1),
                cat_bitset=t.cat_bitset.at[node].set(b.cat_bitset),
                num_leaves=t.num_leaves + 1,
                row_leaf=t.row_leaf)
            lsum_g = st.lsum_g.at[leaf].set(b.left_sum_grad).at[k].set(
                b.right_sum_grad)
            lsum_h = st.lsum_h.at[leaf].set(b.left_sum_hess).at[k].set(
                b.right_sum_hess)
            small_new = (tree_new, bests, cmin_new, cmax_new, begin, wcount,
                         lsum_g, lsum_h, feat_used, fbc, slot_upd, stamps_new)
            small_old = (t, st.bests, st.cmin, st.cmax, st.begin, st.wcount,
                         st.lsum_g, st.lsum_h, st.feat_used, st.fbc,
                         st.slot_of, st.stamps)
            (tree_m, bests_m, cmin_m, cmax_m, begin_m, wcount_m, lsg_m, lsh_m,
             fu_m, fbc_m, slot_m, stamps_m) = jax.tree_util.tree_map(
                sel, small_new, small_old)
        return _PState(tree=tree_m, hist=hist_new, bests=bests_m,
                       cont=ok, cmin=cmin_m, cmax=cmax_m,
                       begin=begin_m, wcount=wcount_m,
                       rows=rows_new,
                       lsum_g=lsg_m, lsum_h=lsh_m, feat_used=fu_m,
                       force_on=st.force_on, fbc=fbc_m,
                       slot_of=slot_m, stamps=stamps_m)

    def level_step(d, Fcap, st: _PState) -> _PState:
        """One BFS level (round 12): split EVERY splittable depth-``d`` leaf
        with at most one multi-window Pallas launch per bucket class, then
        perform the whole frontier's bookkeeping (hist subtraction, child
        best-split search, tree-array updates) as batched scatters.

        ``Fcap`` is the trace-static frontier bound (min(2^d, L-1)); dead
        slots carry ``wc = 0`` windows (skipped in-kernel) and scatter to
        the dropped index ``L``, the level-wise analogue of the leaf-wise
        body's masked dead iteration."""
        t = st.tree
        leaves_i = jnp.arange(L, dtype=jnp.int32)
        gains = jnp.where(leaves_i < t.num_leaves, st.bests.gain, K_MIN_SCORE)
        mask = (t.leaf_depth == d) & (leaves_i < t.num_leaves) & (gains > 0.0)
        # frontier leaves in ascending id order; budget overflow drops the
        # highest ids (nonzero packs the found ids at the front)
        found = jnp.nonzero(mask, size=Fcap, fill_value=L)[0].astype(jnp.int32)
        rank = jnp.arange(Fcap, dtype=jnp.int32)
        active = (found < L) & (rank < L - t.num_leaves)
        nact = jnp.sum(active.astype(jnp.int32))
        lsafe = jnp.minimum(found, L - 1)          # gather-safe leaf ids
        leaf = jnp.where(active, found, L)         # scatter: L drops
        kid = jnp.where(active, t.num_leaves + rank, L)
        node = jnp.where(active, t.num_leaves - 1 + rank, L)

        b = BestSplit(*[x[lsafe] for x in st.bests])         # fields [Fcap]
        wb = jnp.where(active, st.begin[lsafe], 0)
        wc = jnp.where(active, st.wcount[lsafe], 0)
        left_smaller = b.left_count <= b.right_count

        # ---- per-window scalar rows (the leaf-wise fused head, batched) --
        fid = b.feature
        if feat.offset is None:
            unf = jnp.zeros((Fcap,), jnp.int32)
            eoff = jnp.zeros((Fcap,), jnp.int32)
        else:
            unf = jnp.ones((Fcap,), jnp.int32)
            eoff = feat.offset[fid].astype(jnp.int32)
        head = jnp.stack([
            wb, wc, _feature_column(fid, feat).astype(jnp.int32),
            b.threshold.astype(jnp.int32),
            b.default_left.astype(jnp.int32),
            feat.missing_type[fid].astype(jnp.int32),
            feat.num_bin[fid].astype(jnp.int32),
            feat.default_bin[fid].astype(jnp.int32),
            feat.is_categorical[fid].astype(jnp.int32),
            left_smaller.astype(jnp.int32), unf, eoff], axis=1)
        nw = num_bins // 32
        bw = jax.lax.bitcast_convert_type(b.cat_bitset, jnp.int32)
        if bw.shape[1] < nw:
            bw = jnp.concatenate(
                [bw, jnp.zeros((Fcap, nw - bw.shape[1]), jnp.int32)], axis=1)
        scal = jnp.concatenate([head, bw[:, :nw]], axis=1)

        # ---- one multi-window launch per bucket class ----
        # every frontier slot rides every class launch; out-of-class slots
        # are masked to wc = 0 (skipped in-kernel), so the grid stays
        # trace-static and each slot is partitioned exactly once.  Summing
        # the per-class outputs recovers each slot's histogram/count (the
        # other classes contributed exact zeros).
        if fused_bounds is None:
            class_of = jnp.zeros((Fcap,), jnp.int32)
        else:
            class_of = jnp.searchsorted(fused_bounds, wc).astype(jnp.int32)
        rows_m = st.rows
        nl = jnp.zeros((Fcap,), jnp.int32)
        hist_raw = None
        for ci, (small_k, chunk_k, _) in enumerate(plan):
            in_c = (class_of == ci) & active & (wc > 0)
            # zero wb AND wc for out-of-class slots: the pipelined kernels
            # derive their chunk count from the window HEAD offset too, so
            # a fully-zeroed dead window runs zero chunks
            scal_c = scal.at[:, 0].set(jnp.where(in_c, wb, 0)).at[:, 1].set(
                jnp.where(in_c, wc, 0))
            rows_m, hist_c, nl_c = partition_hist_level_pallas(
                rows_m, scal_c, num_features=hist_fc, num_bins=num_bins,
                voff=voff, bpc=bpc, packed=bool(packed_cols),
                exact=_exact_hist(), chunk=chunk_k, small=small_k,
                interpret=pallas_interpret, quantized=quantized)
            nl = nl + nl_c[:, 0]
            hist_raw = hist_c if hist_raw is None else hist_raw + hist_c
        hist_small = jax.vmap(
            lambda h: fold_hist(h, hist_fc, num_bins, quantized))(hist_raw)
        if quantized:
            # level mode is serial-only (grow_level asserts no axis_name),
            # so no collective rides here — dequantize the folded integers
            # directly; st.hist and the subtraction trick stay real f32
            hist_small = dequantize_hist(hist_small, qscale)

        # ---- subtraction trick + child best-split search, batched ----
        parent_hist = st.hist[lsafe]
        hist_larger = parent_hist - hist_small
        ls4 = left_smaller.reshape((-1,) + (1,) * (hist_small.ndim - 1))
        hist_left = jnp.where(ls4, hist_small, hist_larger)
        hist_right = jnp.where(ls4, hist_larger, hist_small)
        hist_new = st.hist.at[leaf].set(hist_left, mode="drop")
        hist_new = hist_new.at[kid].set(hist_right, mode="drop")

        # monotone constraint propagation (vectorized leaf-wise rule)
        pmin, pmax = st.cmin[lsafe], st.cmax[lsafe]
        if has_monotone and feat.monotone is not None:
            mono_f = feat.monotone[fid]
        else:
            mono_f = jnp.zeros((Fcap,), jnp.int32)
        is_num = ~feat.is_categorical[fid]
        mid = (b.left_output + b.right_output) * 0.5
        lmin = jnp.where(is_num & (mono_f < 0), jnp.maximum(pmin, mid), pmin)
        lmax = jnp.where(is_num & (mono_f > 0), jnp.minimum(pmax, mid), pmax)
        rmin = jnp.where(is_num & (mono_f > 0), jnp.maximum(pmin, mid), pmin)
        rmax = jnp.where(is_num & (mono_f < 0), jnp.minimum(pmax, mid), pmax)
        cmin_new = st.cmin.at[leaf].set(lmin, mode="drop").at[kid].set(
            rmin, mode="drop")
        cmax_new = st.cmax.at[leaf].set(lmax, mode="drop").at[kid].set(
            rmax, mode="drop")

        child_best = vmapped_best(
            jnp.concatenate([hist_left, hist_right], axis=0),
            jnp.concatenate([b.left_sum_grad, b.right_sum_grad]),
            jnp.concatenate([b.left_sum_hess, b.right_sum_hess]),
            jnp.concatenate([b.left_count, b.right_count]),
            jnp.concatenate([lmin, rmin]), jnp.concatenate([lmax, rmax]),
            st.feat_used)
        bests = BestSplit(*[
            f.at[leaf].set(c[:Fcap], mode="drop").at[kid].set(
                c[Fcap:], mode="drop")
            for f, c in zip(st.bests, child_best)])

        # ---- parent child-pointer fixup (siblings in one frontier target
        # the same parent node through DIFFERENT lc/rc slots, so the
        # scatter indices stay unique among active slots) ----
        parent = t.leaf_parent[lsafe]
        pidx = jnp.maximum(parent, 0)
        lc, rc = t.left_child, t.right_child
        upd_l = active & (parent >= 0) & (lc[pidx] == ~lsafe)
        upd_r = active & (parent >= 0) & (rc[pidx] == ~lsafe)
        lc = lc.at[jnp.where(upd_l, pidx, L)].set(node, mode="drop")
        rc = rc.at[jnp.where(upd_r, pidx, L)].set(node, mode="drop")
        lc = lc.at[node].set(~lsafe, mode="drop")
        rc = rc.at[node].set(~kid, mode="drop")

        tree_new = TreeArrays(
            split_feature=t.split_feature.at[node].set(b.feature,
                                                       mode="drop"),
            threshold_bin=t.threshold_bin.at[node].set(b.threshold,
                                                       mode="drop"),
            split_gain=t.split_gain.at[node].set(b.gain, mode="drop"),
            default_left=t.default_left.at[node].set(b.default_left,
                                                     mode="drop"),
            left_child=lc,
            right_child=rc,
            internal_value=t.internal_value.at[node].set(
                t.leaf_value[lsafe], mode="drop"),
            internal_weight=t.internal_weight.at[node].set(
                t.leaf_weight[lsafe], mode="drop"),
            internal_count=t.internal_count.at[node].set(
                b.left_count + b.right_count, mode="drop"),
            leaf_value=t.leaf_value.at[leaf].set(
                jnp.nan_to_num(b.left_output), mode="drop").at[kid].set(
                jnp.nan_to_num(b.right_output), mode="drop"),
            leaf_weight=t.leaf_weight.at[leaf].set(
                b.left_sum_hess, mode="drop").at[kid].set(
                b.right_sum_hess, mode="drop"),
            leaf_count=t.leaf_count.at[leaf].set(
                b.left_count, mode="drop").at[kid].set(
                b.right_count, mode="drop"),
            leaf_parent=t.leaf_parent.at[leaf].set(
                node, mode="drop").at[kid].set(node, mode="drop"),
            leaf_depth=t.leaf_depth.at[leaf].set(
                d + 1, mode="drop").at[kid].set(d + 1, mode="drop"),
            cat_bitset=t.cat_bitset.at[node].set(b.cat_bitset, mode="drop"),
            num_leaves=t.num_leaves + nact,
            row_leaf=t.row_leaf)

        begin = st.begin.at[kid].set(wb + nl, mode="drop")
        wcount = st.wcount.at[leaf].set(nl, mode="drop").at[kid].set(
            wc - nl, mode="drop")
        lsum_g = st.lsum_g.at[leaf].set(b.left_sum_grad,
                                        mode="drop").at[kid].set(
            b.right_sum_grad, mode="drop")
        lsum_h = st.lsum_h.at[leaf].set(b.left_sum_hess,
                                        mode="drop").at[kid].set(
            b.right_sum_hess, mode="drop")
        return _PState(tree=tree_new, hist=hist_new, bests=bests,
                       cont=nact > 0, cmin=cmin_new, cmax=cmax_new,
                       begin=begin, wcount=wcount, rows=rows_m,
                       lsum_g=lsum_g, lsum_h=lsum_h,
                       feat_used=st.feat_used, force_on=st.force_on,
                       fbc=st.fbc, slot_of=st.slot_of, stamps=st.stamps)

    if grow_level and L > 1:
        # static level schedule: a depth-D tree is at most D * bucket-class
        # launches.  With no max_depth the schedule covers the complete tree
        # that exactly fills the leaf budget; an early-exhausted frontier
        # (no positive gains / budget spent) makes the remaining levels
        # dead Fcap-slot launches of empty windows.  The leaf budget caps
        # the schedule regardless of max_depth: every live level grows at
        # least one leaf, so levels past L-1 are guaranteed dead — without
        # the cap a "just in case" max_depth=63 guard would unroll 63
        # level_steps and dispatch MORE than leaf-wise ever does.
        n_levels = (min(max_depth, L - 1) if max_depth > 0
                    else max(1, int(np.ceil(np.log2(L)))))
        for d in range(n_levels):
            state = level_step(d, min(1 << d, L - 1), state)
    elif L > 1:
        state = jax.lax.fori_loop(1, L, body, state)

    with jax.named_scope("tree.finish"):
        t = state.tree
        if carried:
            # row_leaf is returned EMPTY: the permuted-order assignment would
            # corrupt original-order consumers (rollback, stall trim), which
            # route the tree over the bins instead (gbdt._gather_tree_output).
            t = t._replace(row_leaf=jnp.zeros((0,), jnp.int32))
            if grad_fn is None:
                # the chunk's store construction: a one-leaf build whose
                # leaf value is 0 moves no score
                assert L == 1 and rows_carry is None
                return t, state.rows
            # hand the store to the next tree: each row's score takes its
            # window's (shrinkage-scaled) leaf value and the next gradients
            # are written beside it, in place — no per-row gather/scatter
            begins, values = leaf_windows(state.begin, state.wcount,
                                          t.leaf_value * score_rate,
                                          t.num_leaves, n)
            rows_out, next_g, next_h = advance_row_state(
                state.rows, begins, values, grad_fn, quant_it + 1, voff=voff,
                n=n, use_pallas=fused, interpret=pallas_interpret)
            return t, rows_out, (next_g, next_h)
        # reconstruct per-row leaf assignment from the windows + permutation
        # (n_arr covers the fused path's spare CHUNK; those rows sit past every
        # window, pick up a garbage leaf id, and are sliced away)
        n_arr = state.rows.shape[0]
        valid = (jnp.arange(L) < t.num_leaves) & (state.wcount > 0)
        mark_pos = jnp.where(valid, state.begin, n_arr)
        marks = jnp.zeros((n_arr,), jnp.int32).at[mark_pos].set(
            jnp.arange(L, dtype=jnp.int32) + 1, mode="drop")
        leaf_of_pos = _ffill_nonzero(marks) - 1
        order = jax.lax.bitcast_convert_type(
            state.rows[:, voff + 8:voff + 12], jnp.int32).reshape(n_arr)
        row_leaf = jnp.zeros((n_arr,), jnp.int32).at[order].set(
            leaf_of_pos, unique_indices=True)[:n]
        arrays = t._replace(row_leaf=row_leaf)
        if lazy_on:
            # paid-bit state back in ORIGINAL row order for the next tree
            bits_out = jnp.zeros((n, bitbytes), jnp.uint8).at[order].set(
                state.rows[:, bitoff:bitoff + bitbytes], unique_indices=True)
            return arrays, bits_out
        return arrays


@functools.partial(jax.jit, static_argnames=("num_leaves",))
def tree_output_binned(bins: jax.Array, tree: TreeArrays, feat: FeatureInfo,
                       *, num_leaves: int, depth_bound=None) -> jax.Array:
    """Per-row leaf VALUE over binned rows without traversal — the
    path-matrix formulation of core/predict.py rebuilt for on-device
    TreeArrays (numerical splits only; categorical models use
    :func:`route_binned`):

        D[n, m]   = +-1  go-left decision at EVERY node (vectorized)
        hits      = D @ P              (P[m, l] = path sign, built on device
                                        by walking leaf_parent chains)
        value(n)  = sum_l leaf_value[l] * (hits[n, l] == path_len[l])

    Replaces the per-level loop of route_binned for the fused valid-score
    update: level-loop routing costs ~8 table gathers per (row, level) and
    measured ~45 ns/row-level on v5e — 2.2x a whole training iteration for
    a 10%-sized valid set.  Here the only per-row work is one MXU column
    gather, ~10 vector ops per node lane, and two matmuls.
    """
    L = num_leaves
    M = max(L - 1, 1)
    n = bins.shape[0]
    nodes = jnp.arange(M, dtype=jnp.int32)
    node_valid = nodes < jnp.maximum(tree.num_leaves - 1, 1)

    # ---- node parents + side signs (scatter over [M]) ----
    lc = tree.left_child[:M]
    rc = tree.right_child[:M]
    parent = jnp.full((M,), -1, jnp.int32)
    sign_in_parent = jnp.zeros((M,), jnp.float32)
    lc_node = jnp.where((lc >= 0) & node_valid, lc, M)
    rc_node = jnp.where((rc >= 0) & node_valid, rc, M)
    parent = parent.at[lc_node].set(nodes, mode="drop")
    sign_in_parent = sign_in_parent.at[lc_node].set(1.0, mode="drop")
    parent = parent.at[rc_node].set(nodes, mode="drop")
    sign_in_parent = sign_in_parent.at[rc_node].set(-1.0, mode="drop")

    # ---- path matrix by walking each leaf's parent chain up ----
    lp = tree.leaf_parent[:L]
    leaves = jnp.arange(L, dtype=jnp.int32)
    start_sign = jnp.where(lc[jnp.maximum(lp, 0)] == ~leaves, 1.0, -1.0)

    def up(_, carry):
        P, plen, cur, sgn = carry
        live = cur >= 0
        curc = jnp.where(live, cur, 0)
        P = P.at[curc, leaves].add(jnp.where(live, sgn, 0.0))
        plen = plen + live.astype(jnp.float32)
        nxt = jnp.where(live, parent[curc], -1)
        sgn = jnp.where(live, sign_in_parent[curc], 0.0)
        return P, plen, nxt, sgn

    steps = (M if depth_bound is None
             else jnp.minimum(jnp.maximum(depth_bound, 1), M))
    P0 = jnp.zeros((M, L), jnp.float32)
    plen0 = jnp.zeros((L,), jnp.float32)
    P, plen, _, _ = jax.lax.fori_loop(
        0, steps, up, (P0, plen0, lp, start_sign))
    # padding leaves (parent -1, not leaf 0 of a stump) never match
    plen = jnp.where((leaves == 0) | (lp >= 0), plen, -1.0)
    plen = jnp.where(leaves < tree.num_leaves, plen, -1.0)

    # ---- vectorized per-node decisions D [n, M] ----
    f_id = tree.split_feature[:M]
    gcols = _feature_column(f_id, feat).astype(jnp.int32)        # [M]
    ncols = bins.shape[1]
    colsel = (gcols[:, None]
              == jnp.arange(ncols, dtype=jnp.int32)[None, :])    # [M, ncols]
    if bins.dtype == jnp.uint16:
        # u16 codes exceed bf16's exact-integer range; HIGHEST keeps the
        # one-hot column gather exact up to 2^24
        colv = jax.lax.dot_general(
            bins.astype(jnp.float32), colsel.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    else:
        colv = jax.lax.dot_general(
            bins.astype(jnp.bfloat16), colsel.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)  # [n, M]
    if feat.offset is not None:
        off = feat.offset[f_id][None, :]
        nbf = feat.num_bin[f_id][None, :]
        unfolded = jnp.where((colv >= off) & (colv <= off + nbf - 2),
                             colv - off + 1, 0)
        colv = unfolded
    mt = feat.missing_type[f_id][None, :]
    nbin = feat.num_bin[f_id][None, :]
    dbin = feat.default_bin[f_id][None, :]
    thr = tree.threshold_bin[:M][None, :]
    dleft = tree.default_left[:M][None, :]
    is_missing = jnp.where(mt == int(MissingType.NAN), colv == nbin - 1,
                           jnp.where(mt == int(MissingType.ZERO),
                                     colv == dbin, False))
    go_left = jnp.where(is_missing, dleft, colv <= thr)
    D = jnp.where(go_left, 1.0, -1.0).astype(jnp.float32)        # [n, M]
    D = D * node_valid[None, :].astype(jnp.float32)

    hits = jax.lax.dot_general(
        D, P, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                      # [n, L]
    ind = (hits == plen[None, :]).astype(jnp.float32)
    return jnp.sum(ind * tree.leaf_value[:L][None, :], axis=1)


@functools.partial(jax.jit, static_argnames=("num_leaves",))
def route_binned(bins: jax.Array, tree: TreeArrays, feat: FeatureInfo,
                 *, num_leaves: int, depth_bound=None) -> jax.Array:
    """Assign every binned row to its leaf (device Tree::GetLeaf over bins).

    ``depth_bound``: optional traced iteration bound — each loop step
    advances every row one LEVEL, so the tree's actual depth (e.g.
    ``jnp.max(tree.leaf_depth)``) suffices and is typically ~10x smaller
    than the worst-case ``num_leaves - 1`` chain."""
    n = bins.shape[0]
    node = jnp.where(tree.num_leaves > 1, 0, -1) * jnp.ones((n,), dtype=jnp.int32)

    def step(_, node):
        is_leaf = node < 0
        nd = jnp.maximum(node, 0)
        f_id = tree.split_feature[nd]
        col = jnp.take_along_axis(
            bins, _feature_column(f_id, feat)[:, None].astype(jnp.int32),
            axis=1)[:, 0].astype(jnp.int32)
        col = _unfold_bin(col, f_id, feat)
        go_left = _route_left(col, tree.threshold_bin[nd], tree.default_left[nd],
                              feat.missing_type[f_id], feat.num_bin[f_id],
                              feat.default_bin[f_id],
                              is_cat=feat.is_categorical[f_id],
                              bitset=tree.cat_bitset[nd])
        nxt = jnp.where(go_left, tree.left_child[nd], tree.right_child[nd])
        return jnp.where(is_leaf, node, nxt)

    steps = (max(num_leaves - 1, 1) if depth_bound is None
             else jnp.maximum(depth_bound, 1))
    node = jax.lax.fori_loop(0, steps, step, node)
    return jnp.where(node < 0, ~node, 0).astype(jnp.int32)


class SerialTreeLearner:
    """Host wrapper: owns device views + static metadata, compiles the build."""

    # parallel learners shard over features and take one column per feature;
    # the serial learner consumes EFB group columns directly (and packs
    # 4-bit bins two-per-byte when every group fits a nibble)
    supports_groups = True
    supports_packing = True

    def __init__(self, dataset: BinnedDataset, config) -> None:
        self.dataset = dataset
        self.config = config
        self.num_leaves = int(config.num_leaves)
        self.max_depth = int(config.max_depth)
        self.params = SplitParams(
            lambda_l1=float(config.lambda_l1),
            lambda_l2=float(config.lambda_l2),
            max_delta_step=float(config.max_delta_step),
            min_data_in_leaf=int(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
            min_gain_to_split=float(config.min_gain_to_split),
            max_cat_to_onehot=int(config.max_cat_to_onehot),
            cat_l2=float(config.cat_l2),
            cat_smooth=float(config.cat_smooth),
            max_cat_threshold=int(config.max_cat_threshold),
            min_data_per_group=int(config.min_data_per_group),
            extra_trees=bool(config.extra_trees),
            extra_seed=int(config.extra_seed),
            feature_contri=self._map_feature_contri(config, dataset))
        is_cat = dataset.feature_is_categorical()
        self.has_categorical = bool(is_cat.any())
        mono_cfg = list(getattr(config, "monotone_constraints", []) or [])
        mono = np.zeros(dataset.num_features, dtype=np.int32)
        for j, orig in enumerate(dataset.used_feature_idx):
            if orig < len(mono_cfg):
                mono[j] = int(mono_cfg[orig])
        self.monotone = mono
        self.has_monotone = bool((mono != 0).any())
        self.use_pallas = jax.default_backend() == "tpu"
        # round-7 fused-kernel dispatch: None derives the size-bucket
        # schedule from the row count (partition.fused_bucket_plan); tests
        # pin a plan and flip pallas_interpret to run the fused path off-TPU
        self.bucket_plan = None
        self.pallas_interpret = False
        if os.environ.get("LIGHTGBM_TPU_PALLAS_INTERPRET", "0") == "1":
            # force the fused Pallas path in interpret mode — the hook
            # CLI-driven child processes (fault injection, dryruns) use to
            # exercise the fused/level dispatch on a host WITHOUT a chip
            # (build_tree_partitioned refuses interpret mode on a tpu)
            self.use_pallas = True
            self.pallas_interpret = True
        # round-12 level-batched dispatch (tree_grow_mode=level): BFS growth
        # with one multi-window launch per bucket class per level; resolved
        # to the effective mode lazily (tests flip use_pallas/interpret on
        # the instance after construction)
        self.tree_grow_mode = str(getattr(config, "tree_grow_mode", "leaf")
                                  or "leaf")
        self._grow_mode_warned = False
        # round-22 quantized-gradient training (hist_precision=quantized):
        # static axis of the build; the stochastic-rounding stream is keyed
        # by (seed, iteration, original row id) — stateless like bagging,
        # so resume/replay is bit-exact without RNG state in the checkpoint
        self.hist_precision = str(getattr(config, "hist_precision", "exact")
                                  or "exact")
        self.quant_seed = int(getattr(config, "seed", 0) or 0)
        self.grouped = bool(dataset.is_bundled and self.supports_groups)
        # histogram (kernel) width is the MXU-friendly power of two; the
        # per-feature scan width stays lane-padded only when group columns
        # must be unpacked into per-feature lanes
        self.feat_bins = _pad_bins(dataset.max_num_bin)
        if self.grouped:
            self.num_bins = _pad_bins_pow2(dataset.max_group_bin)
            group = jnp.asarray(dataset.group_idx)
            offset = jnp.asarray(dataset.bin_offset)
            nb = np.asarray(dataset.num_bin_per_feature)
            lanes = np.arange(self.feat_bins, dtype=np.int32)[None, :]
            lidx = np.clip(np.asarray(dataset.bin_offset)[:, None] + lanes - 1,
                           0, self.num_bins - 1).astype(np.int32)
            lmask = ((lanes >= 1) & (lanes < nb[:, None])).astype(np.float32)
            self.unpack_lanes = (
                jnp.asarray(lidx), jnp.asarray(lmask),
                group_lanes(dataset.group_idx, dataset.bin_offset, nb,
                            dataset.missing_types(), dataset.default_bins(),
                            self.monotone, len(dataset.feature_groups),
                            self.num_bins))
        else:
            self.num_bins = _pad_bins_pow2(dataset.max_num_bin)
            self.feat_bins = self.num_bins   # scans run on the kernel block
            group = offset = None
            self.unpack_lanes = None
        self.feat = FeatureInfo(
            num_bin=jnp.asarray(dataset.num_bin_per_feature, dtype=jnp.int32),
            missing_type=jnp.asarray(dataset.missing_types()),
            default_bin=jnp.asarray(dataset.default_bins()),
            is_categorical=jnp.asarray(dataset.feature_is_categorical()),
            monotone=jnp.asarray(self.monotone),
            group=group, offset=offset)
        # rows padded so the Pallas row tile divides N
        self.num_data = dataset.num_data
        self.padded_rows = (-self.num_data) % _PCHUNK if self.use_pallas else 0
        matrix = (dataset.binned if self.grouped or not dataset.is_bundled
                  else dataset.unbundled_matrix())
        self.packed_cols = 0
        self._route_bins_cache = None
        if self.supports_packing and dataset.max_group_bin <= 16 \
                and matrix.shape[1] > 1:
            # 4-bit packing (dense_nbits_bin.hpp): two columns per byte
            self.packed_cols = matrix.shape[1]
            matrix = pack_nibbles(matrix)
        self._upload_bins(matrix)
        self.forced = self._load_forced_splits(config, dataset)
        self.cegb = self._init_cegb(config, dataset)
        _cat_counters.record_learner(
            int(is_cat.sum()),
            sum(int(dataset.bin_mappers[i].num_bin) for i, c in zip(
                dataset.used_feature_idx, is_cat) if c),
            cat_scan_steps(self.feat_bins, self.params)
            if self.has_categorical else 0, self.params.max_cat_to_onehot)
        if self.grouped:
            in_groups = group_search_applies(True, self.has_categorical,
                                             self.cegb is not None)
            _efb_counters.record_search_lanes(
                len(dataset.feature_groups) * self.num_bins if in_groups
                else dataset.num_features * self.feat_bins)
        # histogram_pool_size MB -> LRU slot count (reference HistogramPool,
        # feature_histogram.hpp:687; <=0 keeps one slot per leaf)
        pool_mb = float(getattr(config, "histogram_pool_size", -1.0))
        self.hist_pool_slots = 0
        if pool_mb > 0 and not (self.forced is None and self.cegb is None):
            from ..utils.log import Log
            Log.warning("histogram_pool_size is ignored with forced splits "
                        "or CEGB (their candidate caches need every leaf's "
                        "histogram resident); histogram memory is unbounded")
        if pool_mb > 0 and self.forced is None and self.cegb is None:
            # stored block is [f_cols, 2, num_bins] f32; MiB like the
            # reference's pool sizing
            if hasattr(self, "bins"):
                width = self.bins.shape[1]
            elif hasattr(self, "_host_bins"):
                width = self._host_bins.shape[1]
            else:
                width = 0
            f_cols = self.packed_cols or width
            if f_cols:
                slot_bytes = f_cols * 2 * self.num_bins * 4
                self.hist_pool_slots = max(
                    2, int(pool_mb * 1024 * 1024 // slot_bytes))
        self.cegb_used = (jnp.zeros((dataset.num_features,), bool)
                          if self.cegb is not None else None)
        # per-(row, feature) lazy-cost paid bits, persisted across trees
        self.cegb_paid = None
        if self.cegb is not None and self.cegb[2] is not None:
            self.cegb_paid = jnp.zeros(
                (self.num_data + self.padded_rows,
                 -(-dataset.num_features // 8)), jnp.uint8)
        # round-18 kernel planner (lightgbm_tpu/plan): ONE resolution
        # covers the fused bucket schedule AND the level ladder —
        # gbdt.py's fused-scan paths inherit both through bucket_plan.
        # An analytic plan is byte-equal to the schedule the builder
        # derives itself, so bucket_plan stays None (identical jit keys,
        # behavior-neutral by default); a tuned/pinned plan installs its
        # schedule here and is stamped into telemetry at train time.
        self.plan = None
        self._resolve_plan()

    @staticmethod
    def _map_feature_contri(config, dataset) -> tuple:
        """config.feature_contri (ORIGINAL feature order, config.h:432-436)
        -> per-used-inner-feature tuple; () when the param is unset."""
        contri = list(getattr(config, "feature_contri", []) or [])
        if not contri:
            return ()
        out = [1.0] * dataset.num_features
        for j, orig in enumerate(dataset.used_feature_idx):
            if orig < len(contri):
                out[j] = float(contri[orig])
        return tuple(out)

    def _load_forced_splits(self, config, dataset):
        """BFS schedule from forcedsplits_filename
        (serial_tree_learner.cpp:458 ForceSplits; numerical splits only)."""
        fname = str(getattr(config, "forcedsplits_filename", "") or "")
        if not fname:
            return None
        import json as _json
        import os as _os
        if not _os.path.exists(fname):
            from ..utils.log import Log
            Log.warning("Forced splits file %s does not exist", fname)
            return None
        with open(fname) as fh:
            spec = _json.load(fh)
        sched = []
        queue = [(spec, 0)]
        while queue and len(sched) < self.num_leaves - 1:
            node, leaf = queue.pop(0)
            orig = int(node.get("feature", -1))
            inner = dataset.inner_feature_map.get(orig)
            if inner is None or \
                    dataset.bin_mappers[orig].bin_type == BinType.CATEGORICAL:
                from ..utils.log import Log
                Log.warning("Forced split on unusable feature %d; dropping the "
                            "rest of the forced-splits schedule", orig)
                break
            thr_bin = int(dataset.bin_mappers[orig].values_to_bins(
                np.asarray([float(node["threshold"])]))[0])
            step = len(sched) + 1
            sched.append((leaf, inner, thr_bin))
            if "left" in node:
                queue.append((node["left"], leaf))
            if "right" in node:
                queue.append((node["right"], step))
        if not sched:
            return None
        arr = np.asarray(sched, dtype=np.int32)
        return (jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1]),
                jnp.asarray(arr[:, 2]))

    def _init_cegb(self, config, dataset):
        """(tradeoff*penalty_split, tradeoff*coupled [F], tradeoff*lazy [F]
        or None) when CEGB is active
        (cost_effective_gradient_boosting.hpp:25-31 IsEnable)."""
        tr = float(config.cegb_tradeoff)
        ps = float(config.cegb_penalty_split)
        coupled_cfg = list(config.cegb_penalty_feature_coupled or [])
        lazy_cfg = list(config.cegb_penalty_feature_lazy or [])
        if ps <= 0.0 and not any(coupled_cfg) and not any(lazy_cfg):
            return None
        from ..utils.log import Log
        if coupled_cfg and len(coupled_cfg) != dataset.num_total_features:
            Log.fatal("cegb_penalty_feature_coupled should be the same size "
                      "as feature number.")
        if lazy_cfg and len(lazy_cfg) != dataset.num_total_features:
            Log.fatal("cegb_penalty_feature_lazy should be the same size "
                      "as feature number.")
        coupled = np.zeros(dataset.num_features, dtype=np.float32)
        lazy = np.zeros(dataset.num_features, dtype=np.float32)
        for j, orig in enumerate(dataset.used_feature_idx):
            if orig < len(coupled_cfg):
                coupled[j] = tr * float(coupled_cfg[orig])
            if orig < len(lazy_cfg):
                lazy[j] = tr * float(lazy_cfg[orig])
        return (jnp.float32(tr * ps), jnp.asarray(coupled),
                jnp.asarray(lazy) if lazy.any() else None)

    def _pad_host_rows(self, binned: np.ndarray) -> np.ndarray:
        if self.padded_rows:
            binned = np.concatenate(
                [binned, np.zeros((self.padded_rows, binned.shape[1]),
                                  dtype=binned.dtype)])
        return binned

    def _upload_bins(self, binned: np.ndarray) -> None:
        # host pad + transfer of the binned table, waited for so that the
        # span holds the transfer: the ingest share of booster set-up
        with _span("ingest.upload"):
            self.bins = jnp.asarray(self._pad_host_rows(binned))
            self.bins.block_until_ready()

    # where a [rows] array lives when the learner shards its rows over a
    # mesh (parallel/learners.py); one device holds them all here
    row_sharding = None

    def pad_rows(self, arr: jax.Array, value=0.0) -> jax.Array:
        """Pad a per-row array up to num_data + padded_rows (idempotent)."""
        short = self.num_data + self.padded_rows - arr.shape[0]
        if short > 0:
            pad_width = [(0, short)] + [(0, 0)] * (arr.ndim - 1)
            return jnp.pad(arr, pad_width, constant_values=value)
        return arr

    def _resolve_plan(self) -> None:
        """Consume the kernel planner (plan/state.py: pinned > tuned
        cache > analytic).  Only a non-analytic plan changes anything:
        its ladder is installed as the trace-static ``bucket_plan``
        (level mode consumes the plan's level ladder — same object
        analytically).  Never raises: planning failures degrade to the
        derived-in-builder schedule."""
        try:
            from ..plan import state as _plan_state
            if hasattr(self, "bins"):
                n = int(self.bins.shape[0])
                bpc = 2 if self.bins.dtype == jnp.uint16 else 1
            else:
                n = int(self.num_data + self.padded_rows)
                bpc = 2 if self.num_bins > 256 else 1
            self.plan = _plan_state.resolve(
                n, int(self.dataset.num_features), int(self.num_bins),
                bpc=bpc, packed=bool(self.packed_cols),
                num_class=int(getattr(self.config, "num_class", 1) or 1),
                quantized=self.hist_precision == "quantized")
            if self.plan.provenance != "analytic" \
                    and self.bucket_plan is None:
                ladder = (self.plan.level_ladder
                          if self.tree_grow_mode == "level"
                          else self.plan.bucket_plan)
                self.bucket_plan = tuple(ladder)
        except Exception:  # noqa: BLE001 - planner must never fail a build
            self.plan = None

    def effective_grow_mode(self) -> str:
        """The growth mode this learner's builds actually run: ``level``
        only when the fused Pallas path is live and no leaf-wise-only
        feature (forced splits, CEGB, histogram pooling, parallel comm) is
        active; anything else falls back to ``leaf`` with one warning."""
        if self.tree_grow_mode != "level":
            return "leaf"
        blockers = []
        if not self.use_pallas:
            blockers.append("non-TPU backend (fused Pallas path required)")
        if getattr(self, "comm", None) is not None:
            blockers.append("parallel tree learner")
        if self.forced is not None:
            blockers.append("forced splits")
        if self.cegb is not None:
            blockers.append("CEGB")
        if self.hist_pool_slots:
            blockers.append("histogram_pool_size")
        if blockers:
            if not self._grow_mode_warned:
                from ..utils.log import Log
                Log.warning("tree_grow_mode=level unavailable (%s); growing "
                            "leaf-wise", "; ".join(blockers))
                self._grow_mode_warned = True
            self._sync_plan_ladder("leaf")
            return "leaf"
        self._sync_plan_ladder("level")
        return "level"

    def _sync_plan_ladder(self, mode: str) -> None:
        """Keep a PLANNER-installed bucket_plan aligned with the mode that
        actually dispatches: construction installs the ladder for the
        CONFIGURED grow mode, but the effective mode can degrade (or be
        test-flipped) afterwards, and a tuned cache may legally carry
        different leaf vs level ladders.  Only a schedule this planner
        installed is swapped — a directly-pinned bucket_plan (tests, the
        autotuner) is never touched."""
        plan = self.plan
        if plan is None or plan.provenance == "analytic" \
                or self.bucket_plan is None:
            return
        ladders = (tuple(plan.bucket_plan), tuple(plan.level_ladder))
        if self.bucket_plan not in ladders:
            return  # pinned by hand, not by the planner
        want = ladders[1] if mode == "level" else ladders[0]
        if self.bucket_plan != want:
            self.bucket_plan = want

    def level_classes(self) -> int:
        """Bucket-class count of the level-batched dispatch schedule."""
        plan = (self.bucket_plan if self.bucket_plan is not None
                else fused_bucket_plan(self.bins.shape[0]))
        return len(plan)

    def level_count(self) -> int:
        """Static level-schedule length of tree_grow_mode=level builds
        (same leaf-budget cap as the builder's schedule)."""
        return (min(self.max_depth, self.num_leaves - 1)
                if self.max_depth > 0
                else max(1, int(np.ceil(np.log2(self.num_leaves)))))

    def launches_per_tree(self) -> int:
        """Split-dispatch launches one tree build issues: L-1 leaf-wise
        (one fused split pass per grown leaf), levels * bucket-classes in
        level mode — the quantity the always-on ``tree_kernel_launches``
        counter (obs/launches.py) accumulates."""
        if self.effective_grow_mode() == "level":
            return self.level_count() * self.level_classes()
        return self.num_leaves - 1

    def train(self, grad: jax.Array, hess: jax.Array,
              num_data_in_bag, feature_mask: Optional[jax.Array] = None,
              iteration=0) -> TreeArrays:
        """grad/hess: [N] f32 already weighted/bagged (padded rows zero).

        ``iteration`` keys the quantized path's stochastic-rounding hash
        (ignored under hist_precision=exact); a traced or host scalar."""
        if feature_mask is None:
            feature_mask = jnp.ones((self.dataset.num_features,), dtype=bool)
        grad = self.pad_rows(grad)
        hess = self.pad_rows(hess)
        cegb = (None if self.cegb is None
                else (self.cegb[0], self.cegb[1], self.cegb_used,
                      self.cegb[2]))
        lazy_active = cegb is not None and cegb[3] is not None
        from ..obs import active as _telemetry_active
        from ..obs import launches as _launches
        grow_mode = self.effective_grow_mode()
        _launches.record(grow_mode, self.launches_per_tree())
        # tree-build span (host dispatch wall) carrying the level-dispatch
        # structure: a tree build is ONE compiled program, so per-level
        # host timing does not exist — the launch gauge and these fields
        # are the honest per-level signal.  Guarded like every hot-path
        # site: a traced caller (parallel learners' shard_map build) and a
        # telemetry-off run both skip it entirely.
        tele = _telemetry_active()
        span_ctx = contextlib.nullcontext()
        if tele is not None and not isinstance(grad, jax.core.Tracer):
            from ..obs import spans as _spans
            fields = dict(mode=grow_mode,
                          launches=int(self.launches_per_tree()))
            if grow_mode == "level":
                fields.update(levels=self.level_count(),
                              classes=self.level_classes())
            span_ctx = _spans.Span(tele, "tree_build", tele.trace_id,
                                   None, fields)
            # plan provenance (round 18): a directly-pinned bucket_plan
            # (tests, the autotuner's candidate sweeps) reports "pinned"
            # even though the resolved plan was analytic — the stamp
            # records what actually dispatched
            from ..plan import state as _plan_state
            prov = (self.plan.provenance if self.plan is not None
                    else "analytic")
            if self.bucket_plan is not None and prov == "analytic":
                prov = "pinned"
            _plan_state.stamp(tele, "tree_build", prov,
                              key="n%d_b%d" % (self.num_data, self.num_bins),
                              mode=grow_mode)
        with span_ctx, _span("partition_build_tree"):
            out = build_tree_partitioned(
                self.bins, grad, hess,
                jnp.asarray(num_data_in_bag, dtype=jnp.int32),
                feature_mask, self.feat,
                num_leaves=self.num_leaves, max_depth=self.max_depth,
                params=self.params, num_bins=self.num_bins,
                use_pallas=self.use_pallas,
                has_categorical=self.has_categorical,
                has_monotone=self.has_monotone,
                feat_num_bins=self.feat_bins,
                unpack_lanes=self.unpack_lanes,
                forced=self.forced, cegb=cegb,
                paid_bits=(self.cegb_paid if lazy_active else None),
                packed_cols=self.packed_cols,
                hist_pool_slots=self.hist_pool_slots,
                bucket_plan=self.bucket_plan,
                pallas_interpret=self.pallas_interpret,
                tree_grow_mode=grow_mode,
                hist_precision=self.hist_precision,
                quant_it=jnp.asarray(iteration, jnp.int32),
                quant_seed=self.quant_seed)
        if lazy_active:
            # per-(row, feature) paid bits live for the whole training
            # (feature_used_in_data_)
            arrays, self.cegb_paid = out
        else:
            arrays = out
        self._update_cegb_used(arrays)
        return arrays

    def _update_cegb_used(self, arrays: TreeArrays) -> None:
        """Persist feature-used state across trees
        (is_feature_used_in_split_ lives for the whole training)."""
        if self.cegb is None:
            return
        valid = jnp.arange(self.num_leaves) < (arrays.num_leaves - 1)
        self.cegb_used = self.cegb_used.at[arrays.split_feature].max(valid)

    def row_layout(self) -> dict:
        """Byte offsets of the combined row store (mirrors
        build_tree_partitioned's layout) for carried-mode consumers."""
        bpc = 2 if self.bins.dtype == jnp.uint16 else 1
        ncols = self.bins.shape[1]
        voff = -(-(ncols * bpc) // 4) * 4
        n = self.bins.shape[0]
        fused = self.use_pallas
        return {"voff": voff, "soff": voff + 16,
                "n_arr": n + (_PCHUNK if fused else 0)}

    def route_bins_matrix(self) -> jax.Array:
        """Training bins with one column per group column (unpacked view for
        route_binned consumers: DART drops, model replay).  Cached."""
        if not self.packed_cols:
            return self.bins
        if self._route_bins_cache is None:
            from .histogram import unpack_nibbles
            self._route_bins_cache = unpack_nibbles(self.bins,
                                                    self.packed_cols)
        return self._route_bins_cache

    def valid_bins(self, dataset: BinnedDataset) -> np.ndarray:
        """Binned matrix of a validation set in this learner's layout."""
        if self.grouped or not dataset.is_bundled:
            return dataset.binned
        return dataset.unbundled_matrix()

    # ---- host tree construction ----

    def host_tree(self, arrays: TreeArrays, shrinkage: float = 1.0) -> Tree:
        return tree_from_arrays(arrays, self.dataset, shrinkage)


def tree_from_arrays(arrays: TreeArrays, dataset: BinnedDataset,
                     shrinkage: float = 1.0) -> Tree:
    """Convert device tree arrays to a host :class:`Tree` with real thresholds."""
    a = jax.tree_util.tree_map(np.asarray, arrays)
    nl = int(a.num_leaves)
    t = Tree(max_leaves=max(nl, 1))
    t.num_leaves = nl
    ni = max(nl - 1, 0)
    mappers = [dataset.bin_mappers[i] for i in dataset.used_feature_idx]
    for node in range(ni):
        inner = int(a.split_feature[node])
        m = mappers[inner]
        t.split_feature_inner[node] = inner
        t.split_feature[node] = dataset.used_feature_idx[inner]
        if m.bin_type == BinType.CATEGORICAL:
            # device bin-bitset -> category-value bitset
            # (tree.h:83 SplitCategorical; Common::ConstructBitset)
            words = np.asarray(a.cat_bitset[node], dtype=np.uint32)
            bins_set = [b for b in range(words.size * 32)
                        if (words[b >> 5] >> (b & 31)) & 1]
            cats = sorted(int(m.bin_2_categorical[b]) for b in bins_set
                          if b < len(m.bin_2_categorical))
            nw_in = max(bins_set, default=0) // 32 + 1
            t.cat_boundaries_inner.append(t.cat_boundaries_inner[-1] + nw_in)
            t.cat_threshold_inner.extend(int(words[w]) for w in range(nw_in))
            nw = (max(cats, default=0) // 32) + 1
            cwords = [0] * nw
            for c in cats:
                cwords[c >> 5] |= 1 << (c & 31)
            t.threshold_in_bin[node] = t.num_cat
            t.threshold[node] = float(t.num_cat)
            t.cat_boundaries.append(t.cat_boundaries[-1] + nw)
            t.cat_threshold.extend(cwords)
            t.num_cat += 1
            _cat_counters.record_split(int(m.num_bin))
        else:
            t.threshold_in_bin[node] = int(a.threshold_bin[node])
            t.threshold[node] = m.bin_to_value(int(a.threshold_bin[node]))
        t.decision_type[node] = Tree.make_decision_type(
            m.bin_type == BinType.CATEGORICAL, bool(a.default_left[node]),
            int(m.missing_type))
    t.split_gain[:ni] = a.split_gain[:ni]
    t.left_child[:ni] = a.left_child[:ni]
    t.right_child[:ni] = a.right_child[:ni]
    t.internal_value[:ni] = a.internal_value[:ni]
    t.internal_weight[:ni] = a.internal_weight[:ni]
    t.internal_count[:ni] = np.round(a.internal_count[:ni]).astype(np.int64)
    t.leaf_value[:nl] = a.leaf_value[:nl]
    t.leaf_weight[:nl] = a.leaf_weight[:nl]
    t.leaf_count[:nl] = np.round(a.leaf_count[:nl]).astype(np.int64)
    t.leaf_parent[:nl] = a.leaf_parent[:nl]
    t.leaf_depth[:nl] = a.leaf_depth[:nl]
    if shrinkage != 1.0:
        t.shrink(shrinkage)
    return t
