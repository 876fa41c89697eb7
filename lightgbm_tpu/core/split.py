"""Vectorized best-split search over feature histograms.

Counterpart of the reference ``FeatureHistogram::FindBestThreshold`` family
(src/treelearner/feature_histogram.hpp:84-304,440-680).  Where the reference scans
each feature's bins twice in serial loops (left->right and right->left to place the
missing-value default direction), this evaluates every (feature, threshold,
direction) candidate at once with prefix sums over the bin axis — the natural
formulation for the VPU, and one fused XLA program per leaf.

Semantics preserved from the reference:
- two directions only when the feature has a missing bin and >2 bins
  (feature_histogram.hpp:102-131); missing data implicitly follows the side that is
  computed as leaf_total - accumulated (":548,:614 skip default bin" trick);
- for MissingType.ZERO the default(zero) bin is excluded from both accumulations and
  its threshold position is not a candidate (:548,:614);
- for MissingType.NAN the last bin holds NaN and is excluded from the accumulated
  side (:542 ``use_na_as_missing``); with <=2 bins default_left=false (:128-130);
- bin counts estimated from hessians via ``cnt_factor = num_data/sum_hess``
  (:535,:601);
- gain math with L1 thresholding, L2, max_delta_step clamp (:463-527);
- validity: min_data_in_leaf / min_sum_hessian_in_leaf on both sides, gain strictly
  above parent gain + min_gain_to_split (:559-575); reported gain is the improvement
  (:114 ``output->gain -= min_gain_shift``);
- tie-breaking: the missing-left scan wins ties, larger thresholds win ties in the
  missing-left scan, smaller in the other (strict-``>`` update order of :579,:641),
  smaller feature index wins across features (split_info.hpp:185 comparators).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import MissingType
from ..obs import scopes as _scopes

K_EPSILON = 1e-15  # meta.h:51
K_MIN_SCORE = -jnp.inf


def dequantize_hist(hist: jax.Array, qscale: jax.Array) -> jax.Array:
    """Rescale an integer-valued quantized histogram back to real sums.

    ``hist[..., 2, B]`` holds per-bin integer sums (channel 0 = grad,
    channel 1 = hess) accumulated from quantized gradients; ``qscale`` is
    the ``(s_g, s_h)`` pair from ``quant.quantize_gradients``.  Works for
    any leading layout — ``[F, 2, B]``, ``[G, F, 2, B]``, or the
    psum_scatter-sharded ``[F/d, 2, B]`` — because the channel axis is
    always second-to-last.  Split-gain math downstream (this module) then
    runs on real-valued sums unchanged."""
    return hist * qscale.reshape((1,) * (hist.ndim - 2) + (2, 1))


class SplitParams(NamedTuple):
    """Static (trace-time) learner hyperparameters."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    max_delta_step: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    # categorical (config.h:600-640)
    max_cat_to_onehot: int = 4
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    min_data_per_group: int = 100
    # extremely-randomized trees (config.h:318): numerical features consider
    # ONE random threshold per (feature, leaf) instead of scanning every bin
    extra_trees: bool = False
    extra_seed: int = 6
    # per-feature split-gain scaling, inner-feature order (config.h:432-436:
    # gain[i] = max(0, feature_contri[i]) * gain[i]); () == disabled.  A
    # tuple so SplitParams stays hashable/static; learners index it by
    # GLOBAL inner feature id (see tree_learner's _apply_contri)
    feature_contri: tuple = ()


class FeatureInfo(NamedTuple):
    """Per-used-feature static metadata (device arrays, [F])."""
    num_bin: jax.Array       # i32
    missing_type: jax.Array  # i32 (MissingType)
    default_bin: jax.Array   # i32
    is_categorical: jax.Array  # bool
    monotone: jax.Array = None  # i32 in {-1, 0, +1}; None == unconstrained
    # EFB bundling (dataset.cpp:92-290): the binned matrix column of each
    # feature and its first group code; None == one column per feature
    group: jax.Array = None  # i32 [F] -> group column
    offset: jax.Array = None  # i32 [F] first group code of bin 1


class BestSplit(NamedTuple):
    """Per-leaf best split candidate (scalars + a [W] bin bitset for
    categorical many-vs-many splits; all-zero for numerical)."""
    gain: jax.Array          # improvement over parent (-inf if none)
    feature: jax.Array       # inner feature index, i32
    threshold: jax.Array     # bin threshold (left: bin <= threshold), i32
    default_left: jax.Array  # bool
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array    # f32 (estimated like the reference)
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array
    cat_bitset: jax.Array    # [B//32] u32; bins going LEFT (categorical only)


class FeatureBest(NamedTuple):
    """Best split of every feature (all [F] arrays) — the device analogue of the
    per-feature ``SplitInfo`` array the reference keeps per leaf
    (serial_tree_learner.cpp:399 best_split_per_leaf_); exposing it lets the
    parallel learners shard the scan (data_parallel_tree_learner.cpp:167) and vote
    on top-k features (voting_parallel_tree_learner.cpp:170)."""
    gain: jax.Array
    threshold: jax.Array
    default_left: jax.Array
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array
    cat_bitset: jax.Array    # [F, B//32] u32


def _avalanche_u32(x):
    """xxhash-style integer avalanche (the same mixer as gbdt._bag_uniforms,
    kept local to avoid a core -> boosting import)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(3266489917)
    return x ^ (x >> 16)


def _extra_trees_draw(num_bin, sum_grad, sum_hess, params: SplitParams,
                      fid=None):
    """One random candidate threshold per (feature, leaf) — the reference's
    ``rand_threshold_gen_`` draw under ``extra_trees`` (config.h:318,
    feature_histogram.hpp use_rand_threshold), for the features ``fid`` (any
    shape; every feature in order when None) of ``num_bin`` bins.  The draw
    is a stateless hash
    of (extra_seed, feature index, leaf-total bits), so it is deterministic
    for a given dataset/seed yet varies across leaves and trees — a
    sequential RNG stream would not survive the vmapped per-leaf scan or
    the fused multi-iteration lax.scan."""
    f32 = jnp.float32
    salt = (jax.lax.bitcast_convert_type(
        sum_grad.astype(f32), jnp.int32).astype(jnp.uint32)
        ^ (jax.lax.bitcast_convert_type(
            sum_hess.astype(f32), jnp.int32).astype(jnp.uint32) << 1))
    if fid is None:
        fid = jnp.arange(num_bin.shape[0], dtype=jnp.uint32)
    x = fid * jnp.uint32(2654435761)
    x = x ^ (salt + jnp.uint32(params.extra_seed & 0xFFFFFFFF)
             * jnp.uint32(0x9E3779B9))
    x = _avalanche_u32(x)
    # thresholds live in [0, nb - 2] (bin <= t goes left)
    ncand = jnp.maximum(num_bin - 1, 1).astype(jnp.uint32)
    return jax.lax.rem(x, ncand).astype(jnp.int32)


def threshold_l1(s, l1):
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def calculate_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step):
    ret = -threshold_l1(sum_grad, l1) / (sum_hess + l2)
    if max_delta_step > 0.0:
        ret = jnp.clip(ret, -max_delta_step, max_delta_step)
    return ret


def leaf_split_gain_given_output(sum_grad, sum_hess, l1, l2, output):
    sg_l1 = threshold_l1(sum_grad, l1)
    return -(2.0 * sg_l1 * output + (sum_hess + l2) * output * output)


def leaf_split_gain(sum_grad, sum_hess, l1, l2, max_delta_step):
    out = calculate_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step)
    return leaf_split_gain_given_output(sum_grad, sum_hess, l1, l2, out)


def _split_gains(gl, hl, gr, hr, p: SplitParams):
    lo = calculate_leaf_output(gl, hl, p.lambda_l1, p.lambda_l2, p.max_delta_step)
    ro = calculate_leaf_output(gr, hr, p.lambda_l1, p.lambda_l2, p.max_delta_step)
    gain = (leaf_split_gain_given_output(gl, hl, p.lambda_l1, p.lambda_l2, lo)
            + leaf_split_gain_given_output(gr, hr, p.lambda_l1, p.lambda_l2, ro))
    return gain, lo, ro


def _evaluate_candidates(gl, hl, cl, gr, hr, cr, valid, params: SplitParams,
                         min_gain_shift, cmin, cmax, mono):
    """(gain or -inf, left output, right output) of every candidate from its
    two sides' sums: the minimums on both sides, outputs clamped into the
    leaf's bounds, the monotone ordering (``mono`` in {-1, 0, +1} a
    candidate, or None) and gain strictly above the parent's
    (feature_histogram.hpp:559-575)."""
    ok = (valid
          & (cl >= params.min_data_in_leaf) & (cr >= params.min_data_in_leaf)
          & (hl >= params.min_sum_hessian_in_leaf)
          & (hr >= params.min_sum_hessian_in_leaf))
    gain, lo, ro = _split_gains_clamped(gl, hl, gr, hr, params,
                                        params.lambda_l2, cmin, cmax)
    if mono is not None:
        ok &= ~(((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro)))
    ok &= gain > min_gain_shift
    return jnp.where(ok, gain, K_MIN_SCORE), lo, ro


def per_feature_best(hist: jax.Array, feat: FeatureInfo, feature_mask: jax.Array,
                     sum_grad: jax.Array, sum_hess: jax.Array,
                     num_data: jax.Array, params: SplitParams,
                     cmin=None, cmax=None,
                     threshold_mask=None) -> FeatureBest:
    """Best numerical split of EACH feature of one leaf (all outputs [F]).

    hist: [F, 2, B] f32; feature_mask: [F] bool (feature_fraction);
    sum_grad/sum_hess/num_data: leaf totals (scalars); cmin/cmax: the leaf's
    monotone-constraint bounds (monotone_constraints.hpp ConstraintEntry) —
    outputs are clamped into [cmin, cmax] and candidates on monotone features
    that violate the ordering are discarded (feature_histogram.hpp:468-527).
    ``threshold_mask`` [B] restricts the candidate thresholds — used to gather
    the stats of one FORCED threshold (feature_histogram.hpp:306
    GatherInfoForThreshold).
    """
    F, _, B = hist.shape
    g = hist[:, 0, :]
    h = hist[:, 1, :]
    total_h = sum_hess + 2 * K_EPSILON  # feature_histogram.hpp:88
    total_g = sum_grad
    num_data_f = num_data.astype(jnp.float32)
    cnt_factor = num_data_f / total_h
    c = jnp.round(h * cnt_factor)

    nb = feat.num_bin[:, None]                      # [F, 1]
    t = jnp.arange(B, dtype=jnp.int32)[None, :]     # [1, B] threshold candidates
    mt = feat.missing_type[:, None]
    is_def = t == feat.default_bin[:, None]
    is_nan_bin = t == nb - 1

    with jax.named_scope(_scopes.FIND_SCAN):
        pre_g = jnp.cumsum(g, axis=1)
        pre_h = jnp.cumsum(h, axis=1)
        pre_c = jnp.cumsum(c, axis=1)
        g_nz = jnp.where(is_def, 0.0, g)
        h_nz = jnp.where(is_def, 0.0, h)
        c_nz = jnp.where(is_def, 0.0, c)
        pre_g_nz = jnp.cumsum(g_nz, axis=1)
        pre_h_nz = jnp.cumsum(h_nz, axis=1)
        pre_c_nz = jnp.cumsum(c_nz, axis=1)
    # totals over data bins only (padded bins hold zeros)
    tot = lambda a: a[:, -1:]
    # totals excluding the NaN bin (last data bin is nb-2)
    last_data = jnp.clip(nb - 2, 0, B - 1)
    at = lambda a, idx: jnp.take_along_axis(a, idx, axis=1)
    tot_nonan = lambda a: at(a, last_data)

    has_missing = (mt != int(MissingType.NONE)) & (nb > 2)
    is_nan_mode = mt == int(MissingType.NAN)
    is_zero_mode = mt == int(MissingType.ZERO)

    with jax.named_scope(_scopes.FIND_GAIN):
        # ---------- direction 0: missing/default LEFT (reference dir=-1 scan) ----------
        right_g0 = jnp.where(has_missing & is_nan_mode, tot_nonan(pre_g) - pre_g,
                    jnp.where(has_missing & is_zero_mode, tot(pre_g_nz) - pre_g_nz,
                              tot(pre_g) - pre_g))
        right_h0 = jnp.where(has_missing & is_nan_mode, tot_nonan(pre_h) - pre_h,
                    jnp.where(has_missing & is_zero_mode, tot(pre_h_nz) - pre_h_nz,
                              tot(pre_h) - pre_h)) + K_EPSILON
        right_c0 = jnp.where(has_missing & is_nan_mode, tot_nonan(pre_c) - pre_c,
                    jnp.where(has_missing & is_zero_mode, tot(pre_c_nz) - pre_c_nz,
                              tot(pre_c) - pre_c))
        left_g0 = total_g - right_g0
        left_h0 = total_h - right_h0
        left_c0 = num_data_f - right_c0
        # valid threshold range: t <= nb-2 always; t <= nb-3 when NaN two-dir;
        # zero-mode cannot place a threshold at default_bin - 1 (:548 skip -> t-1)
        valid0 = t <= nb - 2
        valid0 &= jnp.where(has_missing & is_nan_mode, t <= nb - 3, True)
        valid0 &= jnp.where(has_missing & is_zero_mode,
                            t != feat.default_bin[:, None] - 1, True)

        # ---------- direction 1: missing/default RIGHT (reference dir=+1 scan) --------
        left_g1 = jnp.where(is_zero_mode, pre_g_nz, pre_g)
        left_h1 = jnp.where(is_zero_mode, pre_h_nz, pre_h) + K_EPSILON
        left_c1 = jnp.where(is_zero_mode, pre_c_nz, pre_c)
        right_g1 = total_g - left_g1
        right_h1 = total_h - left_h1
        right_c1 = num_data_f - left_c1
        valid1 = has_missing & (t <= nb - 2)
        valid1 &= jnp.where(is_zero_mode, ~is_def, True)

        gain_shift = leaf_split_gain(total_g, total_h, params.lambda_l1,
                                     params.lambda_l2, params.max_delta_step)
        min_gain_shift = gain_shift + params.min_gain_to_split

        mono = (feat.monotone[:, None]
                if cmin is not None and feat.monotone is not None else None)

        def evaluate(gl, hl, cl, gr, hr, cr, valid):
            return _evaluate_candidates(gl, hl, cl, gr, hr, cr, valid, params,
                                        min_gain_shift, cmin, cmax, mono)

        if threshold_mask is not None:
            valid0 = valid0 & threshold_mask[None, :]
            valid1 = valid1 & threshold_mask[None, :]
        elif params.extra_trees:
            # forced splits (threshold_mask) bypass the randomization, matching
            # the reference's GatherInfoForThreshold
            et_mask = t == _extra_trees_draw(feat.num_bin, sum_grad, sum_hess,
                                             params)[:, None]
            valid0 = valid0 & et_mask
            valid1 = valid1 & et_mask
        gain0, lo0, ro0 = evaluate(left_g0, left_h0, left_c0,
                                   right_g0, right_h0, right_c0, valid0)
        gain1, lo1, ro1 = evaluate(left_g1, left_h1, left_c1,
                                   right_g1, right_h1, right_c1, valid1)

        fm = feature_mask & ~feat.is_categorical
        gain0 = jnp.where(fm[:, None], gain0, K_MIN_SCORE)
        gain1 = jnp.where(fm[:, None], gain1, K_MIN_SCORE)

    with jax.named_scope(_scopes.FIND_PICK):
        # per-feature argmax with reference tie-breaking
        idx0 = (B - 1) - jnp.argmax(gain0[:, ::-1], axis=1)   # largest t wins ties
        best0 = jnp.take_along_axis(gain0, idx0[:, None], axis=1)[:, 0]
        idx1 = jnp.argmax(gain1, axis=1)                      # smallest t wins ties
        best1 = jnp.take_along_axis(gain1, idx1[:, None], axis=1)[:, 0]
        use1 = best1 > best0                                  # dir0 wins ties
        feat_gain = jnp.where(use1, best1, best0)
        feat_thr = jnp.where(use1, idx1, idx0).astype(jnp.int32)

        # with <=2 bins and NaN missing, the single scan reports default_left = false
        # (feature_histogram.hpp:128-130)
        two_bin_nan = (mt[:, 0] == int(MissingType.NAN)) & (feat.num_bin <= 2)
        feat_default_left = ~use1 & ~two_bin_nan

        fidx = jnp.arange(F)

        def pick(arr0, arr1):
            return jnp.where(use1, arr1[fidx, feat_thr], arr0[fidx, feat_thr])

        found = feat_gain > K_MIN_SCORE
        return FeatureBest(
            gain=jnp.where(found, feat_gain - min_gain_shift, K_MIN_SCORE),
            threshold=feat_thr,
            default_left=feat_default_left,
            left_sum_grad=pick(left_g0, left_g1),
            left_sum_hess=pick(left_h0, left_h1) - K_EPSILON,
            left_count=pick(left_c0, left_c1),
            right_sum_grad=pick(right_g0, right_g1),
            right_sum_hess=pick(right_h0, right_h1) - K_EPSILON,
            right_count=pick(right_c0, right_c1),
            left_output=jnp.where(use1, lo1[fidx, feat_thr], lo0[fidx, feat_thr]),
            right_output=jnp.where(use1, ro1[fidx, feat_thr], ro0[fidx, feat_thr]),
            cat_bitset=jnp.zeros((F, B // 32), dtype=jnp.uint32),
        )


def _bits_to_words(bits: jax.Array) -> jax.Array:
    """[..., B] bool -> [..., B//32] u32 bitset words."""
    shape = bits.shape[:-1]
    B = bits.shape[-1]
    w = bits.reshape(shape + (B // 32, 32)).astype(jnp.uint32)
    return (w << jnp.arange(32, dtype=jnp.uint32)).sum(axis=-1, dtype=jnp.uint32)


def cat_scan_steps(num_bins: int, params: SplitParams) -> int:
    """Sorted positions a direction that the many-vs-many search of
    :func:`per_feature_best_categorical` walks over ``num_bins`` bins."""
    return max(min(int(num_bins), int(params.max_cat_threshold)), 0)


def _in_scan_order(x: jax.Array, start: float) -> jax.Array:
    """Running sums of ``x`` ``[2, steps, F]`` along its second axis, from
    ``start``: one add a position in the walk's own order (a ``cumsum`` is
    free to associate otherwise and would round differently)."""
    total = jnp.full(x.shape[:1] + x.shape[2:], start, x.dtype)
    sums = []
    for i in range(x.shape[1]):
        total = total + x[:, i]
        sums.append(total)
    return jnp.stack(sums, axis=1)


def per_feature_best_categorical(hist: jax.Array, feat: FeatureInfo,
                                 feature_mask: jax.Array, sum_grad: jax.Array,
                                 sum_hess: jax.Array, num_data: jax.Array,
                                 params: SplitParams,
                                 cmin=None, cmax=None) -> FeatureBest:
    """Best categorical split of each feature
    (feature_histogram.hpp:136-304 FindBestThresholdCategorical).

    One-hot mode for features with <= max_cat_to_onehot bins; otherwise the
    sorted many-vs-many search: bins with count >= cat_smooth sorted by
    grad/(hess+cat_smooth), walked from both ends up to max_cat_threshold
    with the min_data_per_group batching.  Resulting left-bin sets are
    returned as bitsets.

    The sorted search is straight-line array code: no loop and no indexing
    by data.  ONE stable sort carries ``g``, ``h``, the counts and the bin
    index into sorted order.  A direction walks at most
    :func:`cat_scan_steps` positions, ``min(B, max_cat_threshold)`` (32 of
    256 under the defaults; a position is ``active`` only while ``i <
    min(max_cat_threshold, (used + 1) // 2)``, so later ones change
    nothing), and both directions' positions are taken once as a
    ``[2, steps, F]`` window: the forward one a static slice, the backward
    one, which starts at the data's ``used - 1``, selected by comparison.
    On the window the reference's serial walk unrolls: the left sums are
    running sums in the walk's order; the walk stops after the first active
    position whose right side is too small; the ``min_data_per_group``
    batching is the one true recurrence, a compare-select a position; every
    position's candidate is priced at once and the first maximum wins (the
    serial walk's strict ``>``).  The winner's bins are marked by comparing
    its window's bin indices with every bin.  ``tests/
    test_categorical_table.py`` holds every field to the serial walk bit
    for bit.

    Its own named scopes, inside the caller's ``tree.find_split`` /
    ``tree.root``: ``find.cat_onehot`` (one category against the rest),
    ``find.cat_sort`` (the key, the sort, the two windows, and the winning
    prefix marked in bin order and packed into words) and ``find.cat_scan``
    (the walk of the two windows and the left sums at the winner)."""
    F, _, B = hist.shape
    p = params
    steps = cat_scan_steps(B, p)
    g = hist[:, 0, :]
    h = hist[:, 1, :]
    total_h = sum_hess + 2 * K_EPSILON
    total_g = sum_grad
    num_data_f = num_data.astype(jnp.float32)
    cnt_factor = num_data_f / total_h
    cnt = jnp.round(h * cnt_factor)

    is_full = feat.missing_type == int(MissingType.NONE)
    used_bin = feat.num_bin - 1 + is_full.astype(jnp.int32)     # [F]
    t = jnp.arange(B, dtype=jnp.int32)[None, :]
    in_range = t < used_bin[:, None]

    gain_shift = leaf_split_gain(total_g, total_h, p.lambda_l1, p.lambda_l2,
                                 p.max_delta_step)
    min_gain_shift = gain_shift + p.min_gain_to_split
    use_onehot = feat.num_bin <= p.max_cat_to_onehot                # [F]

    # ---------- one-hot: category t vs rest (:157-189) ----------
    fidx = jnp.arange(F)
    with jax.named_scope(_scopes.FIND_CAT_ONEHOT):
        other_g = total_g - g
        other_h = total_h - h - K_EPSILON
        other_cnt = num_data_f - cnt
        ok1 = (in_range & (cnt >= p.min_data_in_leaf)
               & (h >= p.min_sum_hessian_in_leaf)
               & (other_cnt >= p.min_data_in_leaf)
               & (other_h >= p.min_sum_hessian_in_leaf))
        oh_gain, oh_lo, oh_ro = _split_gains_clamped(
            g, h + K_EPSILON, other_g, other_h, p, p.lambda_l2, cmin, cmax)
        oh_gain = jnp.where(ok1 & (oh_gain > min_gain_shift), oh_gain,
                            K_MIN_SCORE)
        oh_t = jnp.argmax(oh_gain, axis=1).astype(jnp.int32)        # first max
        oh_best = oh_gain[fidx, oh_t]

    # ---------- sorted many-vs-many (:191-268) ----------
    l2c = p.lambda_l2 + p.cat_l2
    step = jnp.arange(steps, dtype=jnp.int32)[:, None]              # [steps, 1]
    with jax.named_scope(_scopes.FIND_CAT_SORT):
        valid_sort = in_range & (cnt >= p.cat_smooth)
        ctr = g / (h + p.cat_smooth)
        sort_key = jnp.where(valid_sort, ctr, jnp.inf)
        # the order of argsort(sort_key, stable=True), its values carried
        _, gs, hs, cs, order = jax.lax.sort(
            (sort_key, g, h, cnt, jnp.broadcast_to(t, (F, B))),
            dimension=1, is_stable=True, num_keys=1)
        used = valid_sort.sum(axis=1).astype(jnp.int32)             # [F]
        max_num_cat = jnp.minimum(p.max_cat_threshold, (used + 1) // 2)

        # walk position i reads sorted position i forward and used - 1 - i
        # backward: one term of each sum below is not zero, so it is exact
        at_bwd = t[None] == jnp.maximum(used[None, :] - 1 - step,
                                        0)[:, :, None]          # [steps, F, B]

        def window(xs):
            """[F, B] in sorted order -> [2 directions, steps, F]."""
            bwd = jnp.sum(jnp.where(at_bwd, xs[None], 0), axis=2,
                          dtype=xs.dtype)
            return jnp.stack([xs[:, :steps].T, bwd])
        wg, wh, wc, wbin = window(gs), window(hs), window(cs), window(order)

    with jax.named_scope(_scopes.FIND_CAT_SCAN):
        live = (step < used[None, :]) & (step < max_num_cat[None, :])
        af = live.astype(jnp.float32)                               # [steps, F]
        sum_lg = _in_scan_order(wg * af, 0.0)                   # [2, steps, F]
        sum_lh = _in_scan_order(wh * af, K_EPSILON)
        left_c = _in_scan_order(wc * af, 0.0)
        cont1 = ((left_c < p.min_data_in_leaf)
                 | (sum_lh < p.min_sum_hessian_in_leaf))
        right_c = num_data_f - left_c
        sum_rh = total_h - sum_lh
        brk = ((right_c < p.min_data_in_leaf)
               | (right_c < p.min_data_per_group)
               | (sum_rh < p.min_sum_hessian_in_leaf))
        # the walk stops after its first live position that breaks: up to
        # there every live position was active and the sums above are the
        # walk's own, past it nothing is a candidate
        first_brk = jnp.min(jnp.where(live & brk, step, steps), axis=1,
                            keepdims=True)
        active = live & (step <= first_brk)
        in_group = active & ~cont1 & ~brk
        grp_c = wc * active.astype(jnp.float32)
        cnt_grp = jnp.zeros((2, F), jnp.float32)
        reached_group = []
        for i in range(steps):
            cnt_grp = cnt_grp + grp_c[:, i]
            reached = in_group[:, i] & (cnt_grp >= p.min_data_per_group)
            reached_group.append(reached)
            cnt_grp = jnp.where(reached, 0.0, cnt_grp)
        gain, _, _ = _split_gains_clamped(sum_lg, sum_lh, total_g - sum_lg,
                                          sum_rh, p, l2c, cmin, cmax)
        gain = jnp.where(jnp.stack(reached_group, axis=1)
                         & (gain > min_gain_shift), gain, K_MIN_SCORE)
        dir_gain = jnp.max(gain, axis=1)                            # [2, F]
        dir_i = jnp.where(dir_gain > K_MIN_SCORE,
                          jnp.argmax(gain, axis=1).astype(jnp.int32), -1)
        use_bwd = dir_gain[1] > dir_gain[0]                          # fwd ties
        so_gain = jnp.where(use_bwd, dir_gain[1], dir_gain[0])
        so_i = jnp.where(use_bwd, dir_i[1], dir_i[0])

        # recompute left sums at the winning prefix (inclusive of so_i)
        in_prefix = jnp.where(use_bwd[:, None],
                              (t >= jnp.maximum(used - 1 - so_i, 0)[:, None])
                              & (t < used[:, None]),
                              t <= so_i[:, None])
        in_prefix &= so_i[:, None] >= 0
        so_lg = jnp.sum(jnp.where(in_prefix, gs, 0.0), axis=1)
        so_lh = jnp.sum(jnp.where(in_prefix, hs, 0.0), axis=1) + K_EPSILON
        so_lc = jnp.sum(jnp.where(in_prefix, cs, 0.0), axis=1)

    # ---------- combine one-hot / sorted per feature ----------
    oh = use_onehot
    cat_gain = jnp.where(oh, oh_best, so_gain)
    l_g = jnp.where(oh, g[fidx, oh_t], so_lg)
    l_h = jnp.where(oh, h[fidx, oh_t] + K_EPSILON, so_lh)
    l_c = jnp.where(oh, cnt[fidx, oh_t], so_lc)
    eff_l2 = jnp.where(oh, p.lambda_l2, l2c)
    r_g = total_g - l_g
    r_h = total_h - l_h
    r_c = num_data_f - l_c
    l_out = _leaf_output_l2(l_g, l_h, p, eff_l2)
    r_out = _leaf_output_l2(r_g, r_h, p, eff_l2)
    if cmin is not None:
        l_out = jnp.clip(l_out, cmin, cmax)
        r_out = jnp.clip(r_out, cmin, cmax)

    # left-bin bitsets: one-hot -> {oh_t}; sorted -> the bins of the
    # winner's window up to so_i (so_i = -1: none)
    with jax.named_scope(_scopes.FIND_CAT_SORT):
        bits_oh = t == oh_t[:, None]
        win_bin = jnp.where(use_bwd[None, :], wbin[1], wbin[0])     # [steps, F]
        bits_sorted = jnp.any((step <= so_i[None, :])[:, :, None]
                              & (win_bin[:, :, None] == t[None]), axis=0)
        bits = jnp.where(oh[:, None], bits_oh, bits_sorted)
        words = _bits_to_words(bits)

    found = (cat_gain > K_MIN_SCORE) & feature_mask & feat.is_categorical
    zero = jnp.zeros((F,), jnp.float32)
    return FeatureBest(
        gain=jnp.where(found, cat_gain - min_gain_shift, K_MIN_SCORE),
        threshold=jnp.where(oh, oh_t, so_i + 1).astype(jnp.int32),
        default_left=jnp.zeros((F,), bool),
        left_sum_grad=jnp.where(found, l_g, zero),
        left_sum_hess=jnp.where(found, l_h - K_EPSILON, zero),
        left_count=jnp.where(found, l_c, zero),
        right_sum_grad=jnp.where(found, r_g, zero),
        right_sum_hess=jnp.where(found, r_h - K_EPSILON, zero),
        right_count=jnp.where(found, r_c, zero),
        left_output=l_out,
        right_output=r_out,
        cat_bitset=jnp.where(found[:, None], words, 0).astype(jnp.uint32),
    )


def _split_gains_l2(gl, hl, gr, hr, p: SplitParams, l2):
    lo = _leaf_output_l2(gl, hl, p, l2)
    ro = _leaf_output_l2(gr, hr, p, l2)
    gain = (leaf_split_gain_given_output(gl, hl, p.lambda_l1, l2, lo)
            + leaf_split_gain_given_output(gr, hr, p.lambda_l1, l2, ro))
    return gain, lo, ro


def _split_gains_clamped(gl, hl, gr, hr, p: SplitParams, l2, cmin, cmax):
    """Like _split_gains_l2, but candidate outputs are clamped into the leaf's
    monotone bounds BEFORE computing gain, matching GetSplitGains going through
    ConstraintEntry (feature_histogram.hpp:468-527) so candidate ranking under
    monotone constraints agrees with the reference."""
    lo = _leaf_output_l2(gl, hl, p, l2)
    ro = _leaf_output_l2(gr, hr, p, l2)
    if cmin is not None:
        lo = jnp.clip(lo, cmin, cmax)
        ro = jnp.clip(ro, cmin, cmax)
    gain = (leaf_split_gain_given_output(gl, hl, p.lambda_l1, l2, lo)
            + leaf_split_gain_given_output(gr, hr, p.lambda_l1, l2, ro))
    return gain, lo, ro


def _leaf_output_l2(sum_grad, sum_hess, p: SplitParams, l2):
    ret = -threshold_l1(sum_grad, p.lambda_l1) / (sum_hess + l2)
    if p.max_delta_step > 0.0:
        ret = jnp.clip(ret, -p.max_delta_step, p.max_delta_step)
    return ret


def per_feature_best_combined(hist: jax.Array, feat: FeatureInfo,
                              feature_mask: jax.Array, sum_grad: jax.Array,
                              sum_hess: jax.Array, num_data: jax.Array,
                              params: SplitParams,
                              any_categorical: bool = True,
                              cmin=None, cmax=None) -> FeatureBest:
    """Numerical + categorical per-feature bests merged by feature type."""
    fb_num = per_feature_best(hist, feat, feature_mask, sum_grad, sum_hess,
                              num_data, params, cmin, cmax)
    if not any_categorical:
        return fb_num
    fb_cat = per_feature_best_categorical(hist, feat, feature_mask, sum_grad,
                                          sum_hess, num_data, params,
                                          cmin, cmax)
    is_cat = feat.is_categorical
    merged = [jnp.where(is_cat[(...,) + (None,) * (c.ndim - 1)], c, n)
              if c.ndim > 1 else jnp.where(is_cat, c, n)
              for n, c in zip(fb_num, fb_cat)]
    return FeatureBest(*merged)


def reduce_feature_best(fb: FeatureBest, feature_ids: jax.Array) -> BestSplit:
    """Argmax-by-gain across features; ties go to the smaller feature id
    (split_info.hpp:185 comparators).  ``feature_ids`` maps positions in ``fb`` to
    global inner-feature indices (they must be ascending for the tie-break)."""
    with jax.named_scope(_scopes.FIND_PICK):
        best_f = jnp.argmax(fb.gain).astype(jnp.int32)   # first max = smallest id
        return BestSplit(
            gain=fb.gain[best_f],
            feature=feature_ids[best_f].astype(jnp.int32),
            threshold=fb.threshold[best_f],
            default_left=fb.default_left[best_f],
            left_sum_grad=fb.left_sum_grad[best_f],
            left_sum_hess=fb.left_sum_hess[best_f],
            left_count=fb.left_count[best_f],
            right_sum_grad=fb.right_sum_grad[best_f],
            right_sum_hess=fb.right_sum_hess[best_f],
            right_count=fb.right_count[best_f],
            left_output=fb.left_output[best_f],
            right_output=fb.right_output[best_f],
            cat_bitset=fb.cat_bitset[best_f],
        )


class GroupLanes(NamedTuple):
    """What each lane of a bundled table's group histogram ``[G, 2, Bg]``
    holds and hosts: static maps built once a learner by :func:`group_lanes`.
    A group's lanes ARE its features' bins 1..nb-1 laid end to end from
    ``bin_offset`` (io/dataset.py ``_assign_group_layout``), so a feature is
    a SEGMENT of lanes and the split search scans it where it lies
    (:func:`group_scans`, :func:`group_best`): lane ``l`` of feature ``f``
    holds bin ``k = l - bin_offset[f] + 1`` and hosts the threshold
    ``t = k - 1``, whose left side is bin 0 plus the bins before ``k``.
    Everything :func:`per_feature_best` works out of ``nb``,
    ``missing_type`` and ``default_bin`` a feature is worked out here a lane,
    on the host: inside the tree's loop the maps are operands."""
    feature: jax.Array    # [G, Bg] i32 inner feature id; F where no feature's bin lives
    threshold: jax.Array  # [G, Bg] i32 t = k - 1
    num_bin: jax.Array    # [G, Bg] i32 the lane's feature's; 0 where none
    monotone: jax.Array   # [G, Bg] i32
    # [2, G, Bg] bool, direction 0 (missing/default LEFT) and 1 (RIGHT):
    # lanes left out of the direction's running sums (the default bin's own
    # in zero mode; for direction 0 the NaN bin's too), and its candidates
    drop: jax.Array
    valid: jax.Array
    zero_is_default: jax.Array   # [G, Bg] bool: bin 0 on neither left side
    # [2 G Bg] i32 over (direction, group, lane): the feature, and the place
    # in the reference's order of preference INSIDE a feature (direction 0
    # first and from the largest t down, then direction 1 from the smallest)
    feature2: jax.Array
    order: jax.Array
    # [4, G Bg] i32, what a winner's record reads at its lane: feature, t,
    # zero_is_default, two bins and NaN (default_left = false, :128-130)
    record: jax.Array
    # [G, Bg, 2 Bg] bf16 zeros and ones, lane k by lane j: k is of j's segment
    # and not before j (the first Bg columns), and before j (the last Bg)
    scan: jax.Array


def group_lanes(group_idx, bin_offset, num_bin, missing_type, default_bin,
                monotone, num_groups: int, group_bins: int) -> GroupLanes:
    """The lane maps of a group layout (host arrays in, device arrays out).
    Lane 0 of a group and the lanes above its last feature belong to no
    feature; a lane past ``group_bins`` is dropped."""
    nf = len(num_bin)
    feature = np.full((num_groups, group_bins), nf, dtype=np.int32)
    t = np.zeros((num_groups, group_bins), dtype=np.int32)
    for f in range(nf):
        ts = np.arange(int(num_bin[f]) - 1, dtype=np.int32)
        lane = int(bin_offset[f]) + ts
        ok = (lane >= 0) & (lane < group_bins)
        feature[int(group_idx[f]), lane[ok]] = f
        t[int(group_idx[f]), lane[ok]] = ts[ok]
    is_feature = feature < nf

    def a_lane(per_feature):
        return np.append(np.asarray(per_feature, dtype=np.int32),
                         np.int32(0))[feature]
    nb, mt, dbin = a_lane(num_bin), a_lane(missing_type), a_lane(default_bin)
    # per_feature_best's modes and candidate ranges, a lane (k = t + 1):
    # two directions only with a missing bin and > 2 bins; zero mode skips
    # the default bin and cannot place t at default_bin - 1 (direction 0) or
    # at default_bin (direction 1); NaN mode keeps the last bin off
    # direction 0's right side and t under it
    has_missing = (mt != int(MissingType.NONE)) & (nb > 2)
    zero2 = has_missing & (mt == int(MissingType.ZERO))
    nan2 = has_missing & (mt == int(MissingType.NAN))
    at_default = zero2 & (t + 1 == dbin)
    skip0 = at_default | (nan2 & (t + 1 == nb - 1))
    valid = np.stack([is_feature & ~skip0,
                      is_feature & has_missing & ~(zero2 & (t == dbin))])
    zero_is_default = zero2 & (dbin == 0)
    two_bin_nan = (mt == int(MissingType.NAN)) & (nb <= 2)
    same = (feature[:, :, None] == feature[:, None, :]) \
        & is_feature[:, :, None]
    k_before_j = np.tri(group_bins, k=-1, dtype=bool).T
    return GroupLanes(
        feature=jnp.asarray(feature), threshold=jnp.asarray(t),
        num_bin=jnp.asarray(nb), monotone=jnp.asarray(a_lane(monotone)),
        drop=jnp.asarray(np.stack([skip0, at_default])),
        valid=jnp.asarray(valid),
        zero_is_default=jnp.asarray(zero_is_default),
        feature2=jnp.asarray(np.tile(feature.reshape(-1), 2)),
        order=jnp.asarray(np.concatenate(
            [group_bins - 1 - t.reshape(-1), group_bins + t.reshape(-1)])),
        record=jnp.asarray(np.stack([
            feature, t, zero_is_default, two_bin_nan]).reshape(4, -1)
            .astype(np.int32)),
        scan=jnp.asarray(np.concatenate(
            [same & ~k_before_j, same & k_before_j], axis=2),
            dtype=jnp.bfloat16))


class GroupScans(NamedTuple):
    """A leaf's group histogram scanned in place (all ``[.., G, Bg]``)."""
    after: jax.Array   # [3]: grad, hess, count of the lane's own bin and the
                       # feature's bins after it, less what direction 0 skips
    before: jax.Array  # [3]: of the bins 1..k-1 before it, less the default bin
    bin0: jax.Array    # [2]: grad, hess of the feature's bin 0, from the totals


def group_scans(hist: jax.Array, lanes: GroupLanes, sum_grad: jax.Array,
                sum_hess: jax.Array, num_data: jax.Array) -> GroupScans:
    """What takes the unbundling's place: the segmented running sums of one
    leaf's group histogram ``[G, 2, Bg]`` and the shared default bin recovered
    from the leaf totals (dataset.h:501 FixHistogram), on the group's own
    lanes.  Counts as :func:`per_feature_best` makes them,
    ``round(h * cnt_factor)`` a bin.

    The sums are ONE product with ``lanes.scan``: a lane's sum is made of its
    own segment's lanes alone (a level of a few hundred rows keeps its own
    rounding beside a group that holds the whole leaf, which a difference of
    group-wide prefixes would not give it), and an empty bin adds an exact
    zero, so two thresholds that cut the same rows tie exactly, as in the
    reference's serial scan.  f32 in, exactly, at any matmul precision: each
    value goes in as three parts of bf16's eight bits, which the unit
    multiplies by 0 or 1 and adds up in f32."""
    with jax.named_scope(_scopes.FIND_SCAN):
        f32 = jnp.float32
        Bg = hist.shape[-1]
        cnt_factor = num_data.astype(f32) / (sum_hess + 2 * K_EPSILON)
        x = jnp.stack([hist[:, 0, :], hist[:, 1, :],
                       jnp.round(hist[:, 1, :] * cnt_factor)])        # [3, G, Bg]
        # the reference's right-to-left scan accumulates the RIGHT side, its
        # left-to-right scan the left (feature_histogram.hpp:535-650); the two
        # plain rows give the segment's own sum, for bin 0
        rows = jnp.concatenate([jnp.where(lanes.drop[0], 0.0, x),
                                jnp.where(lanes.drop[1], 0.0, x), x[:2]])
        # reduce_precision, not a round trip through bf16: the compiler may drop
        # a pair of converts as excess precision, never this
        hi = jax.lax.reduce_precision(rows, exponent_bits=8, mantissa_bits=7)
        mid = jax.lax.reduce_precision(rows - hi, exponent_bits=8, mantissa_bits=7)
        lo = (rows - hi) - mid
        lo, mid, hi = jnp.einsum("prgk,gkj->prgj", jnp.stack([lo, mid, hi]),
                                 lanes.scan.astype(f32))
        sums = (lo + mid) + hi                                    # [8, G, 2 Bg]
        own = sums[6:, :, :Bg] + sums[6:, :, Bg:]
        totals = jnp.stack([sum_grad, sum_hess]).astype(f32)[:, None, None]
        return GroupScans(after=sums[:3, :, :Bg], before=sums[3:6, :, Bg:],
                          bin0=totals - own)


def _two_sides(scans: GroupScans, zero_is_default, total_g, total_h,
               num_data_f):
    """((gl, hl, cl, gr, hr, cr) of direction 0, of direction 1) from the
    scans: direction 0 (missing/default LEFT, the reference's dir=-1 scan)
    accumulated the right side, direction 1 (dir=+1) the left; the other
    side is what the leaf's totals leave.  Lane arrays, or one lane's values.
    """
    right_g0 = scans.after[0]
    right_h0 = scans.after[1] + K_EPSILON
    right_c0 = scans.after[2]
    bin0_g = jnp.where(zero_is_default, 0.0, scans.bin0[0])
    bin0_h = jnp.where(zero_is_default, 0.0, scans.bin0[1])
    left_g1 = bin0_g + scans.before[0]
    left_h1 = bin0_h + scans.before[1] + K_EPSILON
    left_c1 = jnp.round(bin0_h * (num_data_f / total_h)) + scans.before[2]
    return ((total_g - right_g0, total_h - right_h0, num_data_f - right_c0,
             right_g0, right_h0, right_c0),
            (left_g1, left_h1, left_c1,
             total_g - left_g1, total_h - left_h1, num_data_f - left_c1))


def group_best(scans: GroupScans, lanes: GroupLanes, valid: jax.Array,
               sum_grad: jax.Array, sum_hess: jax.Array, num_data: jax.Array,
               params: SplitParams, feat_num_bins: int, cmin=None, cmax=None,
               lane_contri=None) -> BestSplit:
    """Best numerical split of one leaf of a bundled table, searched on the
    ``G x Bg`` lanes of its group histogram: :func:`per_feature_best`'s
    formulas a lane, and :func:`reduce_feature_best`'s winner with the same
    tie rules.  ``valid`` [2, G, Bg]: ``lanes.valid`` of the features in this
    tree's feature_fraction draw; ``lane_contri`` [2 G Bg], as
    ``lanes.feature2``: the candidate's ``feature_contri``, or None.
    """
    G, Bg = lanes.feature.shape
    with jax.named_scope(_scopes.FIND_GAIN):
        total_h = sum_hess + 2 * K_EPSILON  # feature_histogram.hpp:88
        total_g = sum_grad
        num_data_f = num_data.astype(jnp.float32)
        sides0, sides1 = _two_sides(scans, lanes.zero_is_default, total_g,
                                    total_h, num_data_f)
        if params.extra_trees:
            valid = valid & (lanes.threshold == _extra_trees_draw(
                lanes.num_bin, sum_grad, sum_hess, params,
                fid=lanes.feature.astype(jnp.uint32)))
        gain_shift = leaf_split_gain(total_g, total_h, params.lambda_l1,
                                     params.lambda_l2, params.max_delta_step)
        min_gain_shift = gain_shift + params.min_gain_to_split
        mono = lanes.monotone if cmin is not None else None
        gain0, _, _ = _evaluate_candidates(*sides0, valid[0], params,
                                           min_gain_shift, cmin, cmax, mono)
        gain1, _, _ = _evaluate_candidates(*sides1, valid[1], params,
                                           min_gain_shift, cmin, cmax, mono)

    with jax.named_scope(_scopes.FIND_PICK):
        # the winner over (direction, lane): the largest reported gain; among
        # equals the smaller feature id; inside that feature the largest scanned
        # gain, then lanes.order (per_feature_best's argmaxes and
        # reduce_feature_best's, as maxima and one ranked minimum)
        raw = jnp.stack([gain0, gain1]).reshape(-1)
        shown = raw - min_gain_shift
        if lane_contri is not None:
            shown = shown * lane_contri
        shown = jnp.where(raw > K_MIN_SCORE, shown, K_MIN_SCORE)
        best_f = jnp.min(jnp.where(shown == jnp.max(shown), lanes.feature2,
                                   jnp.int32(2**31 - 1)))
        in_f = lanes.feature2 == best_f
        best_raw = jnp.max(jnp.where(in_f, raw, K_MIN_SCORE))
        at = jnp.argmin(jnp.where(in_f & (raw == best_raw), lanes.order,
                                  jnp.int32(2**31 - 1))).astype(jnp.int32)
        lane = at % (G * Bg)
        use1 = at >= G * Bg

        # the winner's own record from its lane's scans (one lane, not stacked
        # arrays of them): the same formulas over again
        def one(x):
            return jax.lax.dynamic_index_in_dim(
                x.reshape(x.shape[:-2] + (-1,)), lane, axis=-1, keepdims=False)
        feature, thr, zero_is_default, two_bin_nan = \
            jax.lax.dynamic_index_in_dim(lanes.record, lane, axis=1,
                                         keepdims=False)
        gl, hl, cl, gr, hr, cr = (
            jnp.where(use1, a1, a0) for a0, a1 in zip(*_two_sides(
                GroupScans(*[one(x) for x in scans]), zero_is_default != 0,
                total_g, total_h, num_data_f)))
        _, lo, ro = _split_gains_clamped(gl, hl, gr, hr, params, params.lambda_l2,
                                         cmin, cmax)
        return BestSplit(
            gain=jax.lax.dynamic_index_in_dim(shown, at, keepdims=False),
            feature=feature, threshold=thr,
            default_left=~use1 & (two_bin_nan == 0),
            left_sum_grad=gl, left_sum_hess=hl - K_EPSILON, left_count=cl,
            right_sum_grad=gr, right_sum_hess=hr - K_EPSILON, right_count=cr,
            left_output=lo, right_output=ro,
            cat_bitset=jnp.zeros((feat_num_bins // 32,), dtype=jnp.uint32))


def sync_best(best: BestSplit, axis_name: str) -> BestSplit:
    """Allreduce-argmax of per-shard best splits across a mesh axis — the XLA
    equivalent of ``SyncUpGlobalBestSplit`` (parallel_tree_learner.h:190-213):
    ONE all_gather of the candidate record and pick max gain, ties to the
    smaller feature id.  The record is the 12 scalars and the [W] bitset as
    32-bit words (bitcasts, so every field arrives bit for bit): one
    collective of 4 * (12 + W) bytes a chip, not one per field."""
    def words(x):
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.uint32)
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)

    with jax.named_scope("comm.best_split"):
        g = jax.lax.all_gather(jnp.concatenate([words(x) for x in best]),
                               axis_name)                       # [d, 12 + W]
    fields, at = [], 0
    for x in best:
        w = g[:, at:at + max(x.size, 1)]
        at += w.shape[1]
        if x.dtype == jnp.bool_:
            w = w != 0
        else:
            w = jax.lax.bitcast_convert_type(w, x.dtype)
        fields.append(w.reshape((g.shape[0],) + x.shape))
    g = BestSplit(*fields)
    max_gain = jnp.max(g.gain)
    tie_feat = jnp.where(g.gain == max_gain, g.feature, jnp.int32(2**31 - 1))
    i = jnp.argmin(tie_feat)
    return BestSplit(*[x[i] for x in g])


@functools.partial(jax.jit, static_argnames=("params",))
def best_split_numerical(hist: jax.Array, feat: FeatureInfo, feature_mask: jax.Array,
                         sum_grad: jax.Array, sum_hess: jax.Array,
                         num_data: jax.Array, params: SplitParams) -> BestSplit:
    """Best numerical split over all features of one leaf (scalars out)."""
    fb = per_feature_best(hist, feat, feature_mask, sum_grad, sum_hess,
                          num_data, params)
    return reduce_feature_best(fb, jnp.arange(hist.shape[0], dtype=jnp.int32))
