"""GBDT training loop.

Counterpart of the reference ``GBDT`` (src/boosting/gbdt.cpp, gbdt.h):
``train_one_iter`` = boost-from-average (first iter) -> objective gradients ->
bagging -> per-class tree train -> leaf-output renewal -> shrinkage -> score
update (gbdt.cpp:370-452); plus bagging (:160-276), early stopping (:472-489),
rollback (:454), snapshots (:291-295) and the reference-compatible text model
format (gbdt_model_text.cpp:271,375).

TPU-first notes:
- Scores live on device as [num_tree_per_iteration, padded_rows] f32; the train
  score update is a leaf-value gather through the freshly built tree's
  ``row_leaf`` (free by-product of the on-device build), validation scores come
  from ``route_binned`` — no host round-trip per iteration except for metrics.
- Bagging is a row mask multiplied into grad/hess (histograms are mask-blind),
  not an index-compacted subset; ``bag_data_cnt`` feeds min_data_in_leaf
  semantics exactly like the reference's ``bag_data_cnt_``.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..core.row_state import f32_col, i32_col
from ..core.tree import Tree
from ..core.tree_learner import (SerialTreeLearner, TreeArrays,
                                 build_tree_partitioned, route_binned,
                                 tree_from_arrays, tree_output_binned)
from ..parallel import create_tree_learner
from ..parallel.learners import arg_specs
from ..io.dataset import BinnedDataset
from ..metric.metric import Metric, create_metrics
from ..objective import ObjectiveFunction, create_objective
from ..obs import active as _telemetry_active
from ..obs import comm as _comm
from ..obs import compile as _compile
from ..obs import devmem as _devmem
from ..obs import launches as _launches
from ..obs import recompile as _recompile
from ..obs import sampling as _sampling
from ..obs import scopes as _scopes
from ..obs import spans as _spans
from ..resilience import PROGRAM_ERRORS as _PROGRAM_ERRORS
from ..resilience import preemption_requested as _preemption_requested
from ..resilience import watch as _watch
from ..utils.file_io import atomic_write
from ..utils.log import LightGBMError, Log

K_EPSILON = 1e-15
MODEL_VERSION = "v3"


def _hoisted_jit(fused, *example_args):
    """jit with every closed-over array hoisted to an explicit argument.

    Closure-captured arrays are inlined as dense literals in the lowered
    module — at the 10.5M-row Higgs shape the binned matrix alone is a 294 MB
    literal (672 MB of StableHLO total) that every lowering, compile and
    cache-key hash would carry.  ``jax.make_jaxpr`` exposes exactly
    those captured arrays as ``.consts`` (``jax.closure_convert`` does NOT
    hoist concrete arrays — only tracer consts), so the program is re-entered
    through ``eval_jaxpr`` with the consts as real parameters: bins, valid
    bins, objective label/weight vectors and the carried aux in one sweep.
    """
    specs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
        example_args)
    closed, out_shape = jax.make_jaxpr(fused, return_shape=True)(*specs)
    out_tree = jax.tree_util.tree_structure(out_shape)
    consts = closed.consts

    def converted(consts_, *args):
        flat, _ = jax.tree_util.tree_flatten(args)
        out = jax.core.eval_jaxpr(closed.jaxpr, consts_, *flat)
        return jax.tree_util.tree_unflatten(out_tree, out)

    jitted = jax.jit(converted)

    def call(*args):
        return jitted(consts, *args)

    call.lower = lambda *args: jitted.lower(consts, *args)
    call._cache_size = jitted._cache_size
    return call


def _mul_mask(grad, hess, mask):
    return grad * mask, hess * mask


def _hash_u32(ids, seed: int, key):
    """A stateless integer hash (xxhash-style avalanche) of (id, seed, key),
    uint32 throughout: the one source of randomness of the bag and of the
    feature mask, reproducible from any execution order."""
    x = ids.astype(jnp.uint32) * jnp.uint32(2654435761)
    x = x ^ (jnp.uint32(seed & 0xFFFFFFFF)
             + key.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(3266489917)
    return x ^ (x >> 16)


def _bag_uniforms(row_ids, seed: int, it_window):
    """Deterministic per-row uniforms in [0, 1) for bagging, keyed by
    (original row id, bagging window).  A stateless integer hash
    (:func:`_hash_u32`) instead of a sequential RNG stream so the SAME mask is
    reproducible from any execution order — per-iteration host path, fused
    lax.scan, and the carried row store (where rows are permuted and only
    their original ids are at hand) all agree bit-exactly.

    Differs from the reference's exact-count sampling-without-replacement
    (gbdt.cpp:160-276): each row is an independent Bernoulli(p) draw, so
    ``bag_data_cnt`` is the realized count.  Quality-equivalent; pinned by
    tests/test_boosting.py bagging windows."""
    x = _hash_u32(row_ids, seed, it_window)
    # u32 -> f32 through two exact 16-bit halves: their one rounded add is the
    # conversion's own rounding, bit for bit, and Mosaic (which has no
    # u32 -> f32 cast) can compile it inside the row store's hand-over pass
    hi = jax.lax.bitcast_convert_type(x >> 16, jnp.int32)
    lo = jax.lax.bitcast_convert_type(x & jnp.uint32(0xFFFF), jnp.int32)
    xf = hi.astype(jnp.float32) * jnp.float32(65536.0) + lo.astype(jnp.float32)
    return xf * jnp.float32(1.0 / 4294967296.0)


def features_used(num_features: int, fraction: float) -> int:
    """Features a tree may split on under ``feature_fraction``."""
    if fraction >= 1.0 or num_features <= 1:
        return num_features
    return max(1, int(round(num_features * fraction)))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def feature_mask_of(num_features: int, used: int, seed: int, it):
    """[num_features] bool: the features iteration ``it`` may split on, a
    pure function of (``feature_fraction_seed``, iteration).  Every feature
    id is hashed with the seed and the iteration (:func:`_hash_u32`) and the
    ``used`` smallest hashes are taken, equal hashes to the smaller id (a
    stable sort).  The ONE draw the fused scan, ``train_one_iter`` and RF
    share, so a model trained per iteration and one trained in fused chunks
    are equal tree for tree and a resume or a rollback needs no RNG state.

    Differs from the reference's ``Random::Sample`` stream
    (serial_tree_learner.cpp BeforeTrain): the same count of features, other
    features for the same seed."""
    order = jnp.argsort(_hash_u32(jnp.arange(num_features, dtype=jnp.int32),
                                  seed, jnp.asarray(it, jnp.int32)),
                        stable=True)
    return jnp.zeros((num_features,), bool).at[order[:used]].set(True)


def _bag_mask(row_ids, seed: int, it, freq: int, frac: float):
    """Bagging mask (f32 0/1) of iteration ``it`` — the ONE implementation
    the fused scan, the carried store's hand-over pass and the host
    per-iteration path use; bit-exact agreement between them is asserted by
    tests/test_fused_valid_bagging.py."""
    itw = it - jax.lax.rem(it, jnp.int32(freq))
    u = _bag_uniforms(row_ids, seed, itw)
    # frac may be a per-row array (pos/neg balanced bagging) or a scalar
    return (u < jnp.asarray(frac, jnp.float32)).astype(jnp.float32)


def _bag_mask_for(row_ids, seed: int, it, freq: int, frac: float):
    """(mask f32 0/1, realized count i32) for iteration ``it``."""
    mask = _bag_mask(row_ids, seed, it, freq, frac)
    cnt = jnp.maximum(jnp.sum(mask, dtype=jnp.float32), 1.0).astype(jnp.int32)
    return mask, cnt


def _carried_fns(objective, num_data: int, bag, bag_seed: int):
    """What carried-row-store training computes per row from what rides the
    store, both element-wise: ``live(order, it)``, 1.0 for a real row in
    iteration ``it``'s bag (``bag``: ``_fused_bag()``) and else 0.0, and
    ``grad_fn(score, aux, order, it) -> (grad, hess)``, which the tree
    builder's hand-over pass calls tile by tile (core/row_state.py)."""
    def live(order, it):
        m = (order < num_data).astype(jnp.float32)
        if bag is not None:
            # the store is PERMUTED, so the mask must be keyed by each row's
            # ORIGINAL id (the order bytes) — exactly what the stateless
            # hash provides
            frac, freq = bag
            m = m * _bag_mask(order, bag_seed, it, freq, frac)
        return m

    def grad_fn(score, aux, order, it):
        g, h = objective.pointwise_gradients(score, aux)
        m = live(order, it)
        return g * m, h * m
    return live, grad_fn


def _add_valid_outputs(vscores, kk, arr, feat, vbins, num_leaves,
                       has_categorical):
    """Valid-score update for one scaled tree inside the fused scan: the
    path-matrix router for numerical trees, per-level routing otherwise."""
    depth = jnp.max(arr.leaf_depth)
    if has_categorical:
        return tuple(
            vsc.at[kk].add(arr.leaf_value[route_binned(
                vb, arr, feat, num_leaves=num_leaves, depth_bound=depth)])
            for vsc, vb in zip(vscores, vbins))
    return tuple(
        vsc.at[kk].add(tree_output_binned(
            vb, arr, feat, num_leaves=num_leaves, depth_bound=depth))
        for vsc, vb in zip(vscores, vbins))


def _scan_grouped(step, carry, its, group: int):
    """``jax.lax.scan`` of ``step`` over ``its`` with ``group`` consecutive
    steps unrolled per scan iteration (round 12 ``trees_per_chunk``): the
    scan body then amortizes its per-step dispatch/bookkeeping cost over
    ``group`` tree builds — the small-tree regime where scan-step overhead
    rivals the build itself.  The SAME ``step`` calls run in the SAME order
    with the same carries as ``group=1`` (only the scan structure changes),
    so results are bit-exact vs the ungrouped scan (pinned by
    tests/test_partition_buckets.py).  A non-dividing tail runs as a second
    ungrouped scan; stacked outputs are re-flattened to per-step order."""
    k = int(its.shape[0])
    if group <= 1 or k <= 1:
        return jax.lax.scan(step, carry, its)
    g = min(int(group), k)
    main = (k // g) * g

    def gstep(c, it_vec):
        outs = []
        for j in range(g):
            c, out = step(c, it_vec[j])
            outs.append(out)
        return c, jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

    carry, stacked = jax.lax.scan(gstep, carry,
                                  its[:main].reshape(k // g, g))
    stacked = jax.tree_util.tree_map(
        lambda x: x.reshape((main,) + x.shape[2:]), stacked)
    if main < k:
        carry, tail = jax.lax.scan(step, carry, its[main:])
        stacked = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), stacked, tail)
    return carry, stacked


class _LazyTreeSlice:
    """One tree of a fused-chunk's stacked TreeArrays, sliced on demand so the
    hot path never issues per-tree device ops."""

    __slots__ = ("stacked", "i")

    def __init__(self, stacked: TreeArrays, i: int) -> None:
        self.stacked = stacked
        self.i = i

    def resolve(self) -> TreeArrays:
        return jax.tree_util.tree_map(lambda a: a[self.i], self.stacked)


def _resolve_arrays(arrays) -> TreeArrays:
    return arrays.resolve() if isinstance(arrays, _LazyTreeSlice) else arrays


class GBDT:
    """Gradient Boosting Decision Tree (sub-model name "tree", gbdt.h:362).

    TPU pipelining: the default training path is fully asynchronous — per
    iteration it only *dispatches* device work (gradients, tree build, score
    update) and records lazy handles; host ``Tree`` objects are materialized in
    one batched device fetch when first needed (save/predict/eval) and the
    no-more-splits stop condition is polled every ``_poll_freq`` iterations.
    This keeps the accelerator queue full instead of paying a host round-trip
    per iteration (the reference's per-iteration host loop is free on CPU but
    dominates wall-clock on a remote accelerator).  DART (and objectives that
    renew leaf outputs on the host) use the synchronous path.
    """

    average_output = False
    lazy_trees = True

    def __init__(self, config: Config, train_data: Optional[BinnedDataset] = None,
                 objective: Optional[ObjectiveFunction] = None,
                 mesh=None) -> None:
        self.config = config
        self.mesh = mesh
        self.models = []
        self.iter_ = 0
        self.num_init_iteration = 0
        self.train_data: Optional[BinnedDataset] = None
        self.objective = objective
        self.num_tree_per_iteration = 1
        self.num_class = int(config.num_class)
        self.shrinkage_rate = float(config.learning_rate)
        self.max_feature_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.label_idx = 0
        self.best_score: Dict = {}
        self.valid_sets: List[dict] = []
        self.train_metrics: List[Metric] = []
        self._loaded_params: Dict[str, str] = {}
        # quality-plane provenance (obs/quality.py): when this booster last
        # trained an iteration, plus cached score fingerprints / baseline
        self.trained_at: Optional[float] = None
        self._score_fingerprint_raw = None
        self._score_fingerprint_out = None
        self._quality_baseline_cache = None
        if train_data is not None:
            with _spans.span("gbdt.construct"):
                self.reset_training_data(train_data, objective)

    # ---- lazy tree materialization ----

    @property
    def models(self) -> List[Tree]:
        """Host trees; materializes any pending device trees (one batched fetch)."""
        if self._pending:
            self._materialize_pending()
        return self._models

    def _invalidate_predict_cache(self) -> None:
        """Bump the model generation: any in-place tree surgery (refit, leaf
        edits, shuffles, rollback) must not serve stale stacked predictions."""
        self._stacked_pred = None
        self._fused_pred = {}
        self._model_gen = getattr(self, "_model_gen", 0) + 1

    @models.setter
    def models(self, value) -> None:
        self._invalidate_predict_cache()
        self._models: List[Tree] = list(value)
        self._pending: Dict[int, Tuple[TreeArrays, float]] = {}
        # device arrays of trees materialized since the last poll, kept so a
        # stall trim can still reverse their score contributions
        self._window: Dict[int, TreeArrays] = {}
        self._nl_handles: List[Tuple[int, int, jax.Array]] = []
        # per-iteration isfinite handles (nan_policy=raise): fetched in the
        # same _poll_stop batch as _nl_handles, so the guard costs no sync
        self._fin_handles: List[Tuple[int, jax.Array]] = []
        self._last_poll = 0
        self._fused_cache: Dict = {}
        # pre-chunk state refs for the per-chunk non-finite rollback
        # (jax arrays are immutable, so holding them is free)
        self._prechunk: Optional[Tuple] = None
        self._nan_rolled_back_at = -1
        # True while _fuse_failed was set by a NaN rollback (not by a trace
        # failure) — cleared, re-arming fusion, once a retry runs clean
        self._nan_refused_fuse = False

    def _materialize_pending(self) -> None:
        idxs = sorted(self._pending)
        recs = [self._pending[i] for i in idxs]
        self._pending = {}
        # ONE device round-trip; row_leaf ([N] per tree) is not needed on
        # host.  Fused-chunk slices share their stacked arrays: fetch each
        # stacked chunk once and slice on host.
        chunks: Dict[int, TreeArrays] = {}
        singles = []
        for rec in recs:
            a = rec[0]
            if isinstance(a, _LazyTreeSlice):
                chunks.setdefault(id(a.stacked), a.stacked)
            else:
                singles.append(a._replace(row_leaf=a.num_leaves))
        fetch = ([c._replace(row_leaf=c.num_leaves) for c in chunks.values()]
                 + singles)
        host = jax.device_get(fetch)
        host_chunks = dict(zip(chunks.keys(), host[:len(chunks)]))
        host_singles = iter(host[len(chunks):])
        for i, rec in zip(idxs, recs):
            a = rec[0]
            if isinstance(a, _LazyTreeSlice):
                arr = jax.tree_util.tree_map(lambda x: x[a.i],
                                             host_chunks[id(a.stacked)])
            else:
                arr = next(host_singles)
            self._window[i] = a
            tree = tree_from_arrays(arr, self.train_data, 1.0)
            if abs(rec[1]) > K_EPSILON:
                tree.add_bias(rec[1])
            self._models[i] = tree

    def _route_arrays_valid(self, arrays: TreeArrays, class_id: int,
                            vs: dict) -> None:
        """Validation score update straight from device tree arrays."""
        leaf = route_binned(vs["bins"], arrays, self.learner.feat,
                            num_leaves=int(self.config.num_leaves))
        vs["score"] = vs["score"].at[class_id].add(arrays.leaf_value[leaf])

    def _poll_stop(self) -> bool:
        """Deferred no-more-splits check (the reference checks every iteration,
        gbdt.cpp:439-450; here that host sync is amortized over _poll_freq
        iterations).  Trims any iterations past the first fully-stalled one —
        exactly where the reference would have stopped — and undoes their score
        contributions."""
        self._last_poll = self.iter_
        if not self._nl_handles and not self._fin_handles:
            return False
        with _spans.span("gbdt.poll_stop"), \
                _watch("poll_stop", iteration=int(self.iter_)):
            fetched = jax.device_get([h for _, _, h in self._nl_handles]
                                     + [f for _, f in self._fin_handles])
        nls = fetched[:len(self._nl_handles)]
        fins = fetched[len(self._nl_handles):]
        bad = [it for (it, _), ok in zip(self._fin_handles, fins)
               if not bool(ok)]
        self._fin_handles = []
        if bad:
            self._raise_nonfinite(bad[0])
        if not self._nl_handles:
            return False
        by_iter: Dict[int, List[int]] = {}
        first_idx: Dict[int, int] = {}
        K = self.num_tree_per_iteration
        for (it, idx, _), nl in zip(self._nl_handles, nls):
            arr = np.asarray(nl)
            if arr.ndim == 0:   # per-iteration entry: one class's tree
                by_iter.setdefault(it, []).append(int(arr))
                first_idx[it] = min(first_idx.get(it, idx), idx)
            else:               # fused chunk entry: [k, K] leaves counts
                for i in range(arr.shape[0]):
                    by_iter.setdefault(it + i, []).extend(
                        int(v) for v in arr[i])
                    first_idx[it + i] = min(first_idx.get(it + i, 1 << 60),
                                            idx + i * K)
        stalled = sorted(it for it, v in by_iter.items() if max(v) <= 1)
        if not stalled:
            self._nl_handles = []
            self._window = {}
            return False
        first = stalled[0]
        cut = first_idx[first]
        trimmed = {i: a for i, a in self._window.items() if i >= cut}
        trimmed.update((i, a) for i, (a, _) in self._pending.items()
                       if i >= cut)  # _pending is fresher than _window
        for idx in sorted(i for i in self._pending if i >= cut):
            self._pending.pop(idx)
        for idx in sorted(trimmed):
            arrays = _resolve_arrays(trimmed[idx])
            k = idx % self.num_tree_per_iteration
            self.train_score = self.train_score.at[k].add(
                -self._gather_tree_output(arrays))
            for vs in self.valid_sets:
                leaf = route_binned(vs["bins"], arrays, self.learner.feat,
                                    num_leaves=int(self.config.num_leaves))
                vs["score"] = vs["score"].at[k].add(-arrays.leaf_value[leaf])
        del self._models[cut:]
        self._nl_handles = []
        self._window = {}
        self.iter_ = first
        Log.warning("Stopped training because there are no more leaves "
                    "that meet the split requirements")
        return True

    # ---- setup ----

    def reset_training_data(self, train_data: BinnedDataset,
                            objective: Optional[ObjectiveFunction]) -> None:
        self.train_data = train_data
        self.objective = objective
        self.num_data = train_data.num_data
        # cached fused programs close over the old learner/objective
        self._fused_cache = {}
        self._fuse_failed = False
        self._fuse_refusals_said = set()
        self.num_tree_per_iteration = (objective.num_model_per_iteration
                                       if objective else max(1, self.num_class))
        self.learner = create_tree_learner(train_data, self.config,
                                           mesh=self.mesh)
        self.max_feature_idx = train_data.num_total_features - 1
        self.feature_names = list(train_data.feature_names)
        self.feature_infos = train_data.feature_infos()
        np_total = self.num_data + self.learner.padded_rows
        self.train_score = jnp.zeros(
            (self.num_tree_per_iteration, np_total), dtype=jnp.float32)
        if train_data.metadata.init_score is not None:
            init = np.asarray(train_data.metadata.init_score, dtype=np.float32)
            init = init.reshape(self.num_tree_per_iteration, self.num_data)
            pad = np.zeros((self.num_tree_per_iteration, self.learner.padded_rows),
                           dtype=np.float32)
            self.train_score = jnp.asarray(np.concatenate([init, pad], axis=1))
            self._has_init_score = True
        else:
            self._has_init_score = False
        self.class_need_train = [True] * self.num_tree_per_iteration
        if self.objective is not None:
            self.objective.init(train_data.metadata, self.num_data)
            if hasattr(self.objective, "class_need_train"):
                self.class_need_train = [
                    self.objective.class_need_train(k)
                    for k in range(self.num_tree_per_iteration)]
        # Per-row state lives with its rows: where the learner shards rows
        # over a mesh, scores, the objective's per-row constants, gradients
        # and the bag mask are created in the row blocks of learner.bins
        # (padded to its row count) and every program between two builds is
        # shard-local (_row_program).
        self._rows_sharded = bool(
            self.shard_row_state and self.learner.row_sharding is not None
            and (self.objective is None
                 or (self.objective.deterministic_gradients
                     and self.objective.shard_rows(self.learner.shard_rows))))
        if self.learner.row_sharding is not None and not self._rows_sharded:
            # the contract's gap, said aloud: nothing lists these programs,
            # so count_row_collectives() has no count to give (None)
            Log.warning(
                "tree_learner=%s shards the rows over %d devices, but %s keeps "
                "scores and gradients whole on one device: row-sized arrays "
                "cross the devices every iteration", self.config.tree_learner,
                self.learner.num_shards,
                "boosting=%s" % self.config.boosting
                if not self.shard_row_state else
                "objective=%s" % getattr(self.objective, "name", "custom"))
        self._row_fns: Dict = {}
        self._row_texts: Dict[str, str] = {}   # compiled, as they are asked for
        self._row_valid = None   # f32 1/0: a real row, or the learner's padding
        self._bag_frac = None
        if self._rows_sharded:
            self.train_score = self.learner.shard_rows(self.train_score)
            self._row_valid = self.learner.shard_rows(
                np.ones(self.num_data, np.float32))
            self._row_ids = self.learner.shard_rows(
                np.arange(np_total, dtype=np.int32))
        self.train_metrics = []
        # plain bagging uses the stateless _bag_uniforms hash; this
        # sequential stream remains for GOSS's sampling (goss.py)
        self._bag_rng = np.random.RandomState(int(self.config.bagging_seed))
        bag = self._fused_bag()
        _sampling.record_config(
            train_data.num_features, self._features_used(),
            *(bag if bag is not None else (1.0, 0)))
        self.bag_mask: Optional[jnp.ndarray] = None
        self.bag_data_cnt = self.num_data
        self._boosted_from_average = False
        self._last_iter_arrays: List[Optional[TreeArrays]] = []
        # gradients cache for custom-objective path
        self._es_state: Dict = {}

    def add_train_metrics(self, metrics: Sequence[Metric]) -> None:
        self.train_metrics = list(metrics)
        for m in self.train_metrics:
            m.init(self.train_data.metadata, self.num_data)

    def add_valid_data(self, valid_data: BinnedDataset, name: str,
                       metrics: Optional[Sequence[Metric]] = None) -> None:
        if metrics is None:
            metrics = create_metrics(self.config.metric, self.config)
        for m in metrics:
            m.init(valid_data.metadata, valid_data.num_data)
        score = jnp.zeros((self.num_tree_per_iteration, valid_data.num_data),
                          dtype=jnp.float32)
        if valid_data.metadata.init_score is not None:
            init = np.asarray(valid_data.metadata.init_score, dtype=np.float32)
            score = jnp.asarray(init.reshape(self.num_tree_per_iteration,
                                             valid_data.num_data))
        self.valid_sets.append({
            "name": name, "data": valid_data,
            "bins": jnp.asarray(self.learner.valid_bins(valid_data)),
            "metrics": list(metrics), "score": score,
        })
        # replay existing model onto the new validation set: ONE blocked
        # binned pass per class (core/predict_fused.py) instead of a
        # per-tree route_binned dispatch.  The in-scan f32 add order equals
        # the per-tree loop's, so the result is bit-identical when the
        # score base is zero; with a nonzero init_score the base joins the
        # sum last instead of first (ULP-level association difference)
        models = self.models
        if models:
            K = self.num_tree_per_iteration
            vs = self.valid_sets[-1]
            scores = self.raw_predict_binned(valid_data,
                                             use_early_stop=False)
            for k in range(K):
                vs["score"] = vs["score"].at[k].add(
                    jnp.asarray(scores[k], dtype=jnp.float32))

    # ---- scores ----

    def _gather_tree_output(self, arrays: TreeArrays) -> jnp.ndarray:
        if arrays.row_leaf.shape[0] == 0:
            # carried-mode trees drop the original-order row_leaf (their
            # per-row state lives in the permuted store); route the bins
            leaf = route_binned(self.learner.route_bins_matrix(), arrays,
                                self.learner.feat,
                                num_leaves=int(self.config.num_leaves))
            return arrays.leaf_value[leaf]
        return arrays.leaf_value[arrays.row_leaf]

    def _add_tree_output(self, arrays: TreeArrays, class_id: int) -> None:
        """train_score[class_id] += the tree's output on its own rows."""
        if self._rows_sharded and arrays.row_leaf.shape[0]:
            sharding = self.train_score.sharding

            def update(score, leaf_value, row_leaf):
                return jax.lax.with_sharding_constraint(
                    score.at[class_id].add(leaf_value[row_leaf]), sharding)
            self.train_score = self._row_program(
                "update_score/%d" % class_id, update, self.train_score,
                arrays.leaf_value, arrays.row_leaf)
        else:
            self.train_score = self.train_score.at[class_id].add(
                self._gather_tree_output(arrays))

    def _masked_gradients(self, gk, hk):
        """One class's gradients as the learner takes them: padded to its row
        count, zero on rows out of the bag and on a sharded learner's
        padding rows (whose gradients were computed like any row's)."""
        gk = self.learner.pad_rows(gk)
        hk = self.learner.pad_rows(hk)
        mask = self.bag_mask if self.bag_mask is not None else self._row_valid
        if mask is not None:
            gk, hk = self._row_program("mask", _mul_mask, gk, hk, mask)
        return gk, hk

    def _tree_to_device(self, tree: Tree) -> TreeArrays:
        """Rebuild a device-routable TreeArrays from a host tree (bin thresholds)."""
        nl = tree.num_leaves
        L = max(nl, 2)
        z = lambda dt: jnp.zeros((L,), dtype=dt)
        pad = lambda a, dt: jnp.asarray(
            np.concatenate([np.asarray(a[:max(nl - 1, 0)]),
                            np.zeros(L - max(nl - 1, 0), dtype=np.asarray(a).dtype)]
                           ).astype(dt))
        padl = lambda a, dt: jnp.asarray(
            np.concatenate([np.asarray(a[:nl]),
                            np.zeros(L - nl, dtype=np.asarray(a).dtype)]).astype(dt))
        ni = max(nl - 1, 0)
        inner = np.asarray([self.train_data.inner_feature_map.get(int(f), 0)
                            for f in tree.split_feature[:ni]],
                           dtype=np.int32) if self.train_data else \
            tree.split_feature_inner[:ni]
        # recompute bin thresholds from real-valued thresholds so parsed models
        # (whose text form stores only real thresholds) route identically;
        # categorical nodes: category-value bitset -> bin bitset
        W = self.learner.num_bins // 32
        thr_bin = np.zeros(ni, dtype=np.int32)
        cat_bits = np.zeros((L, W), dtype=np.uint32)
        for node in range(ni):
            m = self.train_data.bin_mappers[int(tree.split_feature[node])]
            if int(tree.decision_type[node]) & 1:   # categorical
                ci = int(tree.threshold[node])
                lo, hi = tree.cat_boundaries[ci], tree.cat_boundaries[ci + 1]
                for w in range(lo, hi):
                    word = int(tree.cat_threshold[w])
                    for j in range(32):
                        if (word >> j) & 1:
                            b = m.categorical_2_bin.get((w - lo) * 32 + j)
                            if b is not None:
                                cat_bits[node, b >> 5] |= np.uint32(1 << (b & 31))
            else:
                thr_bin[node] = m.value_to_bin(float(tree.threshold[node]))
        return TreeArrays(
            split_feature=pad(inner, np.int32),
            threshold_bin=pad(thr_bin, np.int32),
            split_gain=pad(tree.split_gain, np.float32),
            default_left=pad((tree.decision_type & 2) > 0, bool),
            left_child=pad(tree.left_child, np.int32),
            right_child=pad(tree.right_child, np.int32),
            internal_value=pad(tree.internal_value, np.float32),
            internal_weight=pad(tree.internal_weight, np.float32),
            internal_count=pad(tree.internal_count, np.float32),
            leaf_value=padl(tree.leaf_value, np.float32),
            leaf_weight=padl(tree.leaf_weight, np.float32),
            leaf_count=padl(tree.leaf_count, np.float32),
            leaf_parent=padl(tree.leaf_parent, np.int32),
            leaf_depth=padl(tree.leaf_depth, np.int32),
            cat_bitset=jnp.asarray(cat_bits),
            num_leaves=jnp.int32(nl), row_leaf=jnp.zeros((0,), dtype=jnp.int32))

    def _add_tree_score_train(self, tree: Tree, class_id: int,
                              arrays: Optional[TreeArrays] = None) -> None:
        """train_score += tree(train rows); uses cached row_leaf when available."""
        if arrays is not None and arrays.row_leaf.shape[0] > 0:
            dev = arrays
            leaf = dev.row_leaf
        else:
            dev = self._tree_to_device(tree)
            leaf = route_binned(self.learner.route_bins_matrix(), dev,
                                self.learner.feat,
                                num_leaves=int(self.config.num_leaves))
        vals = jnp.asarray(
            np.concatenate([tree.leaf_value[:tree.num_leaves],
                            np.zeros(max(dev.leaf_value.shape[0]
                                         - tree.num_leaves, 0))]).astype(np.float32))
        self.train_score = self.train_score.at[class_id].add(vals[leaf])

    def _add_tree_score_valid(self, model_idx: int, tree: Tree, class_id: int,
                              vs: dict) -> None:
        dev = self._tree_to_device(tree)
        leaf = route_binned(vs["bins"], dev, self.learner.feat,
                            num_leaves=int(self.config.num_leaves))
        vals = jnp.asarray(
            np.concatenate([tree.leaf_value[:tree.num_leaves],
                            np.zeros(max(dev.leaf_value.shape[0]
                                         - tree.num_leaves, 0))]).astype(np.float32))
        vs["score"] = vs["score"].at[class_id].add(vals[leaf])

    def _add_constant_score(self, value: float, class_id: int) -> None:
        self.train_score = self.train_score.at[class_id].add(value)
        for vs in self.valid_sets:
            vs["score"] = vs["score"].at[class_id].add(value)

    # ---- bagging (gbdt.cpp:160-276) ----

    def _balanced_bagging(self) -> bool:
        """pos/neg_bagging_fraction balanced bagging is active
        (config.h:261-281: needs bagging_freq > 0 and either class fraction
        below 1; label > 0 marks the positive class like the reference's
        BaggingHelper)."""
        cfg = self.config
        return (cfg.bagging_freq > 0
                and (float(cfg.pos_bagging_fraction) < 1.0
                     or float(cfg.neg_bagging_fraction) < 1.0))

    def _bagging(self, it: int) -> None:
        cfg = self.config
        balanced = self._balanced_bagging()
        plain = cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0
        if (balanced or plain) and it % cfg.bagging_freq == 0:
            n = self.num_data
            # The fraction is iteration-invariant, so it is built once: a
            # scalar, or per row — per-class Bernoulli fractions over the
            # SAME stateless uniforms as plain bagging (gbdt.cpp:185-206
            # balanced bagging; independent-draw semantics as documented on
            # _bag_uniforms), and 0 on a sharded learner's padding rows.
            frac = self._bag_frac
            if frac is None:
                if balanced:
                    label = np.asarray(self.train_data.metadata.label)[:n]
                    frac = self._place_rows(jnp.where(
                        jnp.asarray(label > 0),
                        jnp.float32(cfg.pos_bagging_fraction),
                        jnp.float32(cfg.neg_bagging_fraction)))
                elif self._rows_sharded:
                    frac = self._row_valid * jnp.float32(cfg.bagging_fraction)
                else:
                    frac = float(cfg.bagging_fraction)
                self._bag_frac = frac
            seed, freq = int(cfg.bagging_seed), int(cfg.bagging_freq)
            row_ids = (self._row_ids if self._rows_sharded
                       else jnp.arange(n, dtype=jnp.int32))
            # same stateless hash as the fused path, so fused and
            # per-iteration training produce identical masks
            mask, cnt = self._row_program(
                "bag_mask",
                lambda ids, it_, frac_: _bag_mask_for(ids, seed, it_, freq,
                                                      frac_),
                row_ids, jnp.int32(it), frac)
            self.bag_mask = self.learner.pad_rows(mask)
            self.bag_data_cnt = int(cnt)
        elif self.bag_mask is None:
            self.bag_data_cnt = self.num_data

    def _features_used(self) -> int:
        return features_used(self.train_data.num_features,
                             float(self.config.feature_fraction))

    def _feature_mask(self, it=None) -> Optional[jnp.ndarray]:
        """The feature mask of iteration ``it`` (this iteration when None;
        a traced scalar inside the fused scan), or None when every feature is
        searched: :func:`feature_mask_of`, which holds no state."""
        nf, used = self.train_data.num_features, self._features_used()
        if used >= nf:
            return None
        return feature_mask_of(nf, used,
                               int(self.config.feature_fraction_seed),
                               self.iter_ if it is None else it)

    # ---- boosting (gbdt.cpp:143-158, 322-368) ----

    def _boost_from_average(self, class_id: int, update_scorer: bool) -> float:
        if (not self._models and not self._has_init_score
                and self.objective is not None):
            if self.config.boost_from_average or self.train_data.num_features == 0:
                init_score = self.objective.boost_from_score(class_id)
                if abs(init_score) > K_EPSILON:
                    if update_scorer:
                        self._add_constant_score(init_score, class_id)
                    Log.info("Start training from score %f", init_score)
                    return init_score
            elif self.objective.name in ("regression_l1", "quantile", "mape"):
                Log.warning("Disabling boost_from_average in %s may cause the "
                            "slow convergence", self.objective.name)
        return 0.0

    def _place_rows(self, arr) -> jax.Array:
        """A per-row array (rows last) where this booster keeps its rows."""
        if self._rows_sharded:
            return self.learner.shard_rows(arr)
        return jnp.asarray(arr)

    def _row_program(self, name: str, fn, *args):
        """``fn(*args)``, a step of the iteration on per-row arrays.  Where
        the rows are sharded it runs as ONE jitted program kept under
        ``name`` (arrays ``fn`` closes over become arguments, so they keep
        their sharding): the iteration's programs can then be listed and
        read (:meth:`iteration_program_texts`).  ``fn`` must close over
        nothing that changes between calls.  Elsewhere it runs as it is."""
        if not self._rows_sharded:
            return fn(*args)
        prog = self._row_fns.get(name)
        if prog is None:
            prog = self._row_fns[name] = _hoisted_jit(fn, *args)
            prog.specs = arg_specs(args)
        out = prog(*args)
        _recompile.note_dispatch("row_program", name, prog._cache_size(),
                                 watch="row_program/%s/%d" % (name, id(prog)))
        return out

    def iteration_program_texts(self) -> Optional[List[str]]:
        """The compiled texts of the programs one boosting iteration runs
        when the rows are sharded (bag mask, gradients, masking, the
        learner's build, score update) — those that have run.  None when
        per-row state is not sharded.  Lowers again and asks the compiler,
        which the persistent cache answers."""
        if not self._rows_sharded:
            return None
        for name, prog in self._row_fns.items():
            if name not in self._row_texts:
                self._row_texts[name] = prog.lower(
                    *prog.specs).compile().as_text()
        build = self.learner.compiled_build()
        return list(self._row_texts.values()) + (
            [build.as_text()] if build is not None else [])

    def count_row_collectives(self) -> Optional[int]:
        """Collectives of :meth:`iteration_program_texts` with a row-sized
        operand or result; noted in ``obs.comm``.  The data-parallel
        contract says 0."""
        texts = self.iteration_program_texts()
        if texts is None:
            return None
        rows = self.num_data + self.learner.padded_rows
        n = _comm.count_row_collectives(
            texts, (rows, rows // self.learner.num_shards))
        _comm.note_row_collectives(n)
        return n

    def _get_gradients(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        K, obj = self.num_tree_per_iteration, self.objective

        def gradients(score):
            if K == 1:
                g, h = obj.get_gradients(score[0])
                return g[None, :], h[None, :]
            return obj.get_gradients(score)

        if self._rows_sharded:
            # scores and the objective's constants at the learner's row
            # count; the padding rows' values are masked before the build
            return self._row_program("gradients", gradients,
                                     self.train_score)
        return gradients(self.train_score[:, :self.num_data])

    def get_training_score(self) -> jnp.ndarray:
        """Scores used for gradient computation this iteration (DART overrides)."""
        return self.train_score

    # ---- the iteration ----

    _poll_freq = 16

    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None) -> bool:
        """Returns True when training cannot continue (no splittable leaves)."""
        # freshness provenance for the quality plane (obs/quality.py):
        # seconds_behind gauges measure from the last trained iteration
        self.trained_at = time.time()
        use_lazy = (self.lazy_trees
                    and not (self.objective is not None
                             and self.objective.is_renew_tree_output))
        if not use_lazy:
            return self._train_one_iter_sync(gradients, hessians)

        K = self.num_tree_per_iteration
        init_scores = [0.0] * K
        if gradients is None or hessians is None:
            for k in range(K):
                init_scores[k] = self._boost_from_average(k, True)
            with _spans.span("gbdt.gradients"):
                grad, hess = self._get_gradients()
        else:
            grad = np.asarray(gradients, dtype=np.float32).reshape(
                K, self.num_data)
            hess = np.asarray(hessians, dtype=np.float32).reshape(
                K, self.num_data)
        grad, hess, skip = self._guard_gradients(grad, hess)
        if skip:
            return self._skip_iteration(init_scores)
        grad = jnp.asarray(grad)
        hess = jnp.asarray(hess)
        if self._nan_policy == "raise" and gradients is None:
            # async detection: the reduction rides the device queue and is
            # fetched in the next _poll_stop batch — no per-iteration sync
            self._fin_handles.append(
                (self.iter_, self._row_program(
                    "finite", lambda g, h: (jnp.isfinite(g).all()
                                            & jnp.isfinite(h).all()),
                    grad, hess)))
        with _spans.span("gbdt.bagging"):
            self._bagging(self.iter_)
            grad, hess = self._adjust_gradients_for_bagging(grad, hess)

        feature_mask = self._feature_mask()
        self._last_iter_arrays = []
        any_trained = False
        for k in range(K):
            if self.class_need_train[k] and self.train_data.num_features > 0:
                any_trained = True
                gk, hk = self._masked_gradients(grad[k], hess[k])
                with _spans.span("gbdt.train_tree"):
                    arrays = self.learner.train(gk, hk, self.bag_data_cnt,
                                                feature_mask,
                                                iteration=self.iter_)
                rate = self.shrinkage_rate
                scaled = arrays._replace(
                    leaf_value=arrays.leaf_value * rate,
                    internal_value=arrays.internal_value * rate)
                with _spans.span("gbdt.update_score"):
                    self._add_tree_output(scaled, k)
                    for vs in self.valid_sets:
                        self._route_arrays_valid(scaled, k, vs)
                idx = len(self._models)
                self._models.append(None)
                self._pending[idx] = (scaled, init_scores[k])
                self._nl_handles.append((self.iter_, idx, scaled.num_leaves))
                self._last_iter_arrays.append(scaled)
            else:
                new_tree = Tree(1)
                if len(self._models) < K:
                    output = (self.objective.boost_from_score(k)
                              if (not self.class_need_train[k]
                                  and self.objective is not None)
                              else init_scores[k])
                    new_tree.leaf_value[0] = output
                    if abs(output) > K_EPSILON:
                        self._add_constant_score(output, k)
                self._models.append(new_tree)
                self._last_iter_arrays.append(None)

        if not any_trained:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True
        self.iter_ += 1
        _sampling.record_trees("per_iteration", K, self.bag_data_cnt)
        if self.iter_ - self._last_poll >= self._poll_freq:
            return self._poll_stop()
        return False

    # ---- fused multi-iteration training ----
    #
    # Per-iteration training makes ~10 jitted dispatches per tree, each a
    # host round-trip with the device idle in between.
    # When the iteration has no host-side decisions (no leaf renewal,
    # device-traceable objective, serial learner) the whole k-iteration
    # boosting loop runs as ONE compiled lax.scan: gradients -> tree build ->
    # score update per step, trees emitted as stacked TreeArrays.  Validation
    # sets ride the scan as extra score carries (each tree routes the valid
    # bins on device; metrics are computed on the host at chunk ends, which
    # train() aligns to metric_freq).  Row and column subsampling ride it
    # too, both stateless functions of the iteration: bagging is an in-scan
    # deterministic hash mask (_bag_uniforms) and feature_fraction an in-scan
    # per-tree feature mask (feature_mask_of), each entered only when it is
    # on, so a program that samples nothing is the program it always was.

    fuse_iters = True  # subclasses with per-iteration host logic opt out
    # ... and so do those whose iteration reads whole-table arrays on one
    # device (GOSS's top-k, DART's dropped trees, RF's cached gradients):
    # their per-row state is not created with a row-sharding learner's rows
    shard_row_state = True

    def _fuse_refusal(self) -> Optional[str]:
        """Why ``train_chunk`` trains per iteration, in a few words, or None
        when it runs fused chunks.  ``feature_fraction`` and plain bagging
        refuse nothing: both are drawn inside the scan."""
        if not self.fuse_iters:
            return "boosting=%s decides on the host every iteration" \
                % type(self).__name__.lower()
        if not self.lazy_trees:
            return "host trees are built eagerly (lazy_trees off)"
        if self.objective is None:
            return "no objective (custom gradients)"
        if self.objective.is_renew_tree_output:
            return "objective=%s renews leaf outputs on the host" \
                % self.objective.name
        if not self.objective.deterministic_gradients:
            return "objective=%s draws its gradients from a host stream" \
                % self.objective.name
        if not self.train_data.num_features:
            return "the data set has no usable feature"
        if not all(self.class_need_train):
            return "a class needs no training"
        if self._balanced_bagging():
            # the in-scan mask hashes original row ids against ONE scalar
            # fraction; per-class fractions need the labels, which do not
            # ride the (permuted) row store — per-iteration path applies them
            return "pos/neg_bagging_fraction need the labels beside the bag"
        if getattr(self.learner, "comm", None) is not None:
            return "tree_learner=%s builds under shard_map, one tree a call" \
                % self.config.tree_learner
        if getattr(self.learner, "cegb", None) is not None:
            return "CEGB carries feature-used state across iterations"
        if self._fuse_failed:
            return "a fused chunk failed earlier (non-finite scores or an " \
                   "objective that does not trace)"
        return None

    def _can_fuse_iters(self) -> bool:
        return self._fuse_refusal() is None

    _fuse_failed = False

    def _fused_bag(self):
        """(fraction, freq) when bagging is active (fused in-scan mask)."""
        cfg = self.config
        if cfg.bagging_freq > 0 and float(cfg.bagging_fraction) < 1.0:
            return float(cfg.bagging_fraction), int(cfg.bagging_freq)
        return None

    def _trees_per_chunk(self) -> int:
        """Round-12 ``trees_per_chunk``: consecutive boosting iterations
        grouped into one fused-scan step so several small trees share a scan
        step's dispatch cost.  Bit-exact vs 1 (same step sequence)."""
        return max(1, int(getattr(self.config, "trees_per_chunk", 1) or 1))

    def _can_carry_rows(self) -> bool:
        """Carried-row-store training: per-row boosting state (aux, score)
        rides the tree builder's permutation so no per-row gather/scatter
        happens between iterations.  Needs a single-model pointwise objective
        with no sample weights and the serial partitioned learner."""
        if self.num_tree_per_iteration != 1:
            return False
        if self.objective is None or self.objective.carry_aux() is None:
            return False
        if type(self.learner).__name__ != "SerialTreeLearner":
            return False
        return True

    def _make_fused_train_carried(self, k: int):
        objective = self.objective
        learner = self.learner
        rate = float(self.shrinkage_rate)
        n = self.num_data
        ntot = n + learner.padded_rows
        feat = learner.feat
        fm = jnp.ones((self.train_data.num_features,), bool)
        nd = jnp.int32(n)
        lay = learner.row_layout()
        voff, soff = lay["voff"], lay["soff"]
        aux = learner.pad_rows(objective.carry_aux().astype(jnp.float32))
        kwargs = dict(num_leaves=learner.num_leaves,
                      max_depth=learner.max_depth, params=learner.params,
                      num_bins=learner.num_bins, use_pallas=learner.use_pallas,
                      has_categorical=learner.has_categorical,
                      has_monotone=learner.has_monotone,
                      feat_num_bins=learner.feat_bins,
                      unpack_lanes=learner.unpack_lanes,
                      forced=learner.forced,
                      packed_cols=learner.packed_cols,
                      hist_pool_slots=learner.hist_pool_slots,
                      # round-7 size-bucketed fused kernels: the plan is
                      # trace-static (derived from the static row count or
                      # pinned by the learner), so the whole lax.scan still
                      # compiles once; only the per-split window size picks
                      # the branch at run time
                      bucket_plan=learner.bucket_plan,
                      pallas_interpret=learner.pallas_interpret,
                      tree_grow_mode=learner.effective_grow_mode(),
                      hist_precision=learner.hist_precision,
                      quant_seed=learner.quant_seed,
                      carried=True)

        bag = self._fused_bag()
        bag_seed = int(self.config.bagging_seed)
        sample_features = self._features_used() < self.train_data.num_features
        vbins = [vs["bins"] for vs in self.valid_sets]
        L = learner.num_leaves

        live, grad_fn = _carried_fns(objective, n, bag, bag_seed)

        def one_iter_of(bins):
            def one_iter(carry, it):
                rows, sums, vscores = carry
                nd_it, fm_it = nd, fm
                if bag is not None or sample_features:
                    # what this tree samples, each drawn only when it is on
                    with jax.named_scope("gbdt.sample"):
                        if bag is not None:
                            # the bagged row count depends on order, it and
                            # the seed only: one column of the store
                            nd_it = jnp.maximum(
                                jnp.sum(live(i32_col(rows, voff + 8), it),
                                        dtype=jnp.float32),
                                1.0).astype(jnp.int32)
                        if sample_features:
                            fm_it = self._feature_mask(it)
                # the store's gradient bytes are current: the last tree's
                # pass (or the prologue) wrote them; this tree's pass writes
                # its score and the gradients of iteration it + 1
                arr, rows, sums = build_tree_partitioned(
                    bins, None, None, nd_it, fm_it, feat,
                    rows_carry=rows, root_sums=sums,
                    score_rate=jnp.float32(rate), quant_it=it,
                    grad_fn=grad_fn, **kwargs)
                arr = arr._replace(
                    leaf_value=arr.leaf_value * rate,
                    internal_value=arr.internal_value * rate)
                vscores = _add_valid_outputs(
                    vscores, 0, arr, feat, vbins, L,
                    learner.has_categorical)
                out = (arr,)
                return (rows, sums, vscores), (
                    out if bag is None else (out, nd_it))
            return one_iter

        def fused(score, vscores, it0):
            bins, aux_arg = learner.bins, aux
            score0 = score[0, :ntot]
            # the first tree's gradients, in the ORIGINAL row order; every
            # later tree's come out of the tree before it (named scopes like
            # the tree builder's: obs/scopes.py)
            with jax.named_scope("gbdt.gradients"):
                g0, h0 = grad_fn(score0, aux_arg,
                                 jnp.arange(ntot, dtype=jnp.int32), it0)
                sums0 = (jnp.sum(g0), jnp.sum(h0))
            # construct the initial store: a num_leaves=1 build whose only
            # effect is the store construction
            init_kwargs = dict(kwargs)
            init_kwargs["num_leaves"] = 1
            # quantization belongs to the trees that read the store
            init_kwargs["hist_precision"] = "exact"
            _, rows0 = build_tree_partitioned(
                bins, g0, h0, nd, fm, feat, extra=(aux_arg, score0),
                **init_kwargs)
            (rows_fin, _, vs_out), stacked = _scan_grouped(
                one_iter_of(bins), (rows0, sums0, tuple(vscores)),
                it0 + jnp.arange(k, dtype=jnp.int32), self._trees_per_chunk())
            # the chunk's epilogue: the score out of the store, by row id
            with jax.named_scope(_scopes.CHUNK_SCORE_OUT):
                score_out = jnp.zeros((ntot,), jnp.float32).at[
                    i32_col(rows_fin, voff + 8)].set(
                        f32_col(rows_fin, soff), mode="drop")
            if bag is not None:
                # a bagged chunk also hands out its k realised bag counts
                stacked, bag_rows = stacked
                return score_out[None], vs_out, stacked, bag_rows
            return score_out[None], vs_out, stacked

        return _hoisted_jit(fused, self.train_score,
                            tuple(vs["score"] for vs in self.valid_sets),
                            jnp.int32(0))

    def _make_fused_train(self, k: int):
        if self._can_carry_rows():
            return self._make_fused_train_carried(k)
        objective = self.objective
        learner = self.learner
        K = self.num_tree_per_iteration
        rate = float(self.shrinkage_rate)
        n = self.num_data
        pad = learner.padded_rows
        feat = learner.feat
        fm = jnp.ones((self.train_data.num_features,), bool)
        nd = jnp.int32(n)
        kwargs = dict(num_leaves=learner.num_leaves,
                      max_depth=learner.max_depth, params=learner.params,
                      num_bins=learner.num_bins, use_pallas=learner.use_pallas,
                      has_categorical=learner.has_categorical,
                      has_monotone=learner.has_monotone,
                      feat_num_bins=learner.feat_bins,
                      unpack_lanes=learner.unpack_lanes,
                      forced=learner.forced,
                      packed_cols=learner.packed_cols,
                      hist_pool_slots=learner.hist_pool_slots,
                      bucket_plan=learner.bucket_plan,
                      pallas_interpret=learner.pallas_interpret,
                      tree_grow_mode=learner.effective_grow_mode(),
                      hist_precision=learner.hist_precision,
                      quant_seed=learner.quant_seed)

        bag = self._fused_bag()
        bag_seed = int(self.config.bagging_seed)
        sample_features = self._features_used() < self.train_data.num_features
        vbins = [vs["bins"] for vs in self.valid_sets]
        L = learner.num_leaves

        def one_iter_of(bins):
            def one_iter(carry, it):
                score, vscores = carry
                nd_it, fm_it = nd, fm
                if bag is not None or sample_features:
                    # what this tree samples, each drawn only when it is on
                    with jax.named_scope("gbdt.sample"):
                        if bag is not None:
                            frac, freq = bag
                            mask, nd_it = _bag_mask_for(
                                jnp.arange(n, dtype=jnp.int32), bag_seed, it,
                                freq, frac)
                        if sample_features:
                            fm_it = self._feature_mask(it)
                with jax.named_scope("gbdt.gradients"):
                    live = score[:, :n]
                    g, h = objective.get_gradients(
                        live[0] if K == 1 else live)
                    g = jnp.reshape(g, (K, n))
                    h = jnp.reshape(h, (K, n))
                    if bag is not None:
                        g = g * mask[None, :]
                        h = h * mask[None, :]
                outs = []
                for kk in range(K):
                    gk = jnp.pad(g[kk], (0, pad))
                    hk = jnp.pad(h[kk], (0, pad))
                    arr = build_tree_partitioned(bins, gk, hk, nd_it, fm_it,
                                                 feat, quant_it=it, **kwargs)
                    arr = arr._replace(
                        leaf_value=arr.leaf_value * rate,
                        internal_value=arr.internal_value * rate)
                    with jax.named_scope(_scopes.CHUNK_SCORE_OUT):
                        score = score.at[kk].add(
                            arr.leaf_value[arr.row_leaf])
                    vscores = _add_valid_outputs(
                        vscores, kk, arr, feat, vbins, L,
                        learner.has_categorical)
                    outs.append(arr)
                outs = tuple(outs)
                return (score, vscores), (
                    outs if bag is None else (outs, nd_it))
            return one_iter

        def fused(score, vscores, it0):
            (score, vs_out), stacked = _scan_grouped(
                one_iter_of(learner.bins), (score, tuple(vscores)),
                it0 + jnp.arange(k, dtype=jnp.int32), self._trees_per_chunk())
            if bag is not None:
                # a bagged chunk also hands out its k realised bag counts
                stacked, bag_rows = stacked
                return score, vs_out, stacked, bag_rows
            return score, vs_out, stacked

        return _hoisted_jit(fused, self.train_score,
                            tuple(vs["score"] for vs in self.valid_sets),
                            jnp.int32(0))

    def chunk_program_text(self, num_iters: int) -> Optional[str]:
        """The compiled text of the fused ``num_iters``-tree chunk program
        this booster has run, or None when it has run none.  Every
        instruction's ``op_name`` carries the program's named scopes
        (``obs.scopes.op_scopes`` reads them), and a profiler trace names its
        device events by these instructions.  Lowers again and asks the
        compiler, which the persistent cache answers."""
        lowered = self._lowered_chunk(num_iters)
        return None if lowered is None else lowered.compile().as_text()

    def _lowered_chunk(self, num_iters: int):
        """That chunk program lowered and not yet compiled (its StableHLO is
        the program as written, before any compiler's rewriting), or None."""
        fn = self._fused_cache.get(
            (num_iters, self.shrinkage_rate, self.num_tree_per_iteration,
             len(self.valid_sets)))
        if fn is None:
            return None
        return fn.lower(self.train_score,
                        tuple(vs["score"] for vs in self.valid_sets),
                        jnp.int32(0))

    def _objective_traceable(self) -> bool:
        """Whether the objective's gradients trace under jit (an objective
        that computes on the host does not).  Probed on the objective ALONE:
        the fused program's own trace and compile errors must propagate, not
        drop training to the per-iteration path."""
        K, n = self.num_tree_per_iteration, self.num_data
        score = jax.ShapeDtypeStruct((n,) if K == 1 else (K, n), jnp.float32)
        try:
            jax.eval_shape(self.objective.get_gradients, score)
        except Exception as exc:  # noqa: BLE001 - arbitrary objective code
            Log.warning("objective %s is not jit-traceable (%s: %s); "
                        "training per iteration instead of fused chunks",
                        type(self.objective).__name__, type(exc).__name__,
                        exc)
            return False
        return True

    def train_chunk(self, num_iters: int) -> bool:
        """Run up to ``num_iters`` boosting iterations; fused into one XLA
        program when the configuration allows, else per-iteration.  Returns
        True when training stopped (no more splittable leaves)."""
        if num_iters <= 0:
            return False
        self.trained_at = time.time()  # quality-plane freshness provenance
        # pre-chunk state refs for the per-chunk non-finite rollback; jax
        # arrays are immutable so holding them costs nothing
        self._prechunk = (self.train_score,
                          tuple(vs["score"] for vs in self.valid_sets),
                          len(self._models), self.iter_,
                          self.bag_mask, self.bag_data_cnt)
        refused = self._fuse_refusal()
        if refused is not None:
            if refused not in self._fuse_refusals_said:
                # once a reason: a job meant for the fused path that trains
                # one tree a host round says so, here and in the spans
                self._fuse_refusals_said.add(refused)
                Log.info("train_chunk trains per iteration, not in fused "
                         "chunks: %s", refused)
                _spans.note("gbdt.per_iteration: " + refused, 0.0)
            tele = _telemetry_active()
            t0 = time.perf_counter()
            it0 = self.iter_
            stopped = False
            for _ in range(num_iters):
                if self.train_one_iter():
                    stopped = True
                    break
            if tele is not None:
                self._record_chunk_telemetry(tele, it0,
                                             time.perf_counter() - t0,
                                             fused=False)
            return stopped
        key = (num_iters, self.shrinkage_rate, self.num_tree_per_iteration,
               len(self.valid_sets))
        fn = self._fused_cache.get(key)
        chunk_compiled = fn is None
        if fn is None:
            # probe BEFORE any state mutation so the fallback path does not
            # re-apply boost_from_average
            if not self._objective_traceable():
                self._fuse_failed = True
                return self.train_chunk(num_iters)
            # traces eagerly (_hoisted_jit runs make_jaxpr at construction):
            # a kernel's trace error surfaces here and propagates
            with _spans.span("gbdt.fused.trace"):
                fn = self._make_fused_train(num_iters)
            self._fused_cache[key] = fn
            # the fused k-iteration scan compiled a fresh XLA program; a
            # steady-state run reuses config-keyed chunk lengths, so this
            # counter going flat after warmup IS the no-recompile invariant
            _recompile.record("fused_train", "k=%d" % num_iters)
        init_scores = [self._boost_from_average(kk, True)
                       for kk in range(self.num_tree_per_iteration)]
        t0 = time.perf_counter()
        with _spans.span("fused_train_chunk"), \
                _watch("fused_train_chunk", compile_key=int(num_iters),
                       first_iter=int(self.iter_), iters=int(num_iters)):
            new_score, new_vscores, stacked, *bag_rows = fn(
                self.train_score,
                tuple(vs["score"] for vs in self.valid_sets),
                jnp.int32(self.iter_))
        self.train_score = new_score
        for vs, vsc in zip(self.valid_sets, new_vscores):
            vs["score"] = vsc
        K = self.num_tree_per_iteration
        # the fused scan ran one tree build per in-scan iteration — account
        # its (trace-static) split-launch structure like the per-iteration
        # path does in SerialTreeLearner.train
        _launches.record(self.learner.effective_grow_mode(),
                         self.learner.launches_per_tree(),
                         trees=num_iters * K)
        # the k bag counts stay on the device until somebody asks
        _sampling.record_trees("fused", num_iters * K,
                               bag_rows[0] if bag_rows else self.num_data)
        first_idx = len(self._models)
        first_iter = self.iter_
        self._last_iter_arrays = []
        for i in range(num_iters):
            for kk in range(K):
                idx = len(self._models)
                self._models.append(None)
                self._pending[idx] = (_LazyTreeSlice(stacked[kk], i),
                                      init_scores[kk] if i == 0 else 0.0)
        self._nl_handles.append(
            (first_iter, first_idx,
             jnp.stack([s.num_leaves for s in stacked], axis=1)))
        self._last_iter_arrays = [_LazyTreeSlice(stacked[kk], num_iters - 1)
                                  for kk in range(K)]
        self.iter_ += num_iters
        Log.debug("%f seconds elapsed, dispatched iterations %d-%d",
                  time.perf_counter() - t0, first_iter + 1, self.iter_)
        tele = _telemetry_active()
        if tele is not None:
            self._record_chunk_telemetry(tele, first_iter,
                                         time.perf_counter() - t0,
                                         fused=True,
                                         compile_key="k=%d" % num_iters,
                                         compiles=1 if chunk_compiled
                                         else 0)
        if self.iter_ - self._last_poll >= self._poll_freq:
            return self._poll_stop()
        return False

    def _record_chunk_telemetry(self, tele, first_iter: int, dt: float,
                                fused: bool, compile_key=None,
                                compiles: int = 0) -> None:
        """Per-chunk metrics/events; the chunk is the host-work granularity
        of the async pipeline, so telemetry-off runs are untouched per
        iteration.  ``dt`` is the host DISPATCH wall (device completion is
        async); end-to-end run walls come from the run driver's gauges.
        ``compile_key``/``compiles`` feed the compile accountant
        (obs/compile.py): a chunk that traced a fresh fused program is
        priced against the steady chunks that follow it."""
        iters = self.iter_ - first_iter
        if iters <= 0:
            return
        rows = float(self.num_data) * iters
        tele.histogram("chunk_dispatch_s").observe(dt)
        rate = rows / dt if dt > 0 else 0.0
        tele.histogram("chunk_rows_per_s").observe(rate)
        tele.histogram("chunk_ns_per_row").observe(
            dt / rows * 1e9 if rows else 0.0)
        tele.gauge("bag_data_cnt").set(self.bag_data_cnt)
        tele.event("train_chunk", first_iter=int(first_iter),
                   iters=int(iters), dt_s=dt, rows_per_s=rate,
                   fused=bool(fused), bag_data_cnt=int(self.bag_data_cnt))
        if compile_key is not None:
            _compile.note_dispatch(tele, "fused_train", compile_key, dt,
                                   int(compiles))
        # kernel-plan provenance (round 18): the fused-scan path consumes
        # the learner's resolved plan through bucket_plan without calling
        # learner.train, so the stamp rides the chunk telemetry (deduped
        # per run by plan.state)
        learner = getattr(self, "learner", None)
        if learner is not None:
            from ..plan import state as _plan_state
            plan = getattr(learner, "plan", None)
            prov = plan.provenance if plan is not None else "analytic"
            if getattr(learner, "bucket_plan", None) is not None \
                    and prov == "analytic":
                prov = "pinned"
            _plan_state.stamp(tele, "tree_build", prov,
                              key="n%d_b%d" % (int(learner.num_data),
                                               int(learner.num_bins)),
                              mode=str(getattr(learner, "tree_grow_mode",
                                               "leaf")))
        # round-22 quantized-gradient training: the quant path's static
        # facts ride each chunk as counters/gauges + one raw event, so a
        # died run's JSONL still carries the whole quant block (the
        # summary writer may never run); exact runs emit NOTHING here
        if learner is not None and getattr(learner, "hist_precision",
                                           "exact") == "quantized":
            from ..core.histogram import _hist_channels
            from ..core.quant import GRAD_LEVELS, HESS_LEVELS
            tele.counter("quant_chunks").inc()
            tele.counter("quant_iters").inc(int(iters))
            tele.gauge("quant_grad_levels").set(GRAD_LEVELS)
            tele.gauge("quant_hess_levels").set(HESS_LEVELS)
            tele.gauge("quant_hist_channels").set(_hist_channels(True))
            tele.event("quant", first_iter=int(first_iter),
                       iters=int(iters), grad_levels=int(GRAD_LEVELS),
                       hess_levels=int(HESS_LEVELS),
                       hist_channels=int(_hist_channels(True)),
                       exact_channels=int(_hist_channels(False)),
                       collective_dtype=("bfloat16" if getattr(
                           learner, "comm", None) is not None else ""))
        # HBM high-water stamp per chunk (obs/devmem.py): import-safe,
        # quietly empty on backends without memory_stats
        _devmem.sample(tele, phase="train_chunk")
        # span under the run trace: chunks line up as the training
        # lifeline in the Chrome-trace render (obs/spans.py)
        _spans.record_span(tele, "train_chunk", t0=time.time() - dt,
                           dur_s=dt, trace_id=tele.trace_id,
                           first_iter=int(first_iter), iters=int(iters),
                           fused=bool(fused))

    def _train_one_iter_sync(self, gradients: Optional[np.ndarray] = None,
                             hessians: Optional[np.ndarray] = None) -> bool:
        """Synchronous path (host Tree per iteration): DART and leaf-renewal
        objectives need host trees eagerly."""
        init_scores = [0.0] * self.num_tree_per_iteration
        if gradients is None or hessians is None:
            for k in range(self.num_tree_per_iteration):
                init_scores[k] = self._boost_from_average(k, True)
            with _spans.span("gbdt.gradients"):
                grad, hess = self._get_gradients()
        else:
            grad = np.asarray(gradients, dtype=np.float32).reshape(
                self.num_tree_per_iteration, self.num_data)
            hess = np.asarray(hessians, dtype=np.float32).reshape(
                self.num_tree_per_iteration, self.num_data)
        grad, hess, skip = self._guard_gradients(grad, hess, force_check=True)
        if skip:
            return self._skip_iteration(init_scores)
        grad = jnp.asarray(grad)
        hess = jnp.asarray(hess)

        with _spans.span("gbdt.bagging"):
            self._bagging(self.iter_)
            grad, hess = self._adjust_gradients_for_bagging(grad, hess)

        should_continue = False
        self._last_iter_arrays = []
        feature_mask = self._feature_mask()
        for k in range(self.num_tree_per_iteration):
            new_tree = Tree(1)
            arrays = None
            if self.class_need_train[k] and self.train_data.num_features > 0:
                gk, hk = self._masked_gradients(grad[k], hess[k])
                with _spans.span("gbdt.train_tree"):
                    arrays = self.learner.train(gk, hk, self.bag_data_cnt,
                                                feature_mask,
                                                iteration=self.iter_)
                nl = int(arrays.num_leaves)
                if nl > 1:
                    new_tree = self.learner.host_tree(arrays)

            if new_tree.num_leaves > 1:
                should_continue = True
                arrays = self._renew_tree_output(new_tree, arrays, k)
                new_tree.shrink(self.shrinkage_rate)
                scaled = arrays._replace(
                    leaf_value=arrays.leaf_value * self.shrinkage_rate)
                with _spans.span("gbdt.update_score"):
                    self._add_tree_output(scaled, k)
                    for vs in self.valid_sets:
                        self._add_tree_score_valid(len(self.models), new_tree, k,
                                                   vs)
                if abs(init_scores[k]) > K_EPSILON:
                    new_tree.add_bias(init_scores[k])
                self._last_iter_arrays.append(scaled)
            else:
                if len(self.models) < self.num_tree_per_iteration:
                    if not self.class_need_train[k] and self.objective is not None:
                        output = self.objective.boost_from_score(k)
                    else:
                        output = init_scores[k]
                    new_tree = Tree(1)
                    new_tree.leaf_value[0] = output
                    if abs(output) > K_EPSILON:
                        self._add_constant_score(output, k)
                self._last_iter_arrays.append(None)
            self.models.append(new_tree)

        if not should_continue:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            return True
        self.iter_ += 1
        _sampling.record_trees("per_iteration", self.num_tree_per_iteration,
                               self.bag_data_cnt)
        return False

    def _adjust_gradients_for_bagging(self, grad, hess):
        return grad, hess

    # ---- non-finite guards (nan_policy: raise / skip_iter / clip) ----
    #
    # One bad batch — a poisoned label, an overflowing custom gradient —
    # yields NaN/inf grad/hess, and every later tree inherits it through the
    # score carry.  The guard is a cheap isfinite reduction with a policy:
    # ``raise`` (default) fails fast naming the iteration, ``skip_iter``
    # advances the iteration with a constant zero tree, ``clip`` sanitizes
    # (NaN -> 0, +-inf -> +-1e35) and keeps training.  On the async lazy
    # path the raise-policy reduction rides the _poll_stop fetch; resilient
    # policies pay a per-iteration sync by design.  Score-level corruption
    # on the fused path is caught per-chunk (_guard_chunk_scores) and rolled
    # back to the pre-chunk state refs.

    _NAN_CLIP = np.float32(1e35)
    # pre-chunk score/model refs fully describe a chunk's effects; DART's
    # in-place mutation of older trees breaks that, so it opts out of the
    # rollback-retry and stops at detection instead
    _prechunk_rollback_safe = True

    @property
    def _nan_policy(self) -> str:
        return str(getattr(self.config, "nan_policy", "raise"))

    @staticmethod
    def _nan_trip_telemetry(iteration: int, policy: str, action: str) -> None:
        """Cold-path accounting for non-finite guard trips."""
        tele = _telemetry_active()
        if tele is not None:
            tele.counter("nan_policy_trips").inc()
            if action == "rollback_retry":
                tele.counter("nan_rollback_retries").inc()
            tele.event("nan_trip", iteration=int(iteration), policy=policy,
                       action=action)

    @staticmethod
    def _raise_nonfinite(iteration: int) -> None:
        GBDT._nan_trip_telemetry(iteration, "raise", "raise")
        raise LightGBMError(
            "non-finite gradients/hessians/scores at iteration %d "
            "(nan_policy=raise); set nan_policy=skip_iter or clip to "
            "degrade gracefully instead" % iteration)

    def _drain_nonfinite_checks(self) -> None:
        """Fetch any pending isfinite reductions (nan_policy=raise) without
        the stall-trim poll — the end-of-training drain for paths that do
        not finish through train() (engine.train's update loop), and for
        the trailing < _poll_freq iterations."""
        if not self._fin_handles:
            return
        fins = jax.device_get([f for _, f in self._fin_handles])
        bad = [it for (it, _), ok in zip(self._fin_handles, fins)
               if not bool(ok)]
        self._fin_handles = []
        if bad:
            self._raise_nonfinite(bad[0])

    def _guard_gradients(self, grad, hess, force_check: bool = False):
        """(grad, hess, skip): per-iteration non-finite guard.

        Host arrays (custom gradients) are always checked — the check is
        free.  Device arrays are checked when the policy is resilient or
        ``force_check`` (synchronous paths); under the default ``raise``
        policy the lazy path defers to the batched _poll_stop fetch
        instead, so the async pipeline keeps its zero-sync property."""
        policy = self._nan_policy
        host = isinstance(grad, np.ndarray)
        if not host and policy == "raise" and not force_check:
            return grad, hess, False
        xp = np if host else jnp
        finite = bool(xp.isfinite(grad).all()) and bool(xp.isfinite(hess).all())
        if finite:
            return grad, hess, False
        if policy == "raise":
            self._raise_nonfinite(self.iter_)
        if policy == "skip_iter":
            Log.warning("non-finite gradients/hessians at iteration %d; "
                        "skipping the iteration (nan_policy=skip_iter)",
                        self.iter_)
            self._nan_trip_telemetry(self.iter_, policy, "skip_iter")
            return grad, hess, True
        Log.warning("non-finite gradients/hessians at iteration %d; "
                    "clipping (nan_policy=clip)", self.iter_)
        self._nan_trip_telemetry(self.iter_, policy, "clip")
        grad = xp.nan_to_num(grad, nan=0.0, posinf=self._NAN_CLIP,
                             neginf=-self._NAN_CLIP)
        # hessians are curvature weights: non-negative by contract
        hess = xp.nan_to_num(hess, nan=0.0, posinf=self._NAN_CLIP, neginf=0.0)
        return grad, hess, False

    def _skip_iteration(self, init_scores: Optional[List[float]] = None
                        ) -> bool:
        """nan_policy=skip_iter: advance the iteration with constant trees
        so model/iteration bookkeeping stays aligned while the scores stay
        untouched by the bad batch.  A first-iteration skip must still
        carry the boost_from_average offset (already added to the scores
        before gradients were computed) into the model, or every saved
        prediction would be shifted by it."""
        for k in range(self.num_tree_per_iteration):
            tree = Tree(1)
            if init_scores is not None and len(self._models) < \
                    self.num_tree_per_iteration:
                tree.leaf_value[0] = init_scores[k]
            self._models.append(tree)
        self._last_iter_arrays = [None] * self.num_tree_per_iteration
        self.iter_ += 1
        return False

    def _guard_chunk_scores(self) -> bool:
        """Per-chunk isfinite reduction over the training scores (the carry
        every future iteration reads).  Returns True when training must stop
        at the restored last-good state; False to continue.  raise policy
        raises.  On the first corruption with a resilient policy the chunk
        is rolled back to the pre-chunk refs and re-run per-iteration
        (where _guard_gradients can skip/clip the bad batch); if the same
        chunk corrupts twice, training stops at the last good iteration.

        Under the default ``raise`` policy there is no rollback to stage, so
        the reduction rides the _poll_stop/_drain batch as a lazy handle —
        the async pipeline keeps its zero-sync property; only the resilient
        policies pay the per-chunk host sync their rollback needs."""
        with _spans.span("gbdt.guard_chunk_scores"):
            finite = jnp.isfinite(self.train_score).all()
            if self._nan_policy != "raise":
                finite = bool(finite)
        if self._nan_policy == "raise":
            self._prechunk = None
            self._fin_handles.append((self.iter_, finite))
            return False
        if finite:
            self._prechunk = None
            if self._nan_refused_fuse:
                # the retried window completed clean: a TRANSIENT fault is
                # over, re-arm the fused path instead of paying per-iteration
                # dispatch for the rest of the run.  (A persistent poison
                # re-corrupts the next fused chunk and lands back here — one
                # wasted dispatch per chunk, bounded by the _nan_rolled_back
                # latch stopping a same-iteration repeat.)
                self._fuse_failed = False
                self._nan_refused_fuse = False
            return False
        if self._prechunk is None or not self._prechunk_rollback_safe:
            # DART mutates previously committed trees in place (dropout
            # shrink/re-add) and appends tree-weight history per iteration —
            # state the pre-chunk refs cannot restore; stop at detection
            # instead of pretending the rollback is clean
            Log.warning("non-finite training scores after iteration %d with "
                        "no clean rollback state; stopping training",
                        self.iter_)
            return True
        self._restore_prechunk()
        if self._nan_rolled_back_at == self.iter_:
            Log.warning("non-finite scores persist at iteration %d after a "
                        "per-iteration retry; stopping training at the last "
                        "good state (nan_policy=%s)", self.iter_,
                        self._nan_policy)
            return True
        Log.warning("non-finite training scores detected; rolled back to "
                    "iteration %d and retrying per-iteration "
                    "(nan_policy=%s)", self.iter_, self._nan_policy)
        self._nan_trip_telemetry(self.iter_, self._nan_policy,
                                 "rollback_retry")
        self._nan_rolled_back_at = self.iter_
        # re-run the window with per-iteration guards; re-armed once a
        # retried window completes clean (see above)
        self._fuse_failed = True
        self._nan_refused_fuse = True
        return False

    def _restore_prechunk(self) -> None:
        """Roll state back to the refs captured at the last train_chunk
        entry: scores, model list length, bagging window, iteration."""
        score, vscores, n_models, it, bag_mask, bag_cnt = self._prechunk
        self._prechunk = None
        self.train_score = score
        for vs, s in zip(self.valid_sets, vscores):
            vs["score"] = s
        for idx in [i for i in self._pending if i >= n_models]:
            self._pending.pop(idx)
        del self._models[n_models:]
        self.bag_mask = bag_mask
        self.bag_data_cnt = bag_cnt
        self.iter_ = it
        self._window = {i: a for i, a in self._window.items() if i < n_models}
        self._nl_handles = [h for h in self._nl_handles if h[1] < n_models]
        self._fin_handles = []
        self._last_iter_arrays = []
        self._invalidate_predict_cache()

    # ---- fault-tolerant train-state checkpoints (lightgbm_tpu/checkpoint.py) ----

    def capture_train_state(self):
        """(meta, arrays, model_str): EVERYTHING future iterations read.

        The model string alone loses GOSS's sampling stream (the bag and the
        feature mask are stateless functions of the iteration and need
        none), early-stopping bookkeeping, CEGB paid-cost state and the
        f32 score caches, so an init_model resume silently diverges; this
        captures all of it.  Scores go as binary arrays — DART's dropout
        makes the incremental f32 score sum order-dependent, so a replay of
        final leaf values is NOT bit-exact (see checkpoint.py)."""
        from ..checkpoint import encode_rng_state
        if self._nl_handles:
            # settle the deferred no-more-splits poll first: a stalled
            # trailing iteration would otherwise be captured here but
            # TRIMMED by the uninterrupted run's next poll, and the resumed
            # run could never trim below the checkpoint — breaking
            # bit-exactness exactly when training stalls near a boundary
            self._poll_stop()
        from ..checkpoint import dataset_fingerprint
        meta = {
            "boosting": type(self).__name__.lower(),
            "iteration": int(self.iter_),
            # dataset identity + live row count: the resume-vs-wrong-data
            # guard and the elastic (d -> d') reshard both key on these
            "num_data": int(self.num_data),
            "dataset": (dataset_fingerprint(self.train_data)
                        if self.train_data is not None else None),
            "num_init_iteration": int(self.num_init_iteration),
            "shrinkage_rate": float(self.shrinkage_rate),
            "bag_rng": encode_rng_state(self._bag_rng),
            "es_state": [[ds, name, float(cur), int(it)]
                         for (ds, name), (cur, it)
                         in sorted(self._es_state.items())],
            "valid_names": [vs["name"] for vs in self.valid_sets],
            "params": {k: str(v)
                       for k, v in sorted(self.config.raw_params.items())},
            "extra": self._extra_train_state(),
        }
        arrays = {"train_score": np.asarray(self.train_score)}
        for i, vs in enumerate(self.valid_sets):
            arrays["valid_score_%d" % i] = np.asarray(vs["score"])
        ln = self.learner
        if getattr(ln, "cegb_used", None) is not None:
            arrays["cegb_used"] = np.asarray(ln.cegb_used)
        if getattr(ln, "cegb_paid", None) is not None:
            arrays["cegb_paid"] = np.asarray(ln.cegb_paid)
        return meta, arrays, self.save_model_to_string()

    def restore_train_state(self, meta, arrays, model_str) -> None:
        """Inverse of :meth:`capture_train_state`.  Call on a booster whose
        training data AND validation sets are already attached (scores are
        restored positionally over ``valid_sets``); afterwards ``train()``
        continues exactly where the checkpointed run left off."""
        from ..checkpoint import CheckpointError, decode_rng_state
        want = type(self).__name__.lower()
        if meta.get("boosting") != want:
            raise CheckpointError(
                "checkpoint was written by boosting=%r, this booster is %r"
                % (meta.get("boosting"), want))
        names = list(meta.get("valid_names", []))
        have = [vs["name"] for vs in self.valid_sets]
        if names != have:
            # scores are restored positionally: a different order would
            # silently hand each valid set another one's score cache
            raise CheckpointError(
                "checkpoint validation sets %r do not match the attached "
                "ones %r — attach the same valid sets in the same order "
                "before restoring" % (names, have))
        # resume-vs-wrong-data guard: a checkpoint resumed against a
        # DIFFERENT dataset silently trains garbage (the restored score
        # caches describe rows that no longer exist) — hard-error instead
        saved_fp = meta.get("dataset")
        if saved_fp is not None and self.train_data is not None:
            from ..checkpoint import dataset_fingerprint
            cur_fp = dataset_fingerprint(self.train_data)
            diff = [k for k in ("num_rows", "num_features", "bin_digest")
                    if saved_fp.get(k) != cur_fp.get(k)]
            if diff:
                raise CheckpointError(
                    "checkpoint was written against a different dataset "
                    "(%s) — resume needs the same training data"
                    % ", ".join("%s: %r != %r" % (k, saved_fp.get(k),
                                                  cur_fp.get(k))
                                for k in diff))
        ts = np.asarray(arrays["train_score"])
        if tuple(ts.shape) != tuple(self.train_score.shape):
            # elastic resume: the same dataset under a different device
            # count pads the row axis differently ([K, n + pad_d] vs
            # [K, n + pad_d']).  Only the first num_data columns are ever
            # read (gradients, metrics); the pad tail holds routing debris
            # no consumer looks at — so reshard: keep the live columns,
            # re-zero the new pad.  Same-d resume never reaches this branch
            # and stays byte-identical.
            n = self.num_data
            saved_rows = int(meta.get("num_data",
                                      (saved_fp or {}).get("num_rows", -1)))
            if (saved_rows == n and ts.shape[0] == self.train_score.shape[0]
                    and ts.shape[1] >= n):
                pad = self.train_score.shape[1] - n
                ts = np.concatenate(
                    [ts[:, :n], np.zeros((ts.shape[0], pad), ts.dtype)],
                    axis=1)
                Log.warning(
                    "elastic resume: checkpoint score layout %r resharded "
                    "to %r (device count / row padding changed; the %d live "
                    "rows carry over, pad rows re-zeroed)",
                    tuple(np.asarray(arrays["train_score"]).shape),
                    tuple(self.train_score.shape), n)
                tele = _telemetry_active()
                if tele is not None:
                    tele.event("elastic_resume", num_data=int(n),
                               saved_cols=int(np.asarray(
                                   arrays["train_score"]).shape[1]),
                               new_cols=int(self.train_score.shape[1]))
            else:
                raise CheckpointError(
                    "checkpoint train_score shape %r does not match this "
                    "dataset/learner layout %r — resume needs the same "
                    "training data"
                    % (tuple(ts.shape), tuple(self.train_score.shape)))
        # resume assumes the SAME run continuing; differing params mean a
        # stale checkpoint or an edited command — warn loudly, don't guess
        saved_params = meta.get("params")
        if saved_params is not None:
            path_keys = {"output_model", "input_model", "output_result",
                         "config", "task"}
            cur = {k: str(v) for k, v in self.config.raw_params.items()}
            diff = sorted(k for k in set(saved_params) | set(cur)
                          if k not in path_keys
                          and saved_params.get(k) != cur.get(k))
            if diff:
                Log.warning(
                    "resuming a checkpoint whose parameters differ from the "
                    "current run (%s); the resumed model mixes both configs",
                    ", ".join("%s: %r -> %r" % (k, saved_params.get(k),
                                                cur.get(k)) for k in diff))
        self.load_model_from_string(model_str)
        # load_model_from_string treats the model as an init_model (iter_=0,
        # num_init_iteration=total); a RESUME is the same run continuing
        self.iter_ = int(meta["iteration"])
        self.num_init_iteration = int(meta["num_init_iteration"])
        self.shrinkage_rate = float(meta["shrinkage_rate"])
        self._bag_rng.set_state(decode_rng_state(meta["bag_rng"]))
        # (a checkpoint from before the stateless feature mask also carries
        # "feat_rng", a stream nothing draws from any more: not read)
        self._es_state = {(ds, name): (cur, it)
                          for ds, name, cur, it in meta.get("es_state", [])}
        self.train_score = self._place_rows(ts)
        for i, vs in enumerate(self.valid_sets):
            vs["score"] = jnp.asarray(np.asarray(arrays["valid_score_%d" % i]))
        ln = self.learner
        if "cegb_used" in arrays and getattr(ln, "cegb_used", None) is not None:
            ln.cegb_used = jnp.asarray(np.asarray(arrays["cegb_used"]))
        if "cegb_paid" in arrays and getattr(ln, "cegb_paid", None) is not None:
            paid = np.asarray(arrays["cegb_paid"])
            want_rows = int(ln.cegb_paid.shape[0])
            if paid.shape[0] != want_rows and paid.shape[0] >= self.num_data:
                # elastic resume: per-row paid bits follow the score reshard
                # (live rows carry over, pad rows re-zeroed)
                out = np.zeros((want_rows,) + paid.shape[1:], paid.dtype)
                out[:self.num_data] = paid[:self.num_data]
                paid = out
            ln.cegb_paid = jnp.asarray(paid)
        # rebuild the bagging mask for the in-progress window: the stateless
        # hash (_bag_uniforms) regenerates the window-start mask exactly
        cfg = self.config
        if cfg.bagging_freq > 0 and (self._balanced_bagging()
                                     or float(cfg.bagging_fraction) < 1.0):
            itw = self.iter_ - self.iter_ % int(cfg.bagging_freq)
            GBDT._bagging(self, itw)
        self._restore_extra_train_state(meta.get("extra") or {})

    def _extra_train_state(self) -> Dict:
        """Subclass state that must survive a resume (DART overrides)."""
        return {}

    def _restore_extra_train_state(self, extra: Dict) -> None:
        pass

    def save_checkpoint(self, prefix: str, keep: Optional[int] = None) -> str:
        """Atomically write the full train state to
        ``<prefix>.ckpt_iter_<iteration>`` (checkpoint.save_checkpoint)."""
        from ..checkpoint import save_checkpoint
        return save_checkpoint(self, prefix, keep=keep)

    def resume_from_checkpoint(self, prefix: str) -> int:
        """Restore the newest VALID checkpoint for ``prefix`` (corrupt files
        fall back to older ones); returns the restored iteration, 0 when
        none found."""
        from ..checkpoint import restore_checkpoint
        return restore_checkpoint(self, prefix)

    def warm_start_continuation(self, model_str: Optional[str] = None,
                                train_data: Optional[BinnedDataset] = None,
                                objective=None) -> int:
        """Bind this booster to continue a published model — the online
        loop's warm-start contract (never-from-scratch).

        Loads ``model_str`` when given (else keeps the already-loaded
        model), rebinds to ``train_data`` with a blocked binned score
        replay, and — the contract — aligns the training clock to the
        loaded iteration count: ``iter_`` continues ABSOLUTE, so the
        stateless bagging hash (``_bag_uniforms`` keyed by iteration, on
        both the per-iteration and the fused in-scan path) and the
        config-keyed chunk partitioning reproduce exactly the masks and
        programs the uninterrupted run would have used.  That is what
        makes ``train(k)`` → publish → continue-to-``k+m`` byte-identical
        to the checkpoint-resume path at the same boundary
        (tests/test_online.py pins it with bagging on).

        Returns the aligned iteration."""
        if model_str is not None:
            self.load_model_from_string(model_str)
        ds = train_data if train_data is not None else self.train_data
        if ds is None:
            raise LightGBMError("warm_start_continuation needs a training "
                                "dataset to bind the continuation to")
        self.reset_training_data(ds, objective if objective is not None
                                 else self.objective)
        self.replay_train_score()
        # align to the ENSEMBLE, not just num_init_iteration: an
        # in-process-trained booster being rebound to a new window has
        # num_init_iteration == 0 but k trees — rewinding its clock to 0
        # would replay bagging iterations the trees already consumed
        self.iter_ = max(int(self.num_init_iteration),
                         len(self._models)
                         // max(self.num_tree_per_iteration, 1))
        return self.iter_

    def _renew_tree_output(self, tree: Tree, arrays: TreeArrays,
                           class_id: int) -> TreeArrays:
        """Per-leaf output renewal for percentile objectives
        (serial_tree_learner.cpp:706-744 RenewTreeOutput)."""
        if self.objective is None or not self.objective.is_renew_tree_output:
            return arrays
        row_leaf = np.asarray(arrays.row_leaf)[:self.num_data]
        score = np.asarray(self.train_score[class_id, :self.num_data])
        label = self.objective.label_np
        residual = label - score
        if self.objective.name == "mape":
            weights = self.objective.label_weight_np
        else:
            weights = self.objective.weights_np
        bag = (np.asarray(self.bag_mask)[:self.num_data] > 0
               if self.bag_mask is not None else None)
        new_vals = tree.leaf_value.copy()
        for leaf in range(tree.num_leaves):
            rows = row_leaf == leaf
            if bag is not None:
                rows = rows & bag
            if not rows.any():
                continue
            w = None if weights is None else weights[rows]
            new_vals[leaf] = self.objective.renew_tree_output(residual[rows], w)
        tree.leaf_value[:] = new_vals
        return arrays._replace(leaf_value=jnp.asarray(
            np.concatenate([new_vals[:tree.num_leaves],
                            np.zeros(arrays.leaf_value.shape[0]
                                     - tree.num_leaves)]).astype(np.float32)))

    def rollback_one_iter(self) -> None:
        """Undo the last iteration (gbdt.cpp:454-470)."""
        self._invalidate_predict_cache()
        if self.iter_ <= 0:
            return
        for k in range(self.num_tree_per_iteration):
            idx = len(self.models) - self.num_tree_per_iteration + k
            tree = self.models[idx]
            tree.shrink(-1.0)
            arrays = (self._last_iter_arrays[k]
                      if k < len(self._last_iter_arrays) else None)
            if arrays is not None:
                arrays = _resolve_arrays(arrays)
                self.train_score = self.train_score.at[k].add(
                    -self._gather_tree_output(arrays))
            for vs in self.valid_sets:
                self._add_tree_score_valid(idx, tree, k, vs)
        del self.models[-self.num_tree_per_iteration:]
        # the models-property access above emptied _pending (materialization);
        # drop _window/_nl_handles entries for the removed indices so a later
        # stall trim cannot reverse a rolled-back tree's contribution twice
        cut = len(self._models)
        self._window = {i: a for i, a in self._window.items() if i < cut}
        self._nl_handles = [h for h in self._nl_handles if h[1] < cut]
        self.iter_ -= 1
        # the rolled-back iteration's isfinite handle must not raise later
        self._fin_handles = [h for h in self._fin_handles
                             if h[0] < self.iter_]

    def refit(self, leaf_preds: np.ndarray) -> None:
        """Refit the ensemble's leaf values on the current training data.

        Counterpart of ``GBDT::RefitTree`` (gbdt.cpp:299) +
        ``SerialTreeLearner::FitByExistingTree``
        (serial_tree_learner.cpp:199-229): keep every tree's structure, route
        the training rows by ``leaf_preds`` [num_data, num_models], recompute
        each leaf's output from the gradient/hessian sums at the current
        boosting state, blend by ``refit_decay_rate``, and rebuild the train
        scores progressively.
        """
        self._invalidate_predict_cache()
        models = self.models
        leaf_preds = np.asarray(leaf_preds, dtype=np.int32)
        if leaf_preds.ndim != 2 or leaf_preds.shape[0] != self.num_data \
                or leaf_preds.shape[1] != len(models):
            raise ValueError(
                "leaf_preds must be [num_data, num_models] = [%d, %d]"
                % (self.num_data, len(models)))
        K = self.num_tree_per_iteration
        l1 = float(self.config.lambda_l1)
        l2 = float(self.config.lambda_l2)
        mds = float(self.config.max_delta_step)
        decay = float(self.config.refit_decay_rate)
        score = np.zeros((K, self.num_data), dtype=np.float64)
        if self.train_data.metadata.init_score is not None:
            init = np.asarray(self.train_data.metadata.init_score,
                              dtype=np.float64)
            score[:] = init.reshape(K, self.num_data)
        for it in range(len(models) // K):
            g, h = self.objective.get_gradients(
                jnp.asarray(score[0] if K == 1 else score, dtype=jnp.float32))
            grad = np.asarray(g, dtype=np.float64).reshape(K, self.num_data)
            hess = np.asarray(h, dtype=np.float64).reshape(K, self.num_data)
            for k in range(K):
                i = it * K + k
                tree = models[i]
                lp = leaf_preds[:, i]
                nl = tree.num_leaves
                if lp.max(initial=0) >= nl:
                    raise ValueError("leaf prediction out of range for tree %d"
                                     % i)
                sum_g = np.bincount(lp, weights=grad[k], minlength=nl)
                sum_h = np.bincount(lp, weights=hess[k], minlength=nl) + K_EPSILON
                sg = np.sign(sum_g) * np.maximum(np.abs(sum_g) - l1, 0.0)
                out = -sg / (sum_h + l2)
                if mds > 0.0:
                    out = np.clip(out, -mds, mds)
                new_vals = (decay * tree.leaf_value[:nl]
                            + (1.0 - decay) * out * tree.shrinkage)
                tree.leaf_value[:nl] = new_vals
                score[k] += new_vals[lp]
        pad = np.zeros((K, self.train_score.shape[1] - self.num_data),
                       dtype=np.float32)
        self.train_score = self._place_rows(
            np.concatenate([score.astype(np.float32), pad], axis=1))
        self._drop_rollback_caches()

    def _drop_rollback_caches(self) -> None:
        """Invalidate per-iteration device caches after model surgery
        (refit/merge): a later rollback must not subtract stale outputs."""
        self._last_iter_arrays = []
        self._window = {}
        self._nl_handles = []
        self._fin_handles = []

    def merge_from(self, other: "GBDT") -> None:
        """Append another booster's trees (c_api.cpp Booster::MergeFrom).

        Trees are deep-copied (the reference copies serialized models), so
        later leaf surgery on one booster cannot leak into the other."""
        if other.num_tree_per_iteration != self.num_tree_per_iteration:
            raise ValueError("cannot merge boosters with different "
                             "num_tree_per_iteration")
        import copy
        self.models.extend(copy.deepcopy(t) for t in other.models)
        self.iter_ += other.iter_
        self._drop_rollback_caches()

    def shuffle_models(self, start_iter: int = 0, end_iter: int = -1) -> None:
        """Shuffle tree order in [start_iter, end_iter) iterations
        (gbdt.h ShuffleModels; used when merging boosters)."""
        self._invalidate_predict_cache()
        models = self.models
        K = self.num_tree_per_iteration
        total_iter = len(models) // K
        start_iter = max(0, start_iter)
        # reference contract: end_iter <= 0 means the last iteration
        end = total_iter if end_iter <= 0 else min(end_iter, total_iter)
        if end - start_iter <= 1:
            return
        rng = np.random.RandomState(42)
        order = start_iter + rng.permutation(end - start_iter)
        chunk = [models[i * K:(i + 1) * K] for i in range(total_iter)]
        shuffled = (chunk[:start_iter]
                    + [chunk[i] for i in order] + chunk[end:])
        self._models = [t for c in shuffled for t in c]
        self._drop_rollback_caches()

    def set_leaf_value(self, tree_idx: int, leaf_idx: int, value: float) -> None:
        """Directly set one leaf's output (c_api.cpp LGBM_BoosterSetLeafValue)."""
        self._invalidate_predict_cache()
        tree = self.models[tree_idx]
        if not 0 <= leaf_idx < tree.num_leaves:
            raise IndexError("leaf index %d out of range" % leaf_idx)
        tree.leaf_value[leaf_idx] = value

    # ---- training driver with internal early stopping (CLI path) ----

    def train(self, snapshot_out: Optional[str] = None) -> None:
        t_start = time.perf_counter()
        it_start = self.iter_  # nonzero on a checkpoint resume
        total = int(self.config.num_iterations)
        has_eval = bool(self.train_metrics) or bool(self.valid_sets)
        mf = int(self.config.metric_freq)
        sf = int(self.config.snapshot_freq)
        # fused chunks run to the next eval/snapshot boundary in one program
        npad = self.num_data + getattr(self.learner, "padded_rows", 0)
        chunk_cap = int(max(1, min(64, (1 << 31) // max(4 * npad, 1))))
        while self.iter_ < total:
            it = self.iter_
            nxt = total
            if has_eval and mf > 0:
                nxt = min(nxt, it + mf - (it % mf))
            if sf > 0:
                # chunk alignment keyed to the CONFIG, not to whether a
                # snapshot path was passed: fused scans of different lengths
                # compile to bitwise-different programs (XLA unroll/fusion
                # choices), so a resumed run must partition iterations into
                # the same chunks as the uninterrupted one to stay bit-exact
                nxt = min(nxt, it + sf - (it % sf))
            finished = self.train_chunk(min(nxt - it, chunk_cap))
            # per-chunk non-finite guard: raise fails fast, skip_iter/clip
            # roll back to the pre-chunk refs and retry per-iteration
            if self._guard_chunk_scores():
                break
            if self.iter_ == it and not finished:
                continue  # chunk was rolled back; re-run it per-iteration
            Log.info("%f seconds elapsed, finished iteration %d",
                     time.perf_counter() - t_start, self.iter_)
            if not finished and has_eval and mf > 0 \
                    and self.iter_ % mf == 0:
                finished = self.eval_and_check_early_stopping()
            if finished:
                break
            if _preemption_requested():
                # SIGTERM/SIGINT landed (possibly mid-chunk): the poll sits
                # at the chunk boundary — the in-flight fused program
                # completed whole (no mid-chunk tear) — and AFTER the
                # boundary eval, so the emergency checkpoint carries the
                # same early-stopping bookkeeping a periodic one would
                self._preempt_exit(snapshot_out)
            if (snapshot_out and sf > 0 and self.iter_ % sf == 0):
                # settle the stall poll BEFORE capturing so the checkpoint
                # never contains iterations a later poll would trim; a trim
                # here means training is over — snapshot the final state,
                # then stop
                finished = bool(self._nl_handles) and self._poll_stop()
                self._write_snapshot(snapshot_out)
                if finished:
                    break
        if self._nl_handles:
            self._poll_stop()  # trim any trailing stalled iterations
        elif self._fin_handles:
            self._drain_nonfinite_checks()
        tele = _telemetry_active()
        if tele is not None:
            # headline gauges report.summarize folds into row-trees/s; the
            # run owner (cli/engine/bench) calls report.finalize_run.
            # Iterations are the ones trained THIS call — a resumed run's
            # wall covers only this process, so counting the restored
            # iterations would inflate the throughput headline
            tele.gauge("train_rows").set(int(self.num_data))
            tele.gauge("train_iterations").set(int(self.iter_ - it_start))
            tele.gauge("train_wall_s").set(time.perf_counter() - t_start)

    def _preempt_exit(self, snapshot_out: Optional[str]) -> None:
        """Preemption flag set: drain in-flight device work (settle the
        stall poll, fetch pending isfinite reductions), write a
        leader-gated emergency checkpoint through the ordinary atomic
        path, and raise :class:`TrainingPreempted` so the driver exits
        with the distinct resumable code."""
        from ..resilience import (TrainingPreempted, clear_preemption,
                                  emergency_checkpoint)
        if self._nl_handles:
            self._poll_stop()
        if self._fin_handles:
            self._drain_nonfinite_checks()
        path = None
        if snapshot_out:
            path = emergency_checkpoint(self, snapshot_out)
        # the preemption is now fully handled — consume the flag so a later
        # train() in this process (the in-process resume) starts clean
        # instead of instantly re-preempting
        clear_preemption()
        raise TrainingPreempted(int(self.iter_), path)

    def _write_snapshot(self, snapshot_out: str) -> None:
        """Periodic durability point: the reference-compatible model snapshot
        (gbdt.cpp:291-295) plus a full train-state checkpoint, both written
        atomically, retained last-``snapshot_keep``, and only by the mesh
        leader (d hosts must not race the same rename).  Both writes are
        best-effort: transient faults retried inside ``atomic_write``, a
        fatal fault (disk full) skips THIS snapshot and keeps training —
        the previous checkpoint remains the resume point."""
        from ..checkpoint import save_checkpoint_best_effort, skip_io_failure
        from ..parallel.learners import is_write_leader
        if not is_write_leader(self.mesh):
            return
        snap = "%s.snapshot_iter_%d" % (snapshot_out, self.iter_)
        try:
            self.save_model(snap)
        except OSError as exc:
            skip_io_failure("model snapshot %s" % snap, exc)
        save_checkpoint_best_effort(self, snapshot_out)

    # ---- evaluation ----

    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        score = np.asarray(self.get_training_score()[:, :self.num_data])
        for m in self.train_metrics:
            for name, val in zip(m.names, m.eval(score, self.objective)):
                out.append(("training", name, val, m.factor_to_bigger_better > 0))
        return out

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        for vs in self.valid_sets:
            score = np.asarray(vs["score"])
            for m in vs["metrics"]:
                for name, val in zip(m.names, m.eval(score, self.objective)):
                    out.append((vs["name"], name, val,
                                m.factor_to_bigger_better > 0))
        return out

    def eval_and_check_early_stopping(self) -> bool:
        tele = _telemetry_active()
        for ds, name, val, _ in self.eval_train():
            Log.info("Iteration:%d, %s %s : %g", self.iter_, ds, name, val)
            if tele is not None:
                tele.event("eval", iteration=int(self.iter_), dataset=ds,
                           metric=name, value=float(val))
        stop = False
        rounds = int(self.config.early_stopping_round)
        for ds, name, val, bigger_better in self.eval_valid():
            Log.info("Iteration:%d, valid_1 %s : %g", self.iter_, name, val)
            if tele is not None:
                tele.event("eval", iteration=int(self.iter_), dataset=ds,
                           metric=name, value=float(val))
            if rounds > 0:
                key = (ds, name)
                cur = val if bigger_better else -val
                best = self._es_state.get(key)
                if best is None or cur > best[0]:
                    self._es_state[key] = (cur, self.iter_)
                elif self.iter_ - best[1] >= rounds:
                    Log.info("Early stopping at iteration %d, the best iteration "
                             "round is %d", self.iter_, best[1])
                    stop = True
        return stop

    # ---- prediction (core/predict.py device scan; host fallback) ----

    # below this row count the host loop wins (device compile isn't amortized)
    _DEVICE_PREDICT_MIN_ROWS = 512

    def _predict_early_stop(self) -> Tuple[float, int]:
        """(margin, freq); margin < 0 disables
        (prediction_early_stop.cpp:26-65, config.h pred_early_stop*)."""
        # gated on !NeedAccuratePrediction like the reference predictor
        # (predictor.hpp:38-47)
        if bool(self.config.pred_early_stop) \
                and self.num_tree_per_iteration == 1 \
                and self.objective is not None \
                and not self.objective.need_accurate_prediction:
            return (float(self.config.pred_early_stop_margin),
                    int(self.config.pred_early_stop_freq))
        return -1.0, 10

    def _use_device_predict(self, models: List[Tree], n: int) -> bool:
        # categorical models ride the device path too since the fused
        # predictor's bitset decide (core/predict.py decide_raw)
        return n >= self._DEVICE_PREDICT_MIN_ROWS and len(models) > 0

    def _fused_predictor(self, sel: List[Tree], start: int, end: int,
                         class_id: int, kind: str = "raw", layout_ds=None,
                         precision: str = "exact"):
        """EnsembleArrays-keyed predictor cache: the stacked blocked device
        ensemble for one (model range, class, generation, kind, precision)
        is built once and reused by every subsequent predict/eval/refit
        call.  The bf16 tier is its own cache entry — tiers never share a
        stacked ensemble or a compiled program."""
        from ..core.predict_fused import FusedPredictor
        if kind == "binned" and layout_ds is None:
            layout_ds = self.train_data
        key = (kind, start, end, class_id, len(self._models),
               getattr(self, "_model_gen", 0),
               id(layout_ds) if kind == "binned" else 0, precision)
        cache = getattr(self, "_fused_pred", None)
        if cache is None:
            cache = self._fused_pred = {}
        pred = cache.get(key)
        if pred is None:
            if len(cache) >= 8:
                # predict-during-training churns the model range every
                # iteration; drop the oldest stacked ensembles instead of
                # holding every generation's device arrays alive
                cache.pop(next(iter(cache)))
            pred = FusedPredictor(sel, dataset=layout_ds, kind=kind,
                                  precision=precision)
            cache[key] = pred
        return pred

    def _sharded_predict_eligible(self) -> bool:
        return (self.mesh is not None
                and int(np.prod(self.mesh.devices.shape)) > 1)

    def _raw_predict(self, X: np.ndarray, num_iteration: int = -1,
                     start_iteration: int = 0,
                     precision: str = "exact") -> np.ndarray:
        n = len(X)
        K = self.num_tree_per_iteration
        out = np.zeros((K, n), dtype=np.float64)
        total_iter = len(self.models) // K
        end_iter = total_iter if num_iteration <= 0 else min(
            total_iter, start_iteration + num_iteration)
        sel = self.models[start_iteration * K:end_iter * K]
        margin, freq = self._predict_early_stop()
        # a bf16 request always rides the fused device path: the host
        # small-batch predictors are exact-only, and silently upgrading a
        # lossy request to exact would hide the tier the caller asked for
        if self._use_device_predict(sel, n) \
                or (precision != "exact" and len(sel) > 0 and n > 0):
            sharded = self._sharded_predict_eligible()
            for k in range(K):
                pred = self._fused_predictor(sel[k::K], start_iteration,
                                             end_iter, k,
                                             precision=precision)
                if sharded:
                    from ..parallel.learners import sharded_predict
                    out[k] = sharded_predict(
                        pred.ens, np.asarray(X, dtype=np.float32),
                        self.mesh, early_stop_margin=margin,
                        round_period=freq)
                else:
                    out[k] = pred(X, early_stop_margin=margin,
                                  round_period=freq)
            return out
        if margin < 0 and len(sel) > 0:
            # cached flat-array ensemble: the reference's SingleRowPredictor
            # role (c_api.cpp:52-98) for small batches
            from ..core.predict import (StackedTreesPredictor,
                                        has_categorical_splits)
            if not has_categorical_splits(sel):
                key = (start_iteration, end_iter, len(self.models),
                       getattr(self, "_model_gen", 0))
                cached = getattr(self, "_stacked_pred", None)
                if cached is None or cached[0] != key:
                    cached = (key, [StackedTreesPredictor(sel[k::K])
                                    for k in range(K)])
                    self._stacked_pred = cached
                for k in range(K):
                    out[k] = cached[1][k].raw_predict(X)
                return out
        active = np.ones(n, dtype=bool)
        for j, tree in enumerate(sel):
            pred = tree.predict(X[active]) if margin >= 0 else tree.predict(X)
            if margin >= 0:
                out[j % K, active] += pred
                if (j + 1) % freq == 0:
                    active &= 2.0 * np.abs(out[j % K]) < margin
                    if not active.any():
                        break
            else:
                out[j % K] += pred
        return out

    def predict(self, X: np.ndarray, raw_score: bool = False,
                num_iteration: int = -1, start_iteration: int = 0,
                precision: str = "exact") -> np.ndarray:
        if precision not in ("exact", "bf16"):
            raise ValueError("precision must be 'exact' or 'bf16'")
        raw = self._raw_predict(X, num_iteration, start_iteration,
                                precision=precision)
        if self.average_output:
            total_iter = max(len(self.models) // self.num_tree_per_iteration, 1)
            raw = raw / total_iter
        if not raw_score and self.objective is not None:
            raw = np.asarray(self.objective.convert_output(raw))
        return raw[0] if self.num_tree_per_iteration == 1 else raw.T

    # below this row count the host TreeSHAP recursion wins (the device
    # contrib program's compile is not amortized by a one-off tiny batch)
    _DEVICE_CONTRIB_MIN_ROWS = 8

    def predict_contrib(self, X: np.ndarray, num_iteration: int = -1,
                        start_iteration: int = 0) -> np.ndarray:
        """SHAP feature contributions (tree.h:133 PredictContrib), [N,
        num_features+1] (last column = expected value; K classes
        concatenate along axis 1).

        Batches route through the device path-decomposition kernel
        (core/predict_contrib.py) on f32-cast features — the same cast
        every serving path applies — with the host per-tree TreeSHAP scan
        as the degraded fallback (counted via ``resilience.note_fallback``
        site ``predict_contrib``, like the round-11 predictor fallback)
        and for small one-off batches."""
        K = self.num_tree_per_iteration
        total_iter = len(self.models) // K
        end = total_iter if num_iteration <= 0 else min(
            total_iter, start_iteration + num_iteration)
        sel = self.models[start_iteration * K:end * K]
        n = len(X)
        ncol = self.max_feature_idx + 2
        out = np.zeros((K, n, ncol), dtype=np.float64)
        if sel and n >= self._DEVICE_CONTRIB_MIN_ROWS:
            try:
                Xf = np.ascontiguousarray(np.asarray(X, dtype=np.float32))
                sharded = self._sharded_predict_eligible()
                for k in range(K):
                    pred = self._fused_predictor(sel[k::K], start_iteration,
                                                 end, k)
                    if sharded:
                        from ..parallel.learners import \
                            sharded_predict_contrib
                        out[k] = sharded_predict_contrib(
                            pred.contrib_blocks(ncol), Xf, ncol,
                            self.mesh)
                    else:
                        out[k] = pred.predict_contrib(Xf, ncol)
                return out[0] if K == 1 else np.concatenate(out, axis=1)
            except _PROGRAM_ERRORS:
                raise
            except Exception as exc:  # degraded: the host scan serves
                from ..resilience import note_fallback
                note_fallback("predict_contrib",
                              reason="%s: %s" % (type(exc).__name__, exc),
                              rows=int(n))
                tele = _telemetry_active()
                if tele is not None:
                    # keep the live contrib_fallbacks tally consistent
                    # with the event-stream recovery (obs_report counts
                    # contrib-site predict_fallback breadcrumbs)
                    tele.counter("contrib_fallbacks").inc()
                Log.warning("device pred_contrib failed (%s: %s); serving "
                            "DEGRADED via the host TreeSHAP scan",
                            type(exc).__name__, exc)
                out[:] = 0.0
        # host scan: f32-cast rows so routing matches the device path
        Xh = np.asarray(X, dtype=np.float32)
        for i, tree in enumerate(sel):
            out[i % K] += tree.predict_contrib(Xh, ncol)
        return out[0] if K == 1 else np.concatenate(out, axis=1)

    def predict_contrib_binned(self, dataset: Optional[BinnedDataset] = None,
                               num_iteration: int = -1,
                               start_iteration: int = 0) -> np.ndarray:
        """SHAP contributions straight from a binned dataset's u8/u16 row
        store — integer threshold compares with the exact ``_route_left``
        semantics (EFB unfold, categorical bin-bitsets, missing routing),
        pinned bitwise identical to the raw-path kernel on training
        data."""
        ds = dataset if dataset is not None else self.train_data
        if ds is None or ds.binned is None:
            raise ValueError("binned prediction needs a BinnedDataset with "
                             "its row store attached")
        K = self.num_tree_per_iteration
        total_iter = len(self.models) // K
        end = total_iter if num_iteration <= 0 else min(
            total_iter, start_iteration + num_iteration)
        sel = self.models[start_iteration * K:end * K]
        ncol = self.max_feature_idx + 2
        out = np.zeros((K, ds.num_data, ncol), dtype=np.float64)
        layout = self.train_data if self.train_data is not None else ds
        for k in range(K):
            pred = self._fused_predictor(sel[k::K], start_iteration, end,
                                         k, kind="binned", layout_ds=layout)
            out[k] = pred.predict_contrib(ds.binned, ncol)
        return out[0] if K == 1 else np.concatenate(out, axis=1)

    def predict_leaf_index(self, X: np.ndarray,
                           num_iteration: int = -1) -> np.ndarray:
        K = self.num_tree_per_iteration
        total_iter = len(self.models) // K
        end = total_iter if num_iteration <= 0 else min(total_iter, num_iteration)
        sel = self.models[:end * K]
        if self._use_device_predict(sel, len(X)):
            out = np.zeros((len(X), len(sel)), dtype=np.int32)
            for k in range(K):
                pred = self._fused_predictor(sel[k::K], 0, end, k)
                out[:, k::K] = pred(np.asarray(X, dtype=np.float32),
                                    want_leaf=True)
            return out
        cols = [self.models[i].predict_leaf_index(X) for i in range(end * K)]
        return np.stack(cols, axis=1) if cols else np.zeros((len(X), 0), np.int32)

    # ---- quality plane (obs/quality.py) ----

    def quality_baseline(self, layout_ds=None):
        """Drift baseline of THIS model against ``layout_ds`` (default: the
        training data): per-feature training bin occupancy + importance +
        score fingerprints.  Cached per (layout, model generation) — a
        refit or swap rebuilds, steady serving reuses.  None when no
        layout dataset is at hand (a model loaded without its dataset
        serves fine but cannot be drift-scored)."""
        from ..obs.quality import QualityBaseline, capture_fingerprints
        ds = layout_ds if layout_ds is not None else self.train_data
        if ds is None:
            return None
        # the cache HOLDS the layout dataset: an id()-only key could be
        # recycled by a new dataset allocated at a freed one's address
        key = (len(self._models), getattr(self, "_model_gen", 0))
        cached = self._quality_baseline_cache
        if cached is not None and cached[0] is ds and cached[1] == key:
            return cached[2]
        if (self._score_fingerprint_raw is None
                and getattr(self, "train_score", None) is not None):
            # captured HERE, on the first baseline build, not at train
            # end: a telemetry-off training run must not pay the O(n)
            # score-quantile pass for a fingerprint nothing will read
            capture_fingerprints(self)
        base = QualityBaseline.from_model(self, ds)
        self._quality_baseline_cache = (ds, key, base)
        return base

    # ---- binned fast path (core/predict_fused.py): training-format u8 rows ----

    def raw_predict_binned(self, dataset: Optional[BinnedDataset] = None,
                           num_iteration: int = -1, start_iteration: int = 0,
                           use_early_stop: bool = True) -> np.ndarray:
        """[K, N] raw scores straight from a binned dataset's u8/u16 row
        store: integer compares against host-prebinned thresholds — no f32
        gather/NaN pipeline, 1 byte read per (row, node) instead of 4.

        ``dataset`` defaults to the training data; any dataset sharing the
        training bin mappers / EFB layout (reference-aligned valid sets,
        subsets) routes bit-identically to the raw-value path."""
        ds = dataset if dataset is not None else self.train_data
        if ds is None or ds.binned is None:
            raise ValueError("binned prediction needs a BinnedDataset with "
                             "its row store attached")
        K = self.num_tree_per_iteration
        out = np.zeros((K, ds.num_data), dtype=np.float64)
        total_iter = len(self.models) // K
        end_iter = total_iter if num_iteration <= 0 else min(
            total_iter, start_iteration + num_iteration)
        sel = self.models[start_iteration * K:end_iter * K]
        if not sel:
            return out
        margin, freq = ((-1.0, 10) if not use_early_stop
                        else self._predict_early_stop())
        layout = self.train_data if self.train_data is not None else ds
        for k in range(K):
            pred = self._fused_predictor(sel[k::K], start_iteration, end_iter,
                                         k, kind="binned", layout_ds=layout)
            out[k] = pred(ds.binned, early_stop_margin=margin,
                          round_period=freq)
        # quality plane: fold this EXTERNAL dataset's bin ids into the
        # drift counters (training-data replays — dataset None / the train
        # set itself — are by definition drift-free and stay out).  Gated
        # on an active telemetry run first: a telemetry-off process makes
        # zero quality-plane calls (spy-pinned).
        tele = _telemetry_active()
        if tele is not None and dataset is not None \
                and ds is not self.train_data \
                and bool(getattr(self.config, "quality_monitor", True)):
            # quality_monitor=false is a full off-switch for THIS booster:
            # it must neither create a monitor nor feed one another
            # component created (same guard shape as the scheduler's)
            from ..obs import quality as _quality
            mon = _quality.monitor(
                tele, create=True,
                top_k=int(getattr(self.config, "quality_top_k", 20)))
            mon.observe(tele, getattr(self, "quality_name", "model"),
                        self, layout, 1, ds.binned, "binned",
                        scores=out[0] if K == 1 else None,
                        raw_score=True)
        return out

    def predict_binned(self, dataset: Optional[BinnedDataset] = None,
                       raw_score: bool = False, num_iteration: int = -1,
                       start_iteration: int = 0) -> np.ndarray:
        """Like :meth:`predict` but over a binned dataset's row store."""
        raw = self.raw_predict_binned(dataset, num_iteration, start_iteration)
        if self.average_output:
            total_iter = max(len(self.models) // self.num_tree_per_iteration, 1)
            raw = raw / total_iter
        if not raw_score and self.objective is not None:
            raw = np.asarray(self.objective.convert_output(raw))
        return raw[0] if self.num_tree_per_iteration == 1 else raw.T

    def predict_leaf_index_binned(self, dataset: Optional[BinnedDataset] = None,
                                  num_iteration: int = -1) -> np.ndarray:
        """[N, num_models] leaf indices from the binned row store (the refit
        router: gbdt.cpp:299 RefitTree without materializing raw values)."""
        ds = dataset if dataset is not None else self.train_data
        if ds is None or ds.binned is None:
            raise ValueError("binned prediction needs a BinnedDataset with "
                             "its row store attached")
        K = self.num_tree_per_iteration
        total_iter = len(self.models) // K
        end = total_iter if num_iteration <= 0 else min(total_iter,
                                                        num_iteration)
        sel = self.models[:end * K]
        out = np.zeros((ds.num_data, len(sel)), dtype=np.int32)
        layout = self.train_data if self.train_data is not None else ds
        for k in range(K):
            pred = self._fused_predictor(sel[k::K], 0, end, k, kind="binned",
                                         layout_ds=layout)
            out[:, k::K] = pred(ds.binned, want_leaf=True)
        return out

    def replay_train_score(self) -> None:
        """train_score += model(train rows) for ALL trees in ONE blocked
        binned pass per class — the loaded-model replay (cli task=train
        with input_model, engine.train init_model) without T per-tree
        ``route_binned`` dispatches.  Bit-identical to the per-tree loop
        when the score base is zero; a nonzero init_score base joins the
        f32 sum last instead of first (ULP-level association difference)."""
        models = self.models
        K = self.num_tree_per_iteration
        if not models or self.train_data is None:
            return
        n = self.num_data
        scores = self.raw_predict_binned(use_early_stop=False)
        for k in range(K):
            self.train_score = self.train_score.at[k, :n].add(
                jnp.asarray(scores[k], dtype=jnp.float32))

    # ---- feature importance (c_api.cpp:1573 semantics) ----

    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = -1) -> np.ndarray:
        K = self.num_tree_per_iteration
        total_iter = len(self.models) // K
        end = total_iter if num_iteration <= 0 else min(total_iter, num_iteration)
        out = np.zeros(self.max_feature_idx + 1, dtype=np.float64)
        for i in range(end * K):
            t = self.models[i]
            if importance_type == "split":
                for f in t.splits_by_feature():
                    out[f] += 1
            else:
                feats, gains = t.gains_by_feature()
                for f, g in zip(feats, gains):
                    out[f] += g
        return out

    # ---- model serialization (gbdt_model_text.cpp:271,375) ----

    def sub_model_name(self) -> str:
        return "tree"

    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1) -> str:
        lines = [self.sub_model_name(), "version=%s" % MODEL_VERSION,
                 "num_class=%d" % self.num_class,
                 "num_tree_per_iteration=%d" % self.num_tree_per_iteration,
                 "label_index=%d" % self.label_idx,
                 "max_feature_idx=%d" % self.max_feature_idx]
        if self.objective is not None:
            lines.append("objective=%s" % self.objective.to_string())
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names))
        lines.append("feature_infos=" + " ".join(self.feature_infos))

        K = self.num_tree_per_iteration
        total_iter = len(self.models) // K
        start_iteration = min(max(start_iteration, 0), total_iter)
        num_used = total_iter * K
        if num_iteration > 0:
            num_used = min((start_iteration + num_iteration) * K, num_used)
        start_model = start_iteration * K
        tree_strs = []
        for i in range(start_model, num_used):
            tree_strs.append("Tree=%d\n" % (i - start_model)
                             + self.models[i].to_string() + "\n")
        lines.append("tree_sizes=" + " ".join(str(len(s)) for s in tree_strs))
        lines.append("")
        body = "\n".join(lines) + "\n" + "".join(tree_strs) + "end of trees\n"

        imps = self.feature_importance("split", num_iteration)
        pairs = sorted([(int(v), self.feature_names[i])
                        for i, v in enumerate(imps) if v > 0],
                       key=lambda p: -p[0])
        body += "\nfeature importances:\n"
        body += "".join("%s=%d\n" % (nm, v) for v, nm in pairs)
        body += "\nparameters:\n"
        for k, v in sorted(self.config.raw_params.items()):
            body += "[%s: %s]\n" % (k, v)
        body += "end of parameters\n"
        return body

    def save_model(self, filename: str, start_iteration: int = 0,
                   num_iteration: int = -1) -> None:
        # atomic (tmp + fsync + rename): a kill mid-write leaves the previous
        # complete model file, never a truncated one
        atomic_write(filename,
                     self.save_model_to_string(start_iteration, num_iteration))
        Log.info("Finished writing model to file %s", filename)

    def load_model_from_string(self, text: str) -> None:
        """Parse the text model format; malformed/truncated input raises a
        ``LightGBMError`` naming the failing section instead of a cryptic
        IndexError deep in the tree parser."""
        if not text or not text.strip():
            raise LightGBMError("Model file is empty")
        split_at = text.find("\nTree=")
        header = text[:split_at] if split_at >= 0 else text
        rest = text[split_at + 1:] if split_at >= 0 else ""
        kv: Dict[str, str] = {}
        for line in header.splitlines():
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k.strip()] = v.strip()
        if split_at >= 0 and "end of trees" not in rest:
            raise LightGBMError(
                "Model format error: missing 'end of trees' sentinel — the "
                "tree section is truncated")
        try:
            self.num_class = int(kv.get("num_class", 1))
            self.num_tree_per_iteration = int(
                kv.get("num_tree_per_iteration", 1))
            self.label_idx = int(kv.get("label_index", 0))
            self.max_feature_idx = int(kv.get("max_feature_idx", 0))
        except ValueError as exc:
            raise LightGBMError("Model format error: unparseable header "
                                "field (%s)" % exc)
        self.feature_names = kv.get("feature_names", "").split()
        self.feature_infos = kv.get("feature_infos", "").split()
        self.average_output = "average_output" in header.splitlines()
        if "objective" in kv and self.objective is None:
            obj_str = kv["objective"].split()
            cfg = self.config
            if self.num_class > 1:
                cfg.num_class = self.num_class
            self.objective = create_objective(obj_str[0], cfg)
        self.models = []
        if rest:
            trees_text = rest.split("end of trees")[0]
            for block in trees_text.split("Tree="):
                block = block.strip()
                if not block:
                    continue
                block = block.split("\n", 1)[1] if "\n" in block else ""
                if block.strip():
                    try:
                        self.models.append(Tree.from_string(block))
                    except (LightGBMError, ValueError, IndexError,
                            KeyError) as exc:
                        raise LightGBMError(
                            "Model format error: Tree=%d is malformed (%s)"
                            % (len(self.models), exc))
        # outside the `if rest` guard: a file truncated BEFORE the first
        # Tree= block still declares its trees in the header and must not
        # load as a silent 0-tree model
        declared = kv.get("tree_sizes", "").split()
        if declared and len(declared) != len(self.models):
            raise LightGBMError(
                "Model format error: tree_sizes declares %d trees but "
                "%d were parsed — the tree section is truncated"
                % (len(declared), len(self.models)))
        K = max(self.num_tree_per_iteration, 1)
        if len(self.models) % K != 0:
            raise LightGBMError(
                "Model format error: %d trees is not a multiple of "
                "num_tree_per_iteration=%d — the tree section is truncated"
                % (len(self.models), K))
        self.num_init_iteration = len(self.models) // K
        self.iter_ = 0

    @classmethod
    def load_model(cls, filename: str, config: Optional[Config] = None) -> "GBDT":
        with open(filename) as fh:
            text = fh.read()
        config = config or Config()
        first = text.splitlines()[0].strip() if text else ""
        booster = {"tree": cls}.get(first, cls)(config)
        booster.load_model_from_string(text)
        return booster

    @property
    def num_trees(self) -> int:
        return len(self._models)

    @property
    def current_iteration(self) -> int:
        return len(self._models) // max(self.num_tree_per_iteration, 1)
