"""Parallel tree learners over a ``jax.sharding.Mesh``.

Counterparts of the reference learners created by ``CreateTreeLearner``
(src/treelearner/tree_learner.cpp:13-36):

- ``DataParallelTreeLearner`` — rows sharded across chips; per-split global
  histograms by ``psum_scatter`` over the feature axis + allreduce-argmax of
  per-shard best splits (data_parallel_tree_learner.cpp:149-240).
- ``FeatureParallelTreeLearner`` — data replicated; histogram CONSTRUCTION
  and best-split scan sharded over features (each shard builds only its own
  F/d block, feature_parallel_tree_learner.cpp:33-52); only the best-split
  argmax crosses chips.  The row store keeps every routable column on every
  chip (rows are replicated), unlike the reference's vertical column shards.
- ``VotingParallelTreeLearner`` — rows sharded; top-k feature election keeps
  per-split comm at O(2*top_k*bins) (voting_parallel_tree_learner.cpp:170-366).

Unlike the reference — where distribution lives in a process-global ``Network``
singleton called from inside the learner — the whole tree build (histograms,
collectives, split search, partition) is ONE compiled XLA program under
``jax.shard_map``; XLA schedules the collectives on ICI/DCN.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.partition import CHUNK as _PCHUNK
from ..core.split import FeatureInfo
from ..core.tree_learner import (Comm, SerialTreeLearner, TreeArrays,
                                 build_tree_partitioned)
from ..obs import comm as _comm
from ..obs import launches as _launches
from ..obs import recompile as _recompile
from ..obs.spans import span as _span


def _shard_map(fn, *, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def arg_specs(args):
    """The shapes of a dispatch's arguments, each with the sharding it was
    committed to (an uncommitted array goes wherever the program runs): what
    ``.lower`` needs to produce the program that dispatch ran, without
    keeping the arrays alive."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None),
        args)


def default_mesh(num_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    """1-D mesh over the first ``num_devices`` local devices (all by default)."""
    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return Mesh(np.array(devices), (axis,))


def is_write_leader(mesh: Optional[Mesh] = None) -> bool:
    """True when this host should perform model/checkpoint file writes.

    An in-process mesh (8 local devices) has a single controller — always
    the leader.  On a multi-process pod every process runs the same
    training loop over a shared filesystem, so only process 0 writes:
    d racing writers would interleave tmp-file renames and retention
    deletes on the SAME paths (checkpoint.py prune).  ``mesh`` is accepted
    for future per-mesh leadership; today leadership is process-global."""
    del mesh  # single-controller meshes: leadership is process-global
    return jax.process_index() == 0


# ---- sharded batch prediction (core/predict_fused.py over the mesh) ----

_SHARDED_PREDICT_FNS: dict = {}


def sharded_predict_fn(mesh: Mesh, early_stop_margin: float = -1.0,
                       round_period: int = 10):
    """Compiled sharded batch-predict: rows split over the mesh, the blocked
    ensemble replicated, each shard running the tree-blocked scan on its
    n/d rows.  The ONLY cross-device op is the final tiled ``all_gather``
    of the per-shard scores — pinned on the lowered HLO by
    tests/test_predict_fused.py.  Cached per (mesh, early-stop config);
    jit caches per (ensemble, row-bucket) shape under that."""
    key = (mesh, float(early_stop_margin), int(round_period))
    fn = _SHARDED_PREDICT_FNS.get(key)
    if fn is None:
        from ..core.predict_fused import scan_blocks
        axis = mesh.axis_names[0]

        def body(ens, rows):
            score = scan_blocks(ens, rows,
                                early_stop_margin=float(early_stop_margin),
                                round_period=int(round_period))
            return jax.lax.all_gather(score, axis, tiled=True)

        fn = jax.jit(_shard_map(body, mesh=mesh,
                                in_specs=(P(), P(axis, None)),
                                out_specs=P()))
        _SHARDED_PREDICT_FNS[key] = fn
    return fn


def sharded_predict(ens, rows: np.ndarray, mesh: Optional[Mesh] = None, *,
                    early_stop_margin: float = -1.0,
                    round_period: int = 10) -> np.ndarray:
    """[N] f64 raw scores for ``rows`` sharded over ``mesh``.

    ``ens`` is a blocked (raw or binned) ensemble from core/predict_fused;
    ``rows`` is the matching [N, F] f32 / [N, num_groups] u8 matrix.  Rows
    pad so each shard holds a fixed bucket from the serving ladder
    (``shape_bucket``); batches beyond the top bucket stream through it in
    fixed-shape chunks (rows are independent), keeping the no-recompile
    contract per shard at ANY batch size."""
    import time as _time

    from ..core.predict_fused import PREDICT_BUCKETS, shape_bucket
    from ..obs import active as _telemetry_active
    from ..obs.spans import span as _span
    from ..obs import recompile as _recompile
    from ..resilience import PROGRAM_ERRORS as _PROGRAM_ERRORS
    from ..resilience import note_fallback as _note_fallback
    from ..resilience import watch as _watch
    mesh = mesh if mesh is not None else default_mesh()
    d = int(np.prod(mesh.devices.shape))
    rows = np.asarray(rows)
    if rows.dtype.kind == "f":
        rows = rows.astype(np.float32, copy=False)
    n = rows.shape[0]
    fn = sharded_predict_fn(mesh, early_stop_margin, round_period)
    top = PREDICT_BUCKETS[-1] * d
    scores = np.empty(n, dtype=np.float64)
    tele = _telemetry_active()
    for lo in range(0, max(n, 1), top):
        chunk = rows[lo:lo + top]
        nc = len(chunk)
        bucket = shape_bucket(-(-nc // d))
        n_pad = bucket * d
        if n_pad > nc:
            chunk = np.concatenate(
                [chunk, np.zeros((n_pad - nc,) + chunk.shape[1:],
                                 dtype=chunk.dtype)])
        t0 = _time.perf_counter()
        fell_back = False
        try:
            with _span("sharded_predict"), \
                    _watch("sharded_predict", compile_key=int(bucket),
                           rows=int(nc), bucket=int(bucket), shards=int(d)):
                out = fn(ens, jnp.asarray(chunk))
        except _PROGRAM_ERRORS:
            raise
        except Exception as exc:  # mesh unhealthy: serve single-device
            fell_back = True
            from ..core.predict_fused import predict_blocked
            from ..utils.log import Log
            Log.warning("sharded predict failed on the %d-device mesh "
                        "(%s: %s); serving DEGRADED on a single device",
                        d, type(exc).__name__, exc)
            _note_fallback("sharded_predict", reason="%s: %s"
                           % (type(exc).__name__, exc),
                           bucket=int(bucket), shards=int(d))
            # a FRESH watch section: the failed dispatch's clock must not
            # bleed into the recovery (the fallback may legitimately spend
            # a first-dispatch compile here), but a hang of the fallback
            # itself is still caught
            with _watch("sharded_predict_fallback", compile_key=int(bucket),
                        rows=int(nc), bucket=int(bucket)):
                out = predict_blocked(
                    ens, jnp.asarray(chunk),
                    early_stop_margin=float(early_stop_margin),
                    round_period=int(round_period))
        misses = 0
        if not fell_back:
            # one jitted fn per (mesh, early-stop config), each with its OWN
            # jit cache growing from zero: watch them separately (by callable
            # identity — fns are cached for the process lifetime) so a second
            # mesh's compiles aren't swallowed by the first's larger baseline
            misses = _recompile.note_dispatch(
                "sharded_predict(m=%g,p=%d)" % (early_stop_margin,
                                                round_period),
                bucket, fn._cache_size(), watch="sharded_predict/%d" % id(fn))
        if tele is not None:
            dt = _time.perf_counter() - t0
            tele.event("sharded_predict", rows=int(nc), bucket=int(bucket),
                       shards=int(d), dt_s=dt, fallback=bool(fell_back))
            if not fell_back:
                # compile accounting (obs/compile.py): the sharded path's
                # compiles are priced like the single-device ones.  The key
                # carries the early-stop config AND the shard count — two
                # meshes (or two configs) have different steady walls, and
                # pricing one config's compile against the other's steady
                # median would corrupt the autotuner substrate
                from ..obs import compile as _compile
                _compile.note_dispatch(
                    tele, "sharded_predict(m=%g,p=%d,d=%d)"
                    % (early_stop_margin, round_period, d),
                    bucket, dt, misses)
        scores[lo:lo + nc] = np.asarray(out[:nc], dtype=np.float64)
    return scores


# ---- sharded SHAP contributions (core/predict_contrib.py over the mesh) --

_SHARDED_CONTRIB_FNS: dict = {}


def sharded_contrib_fn(mesh: Mesh):
    """Compiled sharded contrib: rows split over the mesh, the blocked
    contrib program inputs replicated, each shard running the TreeSHAP
    path-decomposition scan on its n/d rows; the only cross-device op is
    the final tiled ``all_gather`` of the per-shard [n/d, C] phi rows —
    the sharded_predict_fn discipline applied to explanations."""
    fn = _SHARDED_CONTRIB_FNS.get(mesh)
    if fn is None:
        from ..core.predict_contrib import contrib_scan
        axis = mesh.axis_names[0]

        def body(blocks, rows):
            phi = contrib_scan(blocks, rows)
            return jax.lax.all_gather(phi, axis, tiled=True)

        fn = jax.jit(_shard_map(body, mesh=mesh,
                                in_specs=(P(), P(axis, None)),
                                out_specs=P()))
        _SHARDED_CONTRIB_FNS[mesh] = fn
    return fn


def sharded_predict_contrib(blocks, rows: np.ndarray, ncol: int,
                            mesh: Optional[Mesh] = None) -> np.ndarray:
    """[N, ncol] f64 SHAP contributions for ``rows`` sharded over
    ``mesh``.  ``blocks`` is a blocked contrib input tuple from
    ``FusedPredictor.contrib_blocks`` / ``stack_contrib_blocked``; rows
    pad so each shard holds a fixed serving-ladder bucket, with the
    single-device blocked program as the degraded fallback (counted)."""
    import time as _time

    from ..core.predict_fused import PREDICT_BUCKETS, shape_bucket
    from ..obs import active as _telemetry_active
    from ..obs.spans import span as _span
    from ..obs import recompile as _recompile
    from ..resilience import PROGRAM_ERRORS as _PROGRAM_ERRORS
    from ..resilience import note_fallback as _note_fallback
    from ..resilience import watch as _watch
    mesh = mesh if mesh is not None else default_mesh()
    d = int(np.prod(mesh.devices.shape))
    rows = np.asarray(rows)
    if rows.dtype.kind == "f":
        rows = rows.astype(np.float32, copy=False)
    n = rows.shape[0]
    fn = sharded_contrib_fn(mesh)
    top = PREDICT_BUCKETS[-1] * d
    out = np.empty((n, int(ncol)), dtype=np.float64)
    tele = _telemetry_active()
    for lo in range(0, max(n, 1), top):
        chunk = rows[lo:lo + top]
        nc = len(chunk)
        bucket = shape_bucket(-(-nc // d))
        n_pad = bucket * d
        if n_pad > nc:
            chunk = np.concatenate(
                [chunk, np.zeros((n_pad - nc,) + chunk.shape[1:],
                                 dtype=chunk.dtype)])
        t0 = _time.perf_counter()
        fell_back = False
        try:
            with _span("sharded_contrib"), \
                    _watch("sharded_contrib", compile_key=int(bucket),
                           rows=int(nc), bucket=int(bucket),
                           shards=int(d)), \
                    jax.enable_x64(True):
                # materialize INSIDE the x64 scope (slicing f64 results
                # outside it re-canonicalizes avals to f32)
                res = np.asarray(fn(blocks, jnp.asarray(chunk)))
        except _PROGRAM_ERRORS:
            raise
        except Exception as exc:  # mesh unhealthy: serve single-device
            fell_back = True
            from ..core.predict_contrib import predict_contrib_blocked
            from ..utils.log import Log
            Log.warning("sharded pred_contrib failed on the %d-device mesh "
                        "(%s: %s); serving DEGRADED on a single device",
                        d, type(exc).__name__, exc)
            _note_fallback("sharded_contrib", reason="%s: %s"
                           % (type(exc).__name__, exc),
                           bucket=int(bucket), shards=int(d))
            with _watch("sharded_contrib_fallback", compile_key=int(bucket),
                        rows=int(nc), bucket=int(bucket)), \
                    jax.enable_x64(True):
                res = np.asarray(predict_contrib_blocked(
                    blocks, jnp.asarray(chunk)))
        if not fell_back:
            _recompile.note_dispatch(
                "sharded_contrib", bucket, fn._cache_size(),
                watch="sharded_contrib/%d" % id(fn))
        if tele is not None:
            dt = _time.perf_counter() - t0
            tele.counter("contrib_calls").inc()
            tele.counter("contrib_rows").inc(int(nc))
            if fell_back:
                tele.counter("contrib_fallbacks").inc()
            tele.histogram("contrib_latency_s_bucket_%d"
                           % bucket).observe(dt)
            tele.event("contrib", rows=int(nc), bucket=int(bucket),
                       shards=int(d), dt_s=dt, fallback=bool(fell_back))
        out[lo:lo + nc] = np.asarray(res[:nc], dtype=np.float64)
    return out


class _ParallelTreeLearner(SerialTreeLearner):
    """Shared host wrapper: padding to mesh-divisible shapes + shard_map build."""

    mode = "data_rs"
    supports_groups = False  # feature sharding wants one column per feature
    supports_packing = False

    def __init__(self, dataset, config, mesh: Optional[Mesh] = None) -> None:
        super().__init__(dataset, config)
        if (self.forced is not None or self.cegb is not None) \
                and self.mode != "data_part":
            from ..utils.log import Log
            Log.warning("forced splits / CEGB penalties need the full "
                        "histogram block; tree_learner=%s (feature-sharded "
                        "scan) ignores them — the psum data-parallel "
                        "learner applies them", self.mode)
            self.forced = None
            self.cegb = None
            self.cegb_used = None
        self.mesh = mesh if mesh is not None else default_mesh()
        self.num_shards = int(np.prod(self.mesh.devices.shape))
        if self.mode == "feature" and self.hist_pool_slots:
            # sharded histogram blocks are F/d wide, so the same
            # histogram_pool_size budget admits d times more slots than the
            # serial sizing computed before the mesh was known
            self.hist_pool_slots = max(2, self.hist_pool_slots
                                       * self.num_shards)
        self.axis = self.mesh.axis_names[0]
        self.comm = Comm(axis_name=self.axis, mode=self.comm_mode,
                         num_shards=self.num_shards, top_k=int(config.top_k))
        self._repad(dataset)
        self._build_fn = self._make_build_fn()
        self._build_specs = None
        self._compiled_build = None
        self._comm_per_build = None

    # ---- shape preparation ----

    def _upload_bins(self, binned: np.ndarray) -> None:
        # defer the (single, sharded) device upload to _repad
        self._host_bins = binned

    def _repad(self, dataset) -> None:
        d = self.num_shards
        if self.mode != "feature":
            row_mult = _PCHUNK * d if self.use_pallas else d
            self.padded_rows = (-self.num_data) % row_mult
        binned = self._pad_host_rows(self._host_bins)
        del self._host_bins

        self.feature_pad = 0
        if self.mode in ("data_rs", "feature"):
            self.feature_pad = (-binned.shape[1]) % d
            if self.feature_pad:
                binned = np.concatenate(
                    [binned, np.zeros((binned.shape[0], self.feature_pad),
                                      dtype=binned.dtype)], axis=1)
                pad_with = lambda a, v: jnp.concatenate(
                    [a, jnp.full((self.feature_pad,), v, dtype=a.dtype)])
                self.feat = FeatureInfo(
                    num_bin=pad_with(self.feat.num_bin, 1),
                    missing_type=pad_with(self.feat.missing_type, 0),
                    default_bin=pad_with(self.feat.default_bin, 0),
                    is_categorical=pad_with(self.feat.is_categorical, False),
                    monotone=pad_with(self.feat.monotone, 0))

        # where a [rows] array lives: the contiguous row blocks of self.bins;
        # None where rows are replicated (feature mode)
        self.row_sharding = (None if self.mode == "feature"
                             else NamedSharding(self.mesh, P(self.axis)))
        row_spec = P() if self.mode == "feature" else P(self.axis, None)
        # the one sharded transfer of the binned table, waited for so that
        # the span holds it (as ingest.upload does on one device)
        with _span("ingest.shard_upload"):
            self.bins = jax.device_put(binned,
                                       NamedSharding(self.mesh, row_spec))
            self.bins.block_until_ready()

    # ---- per-row state lives with its rows ----

    def shard_rows(self, arr, axis: int = -1, value=0.0) -> jax.Array:
        """``arr`` with its row axis padded to the learner's row count and
        placed in the row blocks of ``self.bins`` (a no-op for an array that
        is there already)."""
        axis %= arr.ndim
        spec = [None] * arr.ndim
        spec[axis] = self.axis
        sharding = NamedSharding(self.mesh, P(*spec))
        short = self.num_data + self.padded_rows - arr.shape[axis]
        if short > 0:
            widths = [(0, 0)] * arr.ndim
            widths[axis] = (0, short)
            xp = jnp if isinstance(arr, jax.Array) else np
            arr = xp.pad(arr, widths, constant_values=value)
        elif (isinstance(arr, jax.Array)
              and not isinstance(arr, jax.core.Tracer)
              and arr.sharding.is_equivalent_to(sharding, arr.ndim)):
            return arr
        return jax.device_put(arr, sharding)

    def pad_rows(self, arr: jax.Array, value=0.0) -> jax.Array:
        if self.row_sharding is None:
            return super().pad_rows(arr, value)
        return self.shard_rows(arr, axis=0, value=value)

    # ---- compiled build ----
    # Every parallel learner composes over the SAME partitioned base builder
    # (the reference composes its parallel learners over the serial one via
    # templates, tree_learner.cpp:24-33); only the comm_mode differs.

    comm_mode = "rs"

    def _make_build_fn(self):
        base = functools.partial(
            build_tree_partitioned, num_leaves=self.num_leaves,
            max_depth=self.max_depth, params=self.params,
            num_bins=self.num_bins, use_pallas=self.use_pallas,
            has_categorical=self.has_categorical,
            has_monotone=self.has_monotone,
            feat_num_bins=self.feat_bins, unpack_lanes=self.unpack_lanes,
            packed_cols=self.packed_cols, axis_name=self.axis,
            comm_mode=self.comm_mode, num_shards=self.num_shards,
            top_k=int(self.comm.top_k),
            hist_pool_slots=self.hist_pool_slots,
            pallas_interpret=self.pallas_interpret,
            hist_precision=self.hist_precision,
            quant_seed=self.quant_seed)

        # the boosting-iteration scalar rides the shard_map replicated: it
        # keys the quantized path's stochastic-rounding hash (every shard
        # hashes GLOBAL row ids against the same iteration)
        def fn(bins, grad, hess, nd, fm, feat, it):
            return base(bins, grad, hess, nd, fm, feat, quant_it=it)

        row = P() if self.mode == "feature" else P(self.axis)
        bins_spec = P() if self.mode == "feature" else P(self.axis, None)
        out_specs = TreeArrays(
            *([P()] * len(TreeArrays._fields)))._replace(row_leaf=row)
        shard_fn = _shard_map(
            fn, mesh=self.mesh,
            in_specs=(bins_spec, row, row, P(), P(), P(), P()),
            out_specs=out_specs)
        return jax.jit(shard_fn)

    def _prep_train(self, grad, hess, feature_mask):
        """Shared prologue: pad rows; feature mask padded to the sharded
        feature count (NOT the bins width — bins may be nibble-packed)."""
        nf_padded = int(self.feat.num_bin.shape[0])
        if feature_mask is None:
            fm = np.ones(nf_padded, dtype=bool)
            if self.feature_pad:
                fm[nf_padded - self.feature_pad:] = False
        else:
            fm = np.concatenate([np.asarray(feature_mask),
                                 np.zeros(self.feature_pad, dtype=bool)])
        return self.pad_rows(grad), self.pad_rows(hess), jnp.asarray(fm)

    def _dispatch_build(self, *args):
        """One tree build: its launches and collectives recorded at the
        dispatch, its argument shapes kept for :meth:`compiled_build`."""
        _launches.record(self.effective_grow_mode(), self.launches_per_tree())
        _comm.record(self.comm_per_build)
        if self._build_specs is None:
            self._build_specs = arg_specs(args)
        with _span("dp.build_tree"):
            out = self._build_fn(*args)
        _recompile.note_dispatch("dp_build_tree", self.mode,
                                 self._build_fn._cache_size(),
                                 watch="dp_build_tree/%d" % id(self._build_fn))
        return out

    def compiled_build(self):
        """The sharded build program as dispatched (``.as_text()``,
        ``.memory_analysis()``), or None before the first build.  Lowers
        again and asks the compiler, which the persistent cache answers."""
        if self._compiled_build is None and self._build_specs is not None:
            self._compiled_build = self._build_fn.lower(
                *self._build_specs).compile()
        return self._compiled_build

    def comm_per_build(self):
        """(collectives, bytes of their operands on one chip) that one tree
        build takes part in, read off the compiled build program
        (``obs.comm.per_run``: a collective of the builder's loop counts once
        a trip); None before the first build."""
        if self._comm_per_build is None and self._build_specs is not None:
            self._comm_per_build = _comm.per_run(
                self.compiled_build().as_text(),
                unknown_trips=self.num_leaves - 1)
        return self._comm_per_build

    def train(self, grad: jax.Array, hess: jax.Array, num_data_in_bag,
              feature_mask=None, iteration=0) -> TreeArrays:
        grad, hess, fm = self._prep_train(grad, hess, feature_mask)
        return self._dispatch_build(
            self.bins, grad, hess,
            jnp.asarray(num_data_in_bag, dtype=jnp.int32), fm, self.feat,
            jnp.asarray(iteration, jnp.int32))


class DataParallelTreeLearner(_ParallelTreeLearner):
    """tree_learner=data: rows sharded over the mesh, per-leaf partitions
    shard-local, and the reference's exact comm structure per split
    (data_parallel_tree_learner.cpp:149-240): the smaller child's histogram
    is ``psum_scatter``'d over the feature axis so each chip receives and
    scans only the global histograms of its own F/d features, then the
    winning split is an allreduce-argmax (SyncUpGlobalBestSplit).  Per-split
    ICI volume is F*B*16/d bytes per chip and the stored histogram state is
    [L, F/d, 2, B]."""
    mode = "data_rs"
    comm_mode = "rs"


class PartitionedDataParallelTreeLearner(_ParallelTreeLearner):
    """tree_learner=data on the partitioned builder: rows sharded, per-leaf
    physical partitions kept shard-local, child histograms psum'd over ICI —
    the reference data-parallel comm structure
    (data_parallel_tree_learner.cpp:149-240) at the partitioned builder's
    per-leaf cost instead of full-data streaming per split."""
    mode = "data_part"
    comm_mode = "psum"
    # no feature sharding here, so EFB group columns and 4-bit packing apply
    supports_groups = True
    supports_packing = True

    def _lazy_active(self) -> bool:
        return self.cegb is not None and self.cegb[2] is not None

    def _make_build_fn(self):
        forced = self.forced
        lazy = self._lazy_active()

        def fn(bins, grad, hess, nd, fm, feat, cegb_args, paid, it):
            return build_tree_partitioned(
                bins, grad, hess, nd, fm, feat,
                num_leaves=self.num_leaves, max_depth=self.max_depth,
                params=self.params, num_bins=self.num_bins,
                use_pallas=self.use_pallas,
                has_categorical=self.has_categorical,
                has_monotone=self.has_monotone,
                feat_num_bins=self.feat_bins,
                unpack_lanes=self.unpack_lanes,
                packed_cols=self.packed_cols, axis_name=self.axis,
                hist_pool_slots=self.hist_pool_slots,
                pallas_interpret=self.pallas_interpret,
                forced=forced,
                cegb=(cegb_args if cegb_args != () else None),
                paid_bits=(paid if lazy else None),
                hist_precision=self.hist_precision,
                quant_it=it, quant_seed=self.quant_seed)

        row = P(self.axis)
        out_specs = TreeArrays(
            *([P()] * len(TreeArrays._fields)))._replace(row_leaf=row)
        if lazy:
            out_specs = (out_specs, P(self.axis, None))
        paid_spec = P(self.axis, None) if lazy else P()
        shard_fn = _shard_map(
            fn, mesh=self.mesh,
            in_specs=(P(self.axis, None), row, row, P(), P(), P(), P(),
                      paid_spec, P()),
            out_specs=out_specs)
        return jax.jit(shard_fn)

    def train(self, grad, hess, num_data_in_bag, feature_mask=None,
              iteration=0):
        grad, hess, fm = self._prep_train(grad, hess, feature_mask)
        cegb_args = (() if self.cegb is None else
                     (self.cegb[0], self.cegb[1], self.cegb_used,
                      self.cegb[2]))
        lazy = self._lazy_active()
        if lazy and self.cegb_paid.shape[0] != grad.shape[0]:
            # repadded rows (mesh-divisible) after the serial-side init
            self.cegb_paid = jnp.zeros(
                (grad.shape[0], self.cegb_paid.shape[1]), jnp.uint8)
        out = self._dispatch_build(
            self.bins, grad, hess,
            jnp.asarray(num_data_in_bag, dtype=jnp.int32), fm, self.feat,
            cegb_args, self.cegb_paid if lazy else (),
            jnp.asarray(iteration, jnp.int32))
        if lazy:
            arrays, self.cegb_paid = out
        else:
            arrays = out
        self._update_cegb_used(arrays)
        return arrays


class FeatureParallelTreeLearner(_ParallelTreeLearner):
    """tree_learner=feature: replicated data on every shard, histogram
    CONSTRUCTION and scan sharded over features (each shard builds only
    its own F/d block, feature_parallel_tree_learner.cpp:33-52), one
    best-split allreduce per split.  Runs the partitioned base builder
    like every other learner."""
    mode = "feature"
    comm_mode = "feature"


class VotingParallelTreeLearner(_ParallelTreeLearner):
    """tree_learner=voting: rows sharded, histograms local, per-split 2*top_k
    feature election + psum of only the elected features' histograms
    (voting_parallel_tree_learner.cpp:170-366).  Runs the partitioned base
    builder like every other learner."""
    mode = "voting"
    comm_mode = "voting"


_LEARNERS = {
    "serial": SerialTreeLearner,
    # tree_learner=data = partitioned builder + reduce-scatter comm (the
    # reference structure).  The psum variant keeps EFB group columns and
    # 4-bit packing (no feature chunking) and remains importable for
    # bundle-heavy datasets.
    "data": DataParallelTreeLearner,
    "feature": FeatureParallelTreeLearner,
    "voting": VotingParallelTreeLearner,
}


def create_tree_learner(dataset, config, mesh: Optional[Mesh] = None):
    """Factory mirroring ``TreeLearner::CreateTreeLearner``
    (src/treelearner/tree_learner.cpp:13-36).  Parallel learners fall back to
    serial on a single device, like the reference's num_machines=1 conflict
    resolution (src/io/config.cpp CheckParamConflict)."""
    kind = str(config.tree_learner)
    if kind not in _LEARNERS:
        raise ValueError("Unknown tree learner type %s" % kind)
    if kind != "serial":
        n_dev = (int(np.prod(mesh.devices.shape)) if mesh is not None
                 else len(jax.devices()))
        if n_dev <= 1:
            from ..utils.log import Log
            Log.warning("tree_learner=%s with one device: training with the "
                        "serial learner", kind)
            kind = "serial"
    if kind == "serial":
        return SerialTreeLearner(dataset, config)
    cls = _LEARNERS[kind]
    if kind == "data" and (
            str(getattr(config, "forcedsplits_filename", "") or "")
            or float(config.cegb_penalty_split) > 0
            or any(config.cegb_penalty_feature_coupled or [])
            or any(config.cegb_penalty_feature_lazy or [])):
        # forced splits / CEGB need every shard to hold the full histogram
        # block (the reference applies them in the serial base class that all
        # learners share); the psum data-parallel learner provides that
        cls = PartitionedDataParallelTreeLearner
    return cls(dataset, config, mesh=mesh)
