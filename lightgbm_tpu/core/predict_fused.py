"""Fused inference engine: tree-blocked path-matrix prediction.

core/predict.py's `lax.scan` runs ONE [N,M]@[M,L] contraction per tree — T
dispatch-serialized steps for embarrassingly tree-parallel work, each far too
small to fill the MXU.  Here G trees are stacked per scan block and one
batched `dot_general` ([N, G, M] x [G, M, L], batched over the block axis —
the block-diagonal form of a single [N, G*M] @ [G*M, G*L] contraction)
replaces G steps: the scan shrinks to T/G steps of G-fold larger matmuls.
G is chosen so a block's path matrices stay VMEM-resident
(:func:`tree_block` — the same trace-static sizing discipline as
``partition.fused_bucket_plan``).

Three serving mechanisms ride on top:

- **binned fast path** (:class:`BinnedEnsembleArrays`): when the caller holds
  the training-format u8/u16 row store (refit, training-data scoring,
  ``Dataset``-backed predict), ``go_left`` is an integer compare against
  host-prebinned thresholds — the semantics of ``tree_learner._route_left``
  (tree.h:262-331 *Inner decisions) — skipping the f32 gather/NaN pipeline
  and reading 1 byte/feature instead of 4.
- **bounded shape buckets**: rows pad to a fixed ladder
  (:data:`PREDICT_BUCKETS`) instead of unbounded pow2 targets, and batches
  beyond the largest bucket stream through it in fixed-shape chunks — so
  steady-state serving compiles at most ``len(PREDICT_BUCKETS)`` programs per
  model, ever.  :class:`FusedPredictor` additionally caches the stacked
  device ensemble so repeat calls re-stack nothing.
- **sharded batch predict** lives in ``parallel.learners.sharded_predict``
  (rows over the mesh via shard_map; body built from :func:`scan_blocks`).

Every path is BIT-exact vs the per-tree ``predict_ensemble`` scan: hits are
small-integer f32 sums (exact in any accumulation order), ``match`` is an
exact one-hot, so each tree contributes exactly its leaf value, and the [N]
score accumulation + early-stop checks replay the per-tree order inside an
unrolled per-block loop.  Pinned by tests/test_predict_fused.py the way
tests/test_partition_buckets.py pins the split-kernel variants.
"""
from __future__ import annotations

import functools
import time
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import BinType, MissingType
from ..obs import active as _telemetry_active
from ..obs.spans import span as _span
from ..obs import compile as _compile
from ..obs import recompile as _recompile
from ..plan import device_specs as _device_specs
from ..plan import state as _plan_state
from ..resilience import PROGRAM_ERRORS
from .predict import (EnsembleArrays, _path_matrix, decide_raw,
                      stack_ensemble_host)
from .tree import K_CATEGORICAL_MASK, K_DEFAULT_LEFT_MASK, Tree

# path-matrix VMEM budget per scan block (f32 bytes) and the block-width cap;
# the same discipline as partition.fused_bucket_plan: sizes are host-static,
# derived only from the model shape, so the dispatch never retraces.  The
# budget constant moved to plan/device_specs.py (round 18, one source of
# truth per device_kind); a tuned/pinned kernel plan overrides it through
# plan/state.py at stack time.
BLOCK_VMEM_BYTES = _device_specs.PREDICT_BLOCK_VMEM_BYTES
BLOCK_MAX = 64

# fixed row-padding ladder: any batch size compiles at most len() programs
# per model; batches beyond the top bucket stream through it in fixed-shape
# chunks, so steady-state serving NEVER recompiles.
PREDICT_BUCKETS = (128, 1024, 8192, 65536, 524288)


def tree_block(t: int, m: int, l: int,
               vmem_bytes: Optional[int] = None,
               precision: str = "exact") -> int:
    """Trees per scan block: the largest count whose stacked [G, M, L] path
    matrices fit the block VMEM budget, rebalanced so the final block is
    not ragged (T=100 at cap 32 -> 4 blocks of 25, zero pad trees).

    The budget defaults through the kernel planner (round 18): a pinned
    or tuned plan's ``predict_block_vmem_bytes`` wins, else the
    device-spec constant — byte-equal to the historical sizing when no
    plan cache is engaged.  The bf16 tier's path matrices are 2 bytes per
    element, so the same VMEM budget admits ~2x the trees per block —
    bf16 stackings get their OWN G (and their own plan site,
    ``predict_fused_bf16``), never the exact tier's."""
    if vmem_bytes is None:
        vmem_bytes = _plan_state.predict_block_vmem() or BLOCK_VMEM_BYTES
    per_tree = max(m * l * (2 if precision == "bf16" else 4), 1)
    cap = max(1, min(BLOCK_MAX, int(vmem_bytes) // per_tree, max(t, 1)))
    n_blocks = -(-max(t, 1) // cap)
    return -(-max(t, 1) // n_blocks)


def shape_bucket(n: int) -> int:
    """Smallest ladder bucket holding ``n`` rows (top bucket for chunking)."""
    for b in PREDICT_BUCKETS:
        if n <= b:
            return b
    return PREDICT_BUCKETS[-1]


class BinnedEnsembleArrays(NamedTuple):
    """Stacked per-tree arrays for the binned row store, [T, M] per node
    (or [T/G, G, M] when blocked).  Thresholds are host-prebinned; the
    decide is ``tree_learner._route_left`` vectorized over (row, node)."""
    column: jax.Array        # [T, M] i32 — the bin matrix (group) column
    thr_bin: jax.Array       # [T, M] i32
    default_left: jax.Array  # [T, M] bool
    missing_type: jax.Array  # [T, M] i32 (io.binning.MissingType)
    num_bin: jax.Array       # [T, M] i32 (feature bins, for unfold + NaN bin)
    default_bin: jax.Array   # [T, M] i32
    offset: jax.Array        # [T, M] i32 (EFB group code of feature bin 1)
    is_cat: jax.Array        # [T, M] bool
    cat_bitset: jax.Array    # [T, M, W] u32 left-BIN sets (W=0: no cat)
    path_sign: jax.Array     # [T, M, L] f32
    path_len: jax.Array      # [T, L] f32 (pad -1)
    leaf_value: jax.Array    # [T, L] f32


def stack_ensemble_binned_host(trees: List[Tree],
                               dataset) -> BinnedEnsembleArrays:
    """Host: prebin every node of ``trees`` against ``dataset``'s bin
    mappers / EFB group layout (the per-node mapping of
    ``gbdt._tree_to_device``, batched into stacked numpy arrays).

    Any dataset sharing the training mappers (reference-aligned valid sets,
    subsets) routes identically; thresholds land on bin upper bounds so the
    binned decide is bit-parity with the raw-value decide on binned rows."""
    t_cnt = len(trees)
    m = max(max(t.num_leaves - 1, 1) for t in trees)
    l = max(t.num_leaves for t in trees)
    has_cat = any(t.num_cat > 0 for t in trees)
    w = 0
    if has_cat:
        cat_bins = [mp.num_bin for mp in dataset.bin_mappers
                    if mp.bin_type == BinType.CATEGORICAL]
        w = -(-max(cat_bins, default=32) // 32)
    col = np.zeros((t_cnt, m), dtype=np.int32)
    thr = np.zeros((t_cnt, m), dtype=np.int32)
    dl = np.zeros((t_cnt, m), dtype=bool)
    mt = np.zeros((t_cnt, m), dtype=np.int32)
    nb = np.ones((t_cnt, m), dtype=np.int32)
    db = np.zeros((t_cnt, m), dtype=np.int32)
    off = np.ones((t_cnt, m), dtype=np.int32)
    ic = np.zeros((t_cnt, m), dtype=bool)
    cb = np.zeros((t_cnt, m, w), dtype=np.uint32)
    ps = np.zeros((t_cnt, m, l), dtype=np.float32)
    pl = np.full((t_cnt, l), -1.0, dtype=np.float32)
    lv = np.zeros((t_cnt, l), dtype=np.float32)
    group_idx = dataset.group_idx
    for i, tree in enumerate(trees):
        ni = max(tree.num_leaves - 1, 0)
        for node in range(ni):
            f = int(tree.split_feature[node])
            mapper = dataset.bin_mappers[f]
            j = dataset.inner_feature_map[f]
            col[i, node] = 0 if group_idx is None else int(group_idx[j])
            off[i, node] = (1 if dataset.bin_offset is None
                            else int(dataset.bin_offset[j]))
            nb[i, node] = int(dataset.num_bin_per_feature[j])
            db[i, node] = int(mapper.default_bin)
            mt[i, node] = int(mapper.missing_type)
            dt = int(tree.decision_type[node])
            dl[i, node] = (dt & K_DEFAULT_LEFT_MASK) != 0
            if dt & K_CATEGORICAL_MASK:
                ic[i, node] = True
                ci = int(tree.threshold[node])
                lo = tree.cat_boundaries[ci]
                hi = tree.cat_boundaries[ci + 1]
                for wd in range(lo, hi):
                    word = int(tree.cat_threshold[wd])
                    for j2 in range(32):
                        if (word >> j2) & 1:
                            b = mapper.categorical_2_bin.get(
                                (wd - lo) * 32 + j2)
                            if b is not None:
                                cb[i, node, b >> 5] |= np.uint32(1 << (b & 31))
            else:
                thr[i, node] = mapper.value_to_bin(float(tree.threshold[node]))
        ps[i], pl[i] = _path_matrix(tree, m, l)
        lv[i, :tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
    return BinnedEnsembleArrays(column=col, thr_bin=thr, default_left=dl,
                                missing_type=mt, num_bin=nb, default_bin=db,
                                offset=off, is_cat=ic, cat_bitset=cb,
                                path_sign=ps, path_len=pl, leaf_value=lv)


def decide_binned(B: jax.Array, ens: BinnedEnsembleArrays) -> jax.Array:
    """go_left [N, *TD, M] for binned rows B [N, num_groups]; node arrays
    shaped [*TD, M].  Integer compares only — ``_route_left`` +
    ``_unfold_bin`` semantics (NumericalDecisionInner tree.h:262-277,
    CategoricalDecisionInner :283-331: the NaN bin is never a member, so
    missing goes right)."""
    cols = jnp.take(B, ens.column, axis=1).astype(jnp.int32)  # [N, *TD, M]
    off = ens.offset[None]
    nb = ens.num_bin[None]
    # EFB group code -> feature bin (identity for singleton groups, off=1)
    bin_ = jnp.where((cols >= off) & (cols <= off + nb - 2),
                     cols - off + 1, 0)
    mt = ens.missing_type[None]
    is_missing = jnp.where(
        mt == int(MissingType.NAN), bin_ == nb - 1,
        jnp.where(mt == int(MissingType.ZERO),
                  bin_ == ens.default_bin[None], False))
    go_left = jnp.where(is_missing, ens.default_left[None],
                        bin_ <= ens.thr_bin[None])
    w = ens.cat_bitset.shape[-1]
    if w:
        # ONE gather over the word axis (program size O(1) in w, the
        # _route_left lookup shape); bins past the padded word range clamp
        # to zero words, i.e. not-a-member -> right, matching the host
        wi = bin_ >> 5
        word = jnp.take_along_axis(
            ens.cat_bitset[None], jnp.clip(wi, 0, w - 1)[..., None],
            axis=-1)[..., 0]
        bit = (word >> (bin_ & 31).astype(jnp.uint32)) & jnp.uint32(1)
        go_left = jnp.where(ens.is_cat[None], (wi < w) & (bit == 1), go_left)
    return go_left


def _decide(rows: jax.Array, blk) -> jax.Array:
    if isinstance(blk, BinnedEnsembleArrays):
        return decide_binned(rows, blk)
    return decide_raw(rows, blk.split_feature, blk.threshold,
                      blk.default_left, blk.missing_type, blk.is_cat,
                      blk.cat_bitset)


def _block(ens, g: int):
    """[T, ...] stacked numpy arrays -> [T/G, G, ...] blocks (pad trees are
    dead: all-zero path columns + path_len -1 never match, leaf values 0;
    the contrib schedule's pad trees are inactive-by-construction)."""
    t = ens[0].shape[0]
    tb = -(-t // g)
    pad = tb * g - t

    def one(name, a):
        if pad:
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            a = np.pad(a, widths, constant_values=(-1.0 if name == "path_len"
                                                   else 0))
        return jnp.asarray(a.reshape((tb, g) + a.shape[1:]))

    return type(ens)(*[one(n, a) for n, a in zip(ens._fields, ens)])


def _cast_lossy(ens):
    """The bf16 tier's device ensemble: ``path_sign`` and ``leaf_value``
    in bfloat16, EVERY routing array untouched.  Path signs are exactly
    ±1/0 in bf16, and the hit contraction accumulates in f32
    (``preferred_element_type``), so leaf HITS stay bit-exact vs the exact
    tier — only the leaf values (bf16-rounded) and the score accumulation
    (bf16 carry) are lossy, which is the declared error the budget gates."""
    return ens._replace(path_sign=ens.path_sign.astype(jnp.bfloat16),
                        leaf_value=ens.leaf_value.astype(jnp.bfloat16))


def stack_ensemble_blocked(trees: List[Tree], g: Optional[int] = None,
                           precision: str = "exact") -> EnsembleArrays:
    """Raw-feature blocked device ensemble ([T/G, G, ...] fields)."""
    host = stack_ensemble_host(trees)
    m, l = host.path_sign.shape[1], host.path_sign.shape[2]
    ens = _block(host, g or tree_block(len(trees), m, l,
                                       precision=precision))
    return _cast_lossy(ens) if precision == "bf16" else ens


def stack_ensemble_binned_blocked(trees: List[Tree], dataset,
                                  g: Optional[int] = None,
                                  precision: str = "exact"
                                  ) -> BinnedEnsembleArrays:
    """Binned blocked device ensemble ([T/G, G, ...] fields)."""
    host = stack_ensemble_binned_host(trees, dataset)
    m, l = host.path_sign.shape[1], host.path_sign.shape[2]
    ens = _block(host, g or tree_block(len(trees), m, l,
                                       precision=precision))
    return _cast_lossy(ens) if precision == "bf16" else ens


def scan_blocks(blocks, rows: jax.Array, *, early_stop_margin: float = -1.0,
                round_period: int = 10, want_leaf: bool = False):
    """The tree-blocked predict core (traceable; jitted wrappers below).

    One scan step per G-tree block: a shared decide, ONE batched
    [N, G, M] x [G, M, L] contraction, an exact one-hot match, then an
    unrolled per-tree accumulate that replays the per-tree scan's f32 add
    order and early-stop check positions bit-exactly (margin-based
    prediction early stop, prediction_early_stop.cpp:26-65).

    Dtype-generic over the ensemble's value arrays: the accumulate dtype
    is inferred from ``leaf_value`` (f32 exact tier / bf16 lossy tier),
    and every cast below is a no-op for f32 inputs, so the exact tier's
    jaxpr — and therefore its compiled program and its scores — is
    byte-identical to the pre-precision-axis one.  In the bf16 tier the
    hit sums still accumulate in f32 (small exact integers from ±1 bf16
    products) and ``match`` is still an exact one-hot, so ROUTING is
    bit-exact across tiers; only leaf rounding + the bf16 score carry
    differ."""
    n = rows.shape[0]
    g = blocks.path_len.shape[1]
    acc_dtype = blocks.leaf_value.dtype

    def block_step(carry, blk):
        score, active, idx = carry
        go_left = _decide(rows, blk)                        # [N, G, M]
        d = jnp.where(go_left, 1.0, -1.0).astype(blk.path_sign.dtype)
        hits = jax.lax.dot_general(
            d, blk.path_sign, (((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.float32)             # [G, N, L]
        match = (hits == blk.path_len[:, None, :]).astype(acc_dtype)
        contrib = jax.lax.dot_general(
            match, blk.leaf_value, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=acc_dtype)               # [G, N]
        for j in range(g):
            score = score + jnp.where(active, contrib[j], 0.0)
            if early_stop_margin >= 0:
                check = (idx + j + 1) % round_period == 0
                active = active & jnp.where(
                    check, 2.0 * jnp.abs(score) < early_stop_margin, True)
        if want_leaf:
            leaf = jnp.argmax(match, axis=2).astype(jnp.int32)  # [G, N]
            return (score, active, idx + g), leaf
        return (score, active, idx + g), None

    init = (jnp.zeros((n,), acc_dtype), jnp.ones((n,), bool), jnp.int32(0))
    (score, _, _), leaves = jax.lax.scan(block_step, init, blocks)
    if want_leaf:
        return score, jnp.transpose(leaves, (2, 0, 1)).reshape(n, -1)
    return score


@functools.partial(jax.jit, static_argnames=("early_stop_margin",
                                             "round_period", "want_leaf"))
def predict_blocked(blocks, rows, early_stop_margin: float = -1.0,
                    round_period: int = 10, want_leaf: bool = False):
    """Jitted tree-blocked predict over a raw [N, F] f32 chunk or a binned
    [N, num_groups] u8/u16 chunk (dispatch on the ensemble type)."""
    return scan_blocks(blocks, rows, early_stop_margin=early_stop_margin,
                       round_period=round_period, want_leaf=want_leaf)


def predict_compile_count() -> int:
    """Compiled-program count of the bucketed dispatch (the no-recompile
    serving contract is pinned against this going flat)."""
    return predict_blocked._cache_size()


@functools.partial(jax.jit, static_argnames=("early_stop_margin",
                                             "round_period", "want_leaf"))
def predict_scan_fallback(blocks, rows, early_stop_margin: float = -1.0,
                          round_period: int = 10, want_leaf: bool = False):
    """The degraded-mode predictor: the same scan core over a g=1 blocking
    (one tree per scan step — the pre-blocking per-tree scan), jitted into
    its OWN cache so a failure of the big blocked program (bucket compile,
    corrupted cache entry) cannot poison the fallback.  Bit-exact with the
    blocked path by the same argument every blocking is (integer hit sums,
    per-tree f32 add order replayed)."""
    return scan_blocks(blocks, rows, early_stop_margin=early_stop_margin,
                       round_period=round_period, want_leaf=want_leaf)


class FusedPredictor:
    """Device predictor for one class's tree sequence, stacked ONCE.

    The serving counterpart of the reference's cached ``SingleRowPredictor``
    (c_api.cpp:52-98), keyed by the boosters' ``EnsembleArrays`` identity:
    GBDT caches instances per (model range, generation, kind), so the hot
    path is pad-to-bucket + one cached-executable call."""

    def __init__(self, trees: List[Tree], dataset=None,
                 kind: str = "raw", precision: str = "exact") -> None:
        if kind not in ("raw", "binned"):
            raise ValueError("kind must be 'raw' or 'binned'")
        if precision not in ("exact", "bf16"):
            raise ValueError("precision must be 'exact' or 'bf16'")
        if kind == "binned" and dataset is None:
            raise ValueError("binned predictor needs the training dataset "
                             "layout (bin mappers + EFB groups)")
        self.kind = kind
        self.precision = precision
        # recompile/compile attribution site: the bf16 tier dispatches
        # through the SAME predict_blocked jit cache (dtype is part of the
        # aval, so tiers can never share a compiled program), but counts
        # under its own site name; `watch` below keys the shared cache so
        # the first bf16 dispatch doesn't inherit exact-tier compiles
        self._site = ("predict_blocked" if precision == "exact"
                      else "predict_blocked_bf16")
        self.n_trees = len(trees)
        # host trees retained for the contrib path: the SHAP schedule is
        # harvested lazily on the first predict_contrib call (score-only
        # serving pays nothing), and the host trees are the harvest input
        self._trees = list(trees)
        # lazily-built contrib program inputs per phi width, plus the g=1
        # degraded re-blocking (same discipline as _fb_ens)
        self._contrib: dict = {}
        self._fb_contrib: dict = {}
        self._contrib_warned = False
        # optional growth hook (serving registry residency accounting):
        # called with the byte size of lazily-built contrib ensembles
        self.on_grow = None
        # serving attribution: the ModelRegistry stamps the owning model's
        # name here so degraded-path fallbacks count per model, and hooks
        # on_fallback so each registry tallies only its OWN degradations
        # (the process-global resilience ledger can't distinguish two
        # registries holding a model under the same name)
        self.owner: Optional[str] = None
        self.on_fallback = None
        # keep the layout dataset alive: GBDT's predictor cache keys on
        # id(dataset), which must not be recycled while this entry lives
        self.layout_ds = dataset
        # degraded-mode serving: the g=1 fallback ensemble is derived from
        # the blocked one by reshape on first failure (no host trees
        # retained, no re-stacking; never an exception on the serving path)
        self._fb_ens = None
        self._fb_warned = False
        if kind == "raw":
            self.ens = (stack_ensemble_blocked(trees, precision=precision)
                        if trees else None)
        else:
            self.ens = (stack_ensemble_binned_blocked(
                trees, dataset, precision=precision) if trees else None)
        # plan provenance (round 18): which planner sized this stacking's
        # tree-block G — stamped once per run so BENCH/serving artifacts
        # record the plan behind every latency number.  The bf16 tier is
        # its own site (its 2-byte path matrices size a different G).
        tele = _telemetry_active()
        if tele is not None and self.ens is not None:
            _plan_state.stamp(
                tele, ("predict_fused" if precision == "exact"
                       else "predict_fused_bf16"),
                _plan_state.current_provenance(),
                key="t%d_g%d" % (self.n_trees,
                                 int(self.ens.path_len.shape[1])),
                store=self.kind, g=int(self.ens.path_len.shape[1]))

    def _prep_rows(self, X) -> np.ndarray:
        if self.kind == "raw":
            return np.ascontiguousarray(np.asarray(X, dtype=np.float32))
        X = np.ascontiguousarray(np.asarray(X))
        if X.dtype not in (np.uint8, np.uint16):
            raise TypeError("binned predictor wants the u8/u16 row store, "
                            "got %s" % X.dtype)
        return X

    def __call__(self, X, early_stop_margin: float = -1.0,
                 round_period: int = 10, want_leaf: bool = False):
        """[N] f64 raw scores (or [N, T] i32 leaf indices with want_leaf).

        Rows pad to the bucket ladder; batches beyond the top bucket stream
        through it in fixed-shape chunks (rows are independent, so early
        stop and leaves are chunk-local)."""
        n = len(X)
        if self.n_trees == 0 or n == 0:
            if want_leaf:
                return np.zeros((n, self.n_trees), dtype=np.int32)
            return np.zeros(n, dtype=np.float64)
        X = self._prep_rows(X)
        top = PREDICT_BUCKETS[-1]
        scores = np.empty(n, dtype=np.float64)
        leaves = (np.empty((n, self.n_trees), dtype=np.int32)
                  if want_leaf else None)
        tele = _telemetry_active()
        for lo in range(0, n, top):
            chunk = X[lo:lo + top]
            nc = len(chunk)
            bucket = shape_bucket(nc)
            if bucket > nc:
                chunk = np.concatenate(
                    [chunk, np.zeros((bucket - nc,) + chunk.shape[1:],
                                     dtype=chunk.dtype)])
            t0 = time.perf_counter()
            misses = 0
            try:
                with _span("tree_block_predict"):
                    out = predict_blocked(
                        self.ens, jnp.asarray(chunk),
                        early_stop_margin=float(early_stop_margin),
                        round_period=int(round_period),
                        want_leaf=want_leaf)
                # growth of the bucketed dispatch's compiled-program count
                # is a recompile, attributed to this row bucket: the live
                # form of the "steady-state serving never recompiles"
                # invariant.  watch= keys the SHARED predict_blocked jit
                # cache, so each tier baselines against the same counter
                # instead of charging the other tier's compiles to itself.
                misses = _recompile.note_dispatch(self._site, bucket,
                                                  predict_compile_count(),
                                                  watch="predict_blocked")
            except PROGRAM_ERRORS:
                raise
            except Exception as exc:  # degraded serving: never an exception
                out = self._predict_degraded(
                    jnp.asarray(chunk), bucket, exc,
                    float(early_stop_margin), int(round_period), want_leaf)
            if tele is not None:
                dt = time.perf_counter() - t0
                hist = ("predict_dispatch_s_bucket_%d" % bucket
                        if self.precision == "exact" else
                        "predict_dispatch_bf16_s_bucket_%d" % bucket)
                tele.histogram(hist).observe(dt)
                tele.event("predict", rows=int(nc), bucket=int(bucket),
                           store=self.kind, trees=int(self.n_trees),
                           dt_s=dt, want_leaf=bool(want_leaf),
                           precision=self.precision)
                # compile accounting (obs/compile.py): every dispatch
                # wall feeds the steady estimate; miss-bearing ones are
                # priced against it (warm persistent-cache loads told
                # apart from true compiles by their tiny excess)
                _compile.note_dispatch(tele, self._site, bucket,
                                       dt, misses)
            if want_leaf:
                leaves[lo:lo + nc] = np.asarray(
                    out[1][:nc, :self.n_trees], dtype=np.int32)
            else:
                scores[lo:lo + nc] = np.asarray(out[:nc], dtype=np.float64)
        return leaves if want_leaf else scores

    # ---- SHAP contributions (core/predict_contrib.py) ----

    def contrib_blocks(self, ncol: int):
        """The stacked contrib program inputs for this predictor's trees
        (decide arrays + harvested TreeSHAP schedules, [T/G', G', ...]
        blocked at the contrib G'), built ONCE per phi width and cached —
        the FusedPredictor cache contract extended to explanations."""
        blocks = self._contrib.get(int(ncol))
        if blocks is None:
            from .predict_contrib import stack_contrib_blocked
            blocks, g = stack_contrib_blocked(
                self._trees, int(ncol),
                dataset=self.layout_ds if self.kind == "binned" else None,
                kind=self.kind)
            self._contrib[int(ncol)] = blocks
            if self.on_grow is not None:
                grew = sum(int(a.size * a.dtype.itemsize)
                           for part in blocks for a in part)
                self.on_grow(grew)
            tele = _telemetry_active()
            if tele is not None:
                # plan provenance: the contrib G is a round-18 plan site
                # of its own (sized on the REAL schedule footprint)
                _plan_state.stamp(
                    tele, "contrib_fused", _plan_state.current_provenance(),
                    key="t%d_g%d" % (self.n_trees, int(g)),
                    store=self.kind, g=int(g))
        return blocks

    def predict_contrib(self, X, ncol: int) -> np.ndarray:
        """[N, ncol] f64 SHAP contributions (last column = expected
        value) through the device path-decomposition kernel.  Rows pad to
        the same shape-bucket ladder as scores; batches beyond the top
        bucket stream through it in fixed-shape chunks; failures serve
        DEGRADED through the g=1 contrib program, and a failure of the
        harvest or of the degraded program itself falls all the way back
        to the host TreeSHAP scan (raw rows; counted — a raw contrib
        request is never an exception)."""
        if self.precision != "exact":
            raise ValueError("pred_contrib has no lossy tier: SHAP "
                             "contributions are exact (f64) only; use a "
                             "precision='exact' predictor")
        n = len(X)
        if self.n_trees == 0 or n == 0:
            return np.zeros((n, int(ncol)), dtype=np.float64)
        X = self._prep_rows(X)
        try:
            return self._predict_contrib_device(X, ncol)
        except PROGRAM_ERRORS:
            raise
        except Exception as exc:  # harvest or double-failure: host net
            return self._contrib_host_scan(X, ncol, exc)

    def _predict_contrib_device(self, X: np.ndarray,
                                ncol: int) -> np.ndarray:
        n = len(X)
        blocks = self.contrib_blocks(ncol)
        top = PREDICT_BUCKETS[-1]
        out = np.empty((n, int(ncol)), dtype=np.float64)
        tele = _telemetry_active()
        for lo in range(0, n, top):
            chunk = X[lo:lo + top]
            nc = len(chunk)
            bucket = shape_bucket(nc)
            if bucket > nc:
                chunk = np.concatenate(
                    [chunk, np.zeros((bucket - nc,) + chunk.shape[1:],
                                     dtype=chunk.dtype)])
            t0 = time.perf_counter()
            misses = 0
            try:
                from .predict_contrib import (contrib_compile_count,
                                              predict_contrib_blocked)
                with _span("contrib_fused"), \
                        jax.enable_x64(True):
                    # materialize INSIDE the x64 scope: slicing the f64
                    # result outside it would re-canonicalize avals to f32
                    res = np.asarray(predict_contrib_blocked(
                        blocks, jnp.asarray(chunk)))
                misses = _recompile.note_dispatch(
                    "predict_contrib_blocked", bucket,
                    contrib_compile_count())
            except PROGRAM_ERRORS:
                raise
            except Exception as exc:  # degraded serving: never an exception
                res = self._contrib_degraded(chunk, bucket, exc, ncol)
            if tele is not None:
                dt = time.perf_counter() - t0
                tele.histogram("contrib_latency_s_bucket_%d"
                               % bucket).observe(dt)
                tele.counter("contrib_calls").inc()
                tele.counter("contrib_rows").inc(int(nc))
                tele.event("contrib", rows=int(nc), bucket=int(bucket),
                           store=self.kind, trees=int(self.n_trees),
                           dt_s=dt)
                _compile.note_dispatch(tele, "predict_contrib_blocked",
                                       bucket, dt, misses)
            out[lo:lo + nc] = np.asarray(res[:nc], dtype=np.float64)
        return out

    def _contrib_degraded(self, chunk, bucket: int, exc: Exception,
                          ncol: int):
        """Serve the contrib chunk through the g=1 contrib program after
        the blocked dispatch failed — counted like every degraded path
        (``resilience.note_fallback`` + the ``contrib_fallbacks``
        counter), warned once per predictor."""
        from ..resilience import note_fallback
        from ..utils.log import Log
        from .predict_contrib import predict_contrib_scan_fallback
        if not self._contrib_warned:
            self._contrib_warned = True
            Log.warning("fused pred_contrib failed for bucket %d (%s: %s); "
                        "serving DEGRADED via the g=1 contrib program",
                        bucket, type(exc).__name__, exc)
        site = ("predict_contrib_blocked@%s" % self.owner if self.owner
                else "predict_contrib_blocked")
        note_fallback(site, reason="%s: %s" % (type(exc).__name__, exc),
                      bucket=int(bucket),
                      **({"model": self.owner} if self.owner else {}))
        tele = _telemetry_active()
        if tele is not None:
            tele.counter("contrib_fallbacks").inc()
        if self.on_fallback is not None:
            self.on_fallback(site)
        fb = self._fb_contrib.get(int(ncol))
        if fb is None:
            with jax.enable_x64(True):
                fb = tuple(
                    type(part)(*[
                        jnp.reshape(a, (a.shape[0] * a.shape[1], 1)
                                    + a.shape[2:]) for a in part])
                    for part in self._contrib[int(ncol)])
            self._fb_contrib[int(ncol)] = fb
        with jax.enable_x64(True):
            res = np.asarray(predict_contrib_scan_fallback(
                fb, jnp.asarray(chunk)))
        _recompile.note_dispatch(
            "predict_contrib_fallback", bucket,
            predict_contrib_scan_fallback._cache_size())
        return res

    def _contrib_host_scan(self, X: np.ndarray, ncol: int,
                           exc: Exception) -> np.ndarray:
        """The last-resort net under :meth:`predict_contrib`: the host
        per-tree TreeSHAP recursion on the f32-cast raw rows (routing
        matches the device decide by the floored-threshold contract).
        Binned rows carry bin CODES, not feature values — the host scan
        cannot route them, so a binned double-failure re-raises (the
        caller's raw-path booster fallback still applies)."""
        if self.kind != "raw":
            raise exc
        from ..resilience import note_fallback
        from ..utils.log import Log
        site = ("predict_contrib@%s" % self.owner if self.owner
                else "predict_contrib")
        Log.warning("device pred_contrib failed beyond the degraded "
                    "program (%s: %s); serving via the host TreeSHAP scan",
                    type(exc).__name__, exc)
        note_fallback(site, reason="%s: %s" % (type(exc).__name__, exc),
                      rows=int(len(X)),
                      **({"model": self.owner} if self.owner else {}))
        tele = _telemetry_active()
        if tele is not None:
            tele.counter("contrib_fallbacks").inc()
        if self.on_fallback is not None:
            self.on_fallback(site)
        out = np.zeros((len(X), int(ncol)), dtype=np.float64)
        for tree in self._trees:
            out += tree.predict_contrib(X, int(ncol))
        return out

    # ---- degraded mode (resilience): per-tree scan fallback ----

    def _fallback_ens(self):
        """g=1 re-blocking of the degraded path, built lazily on the first
        failure (a healthy predictor never pays for it) by RESHAPING the
        stacked ensemble: [T/G, G, ...] -> [T_pad, 1, ...].  Pad trees stay
        dead (path_len -1 never matches, leaf values 0) and trail the real
        ones, so scores, early-stop check positions and the leading
        ``n_trees`` leaf columns are unchanged — same bit-exactness
        argument as any other blocking."""
        if self._fb_ens is None:
            self._fb_ens = type(self.ens)(*[
                jnp.reshape(a, (a.shape[0] * a.shape[1], 1) + a.shape[2:])
                for a in self.ens])
        return self._fb_ens

    def _predict_degraded(self, rows, bucket: int, exc: Exception,
                          early_stop_margin: float, round_period: int,
                          want_leaf: bool):
        """Serve the chunk through the per-tree scan after the blocked
        dispatch failed: counted (``resilience.note_fallback`` +
        ``predict_fallbacks`` telemetry counter), warned once per
        predictor, bit-exact with the blocked result."""
        from ..resilience import note_fallback
        from ..utils.log import Log
        if not self._fb_warned:
            self._fb_warned = True
            Log.warning("fused predict failed for bucket %d (%s: %s); "
                        "serving DEGRADED via the per-tree scan path",
                        bucket, type(exc).__name__, exc)
        # serving runs carry the owning model in the site key so fallback
        # counts surface per model in the registry stats + summary
        site = ("%s@%s" % (self._site, self.owner) if self.owner
                else self._site)
        note_fallback(site, reason="%s: %s" % (type(exc).__name__, exc),
                      bucket=int(bucket),
                      **({"model": self.owner} if self.owner else {}))
        if self.on_fallback is not None:
            self.on_fallback(site)
        out = predict_scan_fallback(
            self._fallback_ens(), rows,
            early_stop_margin=float(early_stop_margin),
            round_period=int(round_period), want_leaf=want_leaf)
        # the fallback's own compiles are recompiles too — a steady-state
        # degraded loop must also read zero after its first bucket compile
        # (both tiers share the fallback jit cache; watch= keys it once)
        _recompile.note_dispatch(
            "predict_fallback" if self.precision == "exact"
            else "predict_fallback_bf16", bucket,
            predict_scan_fallback._cache_size(), watch="predict_fallback")
        return out
