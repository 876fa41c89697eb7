"""The hand-over of the carried row store from one tree to the next: ONE
streamed, in-place pass per tree.

Carried-row-store training (``GBDT._make_fused_train_carried``) keeps every
row's boosting state — gradient, hessian, original row id, the objective's aux
value and the running score, 20 contiguous bytes at ``voff`` — inside the tree
builder's leaf-partitioned ``[N, W]`` u8 store.  Between two trees three
things happen to those bytes, per row at store position ``pos``:

1. the finished tree's (shrinkage-scaled) leaf value of ``pos`` is looked up:
   its leaf windows are disjoint, contiguous and cover ``[0, n)``, so the
   sorted window begins (:func:`leaf_windows`) index it;
2. ``score += leaf value`` (f32);
3. the next tree's ``grad, hess = grad_fn(score, aux, order, it)`` — the
   objective's pointwise gradients, zeroed past the real rows and under the
   bagging mask, one traced function built by the boosting loop.

Through PR 27 these were three whole-store XLA passes (``tree.finish``,
``gbdt.gradients``, ``tree.store``: 112.6 ms of a 747 ms tree at 10.5M rows,
each ``dynamic-update-slice`` of the store alone 8x what reading and writing
it once costs).  Here they are one read-modify-write: the 12 changed bytes go
back into the tile they were read from, the other bytes stay where they are.

Two forms of the same function, chosen like every other step of the learner
by ``use_pallas`` (:func:`advance_row_state`): :func:`advance_row_state_xla`
is the plain form (the CPU path and the tests' oracle) and
:func:`row_state_pass` the Pallas kernel, whose name is what a profiler trace
prints (``%row_state_pass.<n>``; per-layer metric
``row_pass_ms_per_tree.train``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
TILE = 4096          # rows per grid step: the store's padding unit
                     # (partition.CHUNK), so it always divides the row count.
                     # At 10.5M rows x 128 B on a v5e: 4.57 ms a pass, against
                     # 5.70 at 2048 and 8.84 at 1024 (0.6 us a grid step)


def leaf_windows(begin, wcount, leaf_value, num_leaves, n: int):
    """The finished tree's leaf windows in position order: (begins [L] i32
    ascending, values [L] f32).  Slots of leaves that do not exist or hold no
    row sort to the end as ``(n, 0.0)``, so positions past the windows (the
    fused path's spare chunk) read a leaf value of 0."""
    L = begin.shape[0]
    live = (jnp.arange(L) < num_leaves) & (wcount > 0)
    return jax.lax.sort_key_val(
        jnp.where(live, begin, n).astype(jnp.int32),
        jnp.where(live, leaf_value, 0.0).astype(jnp.float32))


def _f32_bytes(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint8)


def i32_col(rows, off: int):
    """The 4-byte column at byte ``off`` of a row store, as [n] i32."""
    return jax.lax.bitcast_convert_type(
        rows[:, off:off + 4], jnp.int32).reshape(rows.shape[0])


def f32_col(rows, off: int):
    return jax.lax.bitcast_convert_type(i32_col(rows, off), jnp.float32)


def advance_row_state_xla(rows, begins, values, grad_fn, it, *, voff: int,
                          n: int):
    """Plain form of the pass.  ``rows`` [n_arr, W] u8 with n_arr >= n;
    ``begins``/``values`` from :func:`leaf_windows`; ``grad_fn(score, aux,
    order, it) -> (grad, hess)`` element-wise.  Returns the new store and the
    sums of grad and hess over the first ``n`` positions (the next tree's
    root totals, in the order a sum over the gradient arrays would take)."""
    pos = jnp.arange(rows.shape[0], dtype=jnp.int32)
    leaf_val = values[jnp.searchsorted(begins, pos, side="right") - 1]
    score = f32_col(rows, voff + 16) + leaf_val
    grad, hess = grad_fn(score, f32_col(rows, voff + 12),
                         i32_col(rows, voff + 8), it)
    slab = jnp.concatenate(
        [_f32_bytes(grad), _f32_bytes(hess), rows[:, voff + 8:voff + 16],
         _f32_bytes(score)], axis=1)
    return (rows.at[:, voff:voff + 20].set(slab),
            jnp.sum(grad[:n]), jnp.sum(hess[:n]))


def _blocking(voff: int, W: int):
    """(first lane, lanes, rows) of the blocks the kernel moves: the
    128-lane-aligned column range of the store that holds the state bytes
    ``[voff, voff + 20)`` — a wide store's bin bytes never leave HBM — by
    as many rows as keep the blocks, double-buffered in and out, and the
    placed i32 tile inside the 16 MiB of VMEM a kernel may use."""
    c0 = voff // _LANE * _LANE
    cw = -(-(voff + 20) // _LANE) * _LANE - c0
    if c0 % cw:                 # a straddling slab in an odd block: all of W
        c0, cw = 0, W
    rows = TILE
    while rows * cw > (1 << 20) and rows > 8 * _LANE:
        rows //= 2
    return c0, cw, rows


def _byte_onehot(cw: int, offs, dtype):
    """[32, cw] one-hot: row ``8 * k + c`` marks byte ``k`` of the 4-byte
    column ``c`` at lane ``offs[c] + k``.  Bytes of one significance share a
    sublane tile of 8, so whole [8, R] tiles shift and OR with no sublane
    shuffle."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (32, cw), 1)
    r = jax.lax.broadcasted_iota(jnp.int32, (32, cw), 0)
    k, c = r // 8, r % 8
    want = jnp.full((32, cw), -1, jnp.int32)
    for ci, off in enumerate(offs):
        want = jnp.where(c == ci, off + k, want)
    return (lanes == want).astype(dtype)


def _make_kernel(grad_fn, *, L: int, R: int, cw: int, voff: int):
    """``voff`` is relative to the lane block the kernel sees."""
    npk = R // _LANE
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    steps = max(1, L.bit_length())
    rd = (voff + 8, voff + 12, voff + 16)       # order, aux, score
    wr = (voff, voff + 4, voff + 16)            # grad, hess, score
    NT = (((1,), (1,)), ((), ()))
    TN = (((0,), (0,)), ((), ()))

    def kernel(begins_ref, values_ref, it_ref, rows_ref, out_ref, sums_ref):
        i = pl.program_id(0)
        p0 = i * R

        # ---- the finished tree's leaf value of every position ----
        # j0 = the window that holds p0 (begins ascending, begins[0] == 0):
        # a binary search for the number of begins <= p0
        def search(_, lohi):
            lo, hi = lohi
            mid = jnp.minimum((lo + hi) // 2, L - 1)
            le = begins_ref[mid] <= p0
            go = lo < hi
            return (jnp.where(go & le, mid + 1, lo),
                    jnp.where(go & jnp.logical_not(le), mid, hi))

        lo, _ = jax.lax.fori_loop(0, steps, search, (i32(0), i32(L)))
        j0 = jnp.maximum(lo - 1, 0)
        pos = (p0
               + jax.lax.broadcasted_iota(i32, (npk, _LANE), 0) * _LANE
               + jax.lax.broadcasted_iota(i32, (npk, _LANE), 1))

        # then only the windows that begin inside the tile: on average
        # 1 + L / (rows / R) of them
        def more(c):
            j, _ = c
            return (j < L) & (begins_ref[jnp.minimum(j, L - 1)] < p0 + R)

        def take(c):
            j, lv = c
            return j + 1, jnp.where(pos >= begins_ref[j], values_ref[j], lv)

        _, leaf_val = jax.lax.while_loop(
            more, take, (j0 + 1, jnp.full((npk, _LANE), values_ref[j0], f32)))

        # ---- read: order, aux, score as lane-major 32-bit words ----
        # one i8 x i8 -> i32 selector dot, transposed so rows land on lanes
        # (partition._extract_col_lanes); & 255 undoes the signed-byte wrap
        tile = rows_ref[...]
        ext = jax.lax.dot_general(
            _byte_onehot(cw, rd, i8),
            jax.lax.bitcast_convert_type(tile, i8), NT,
            preferred_element_type=i32)                      # [32, R]
        word = ((ext[0:8] & 255) | ((ext[8:16] & 255) << 8)
                | ((ext[16:24] & 255) << 16) | (ext[24:32] << 24))

        def col(c, dtype):
            w = word[c:c + 1, :].reshape(npk, _LANE)
            return w if dtype == i32 else jax.lax.bitcast_convert_type(
                w, dtype)

        # ---- the row's step: score of this tree, gradients of the next ----
        score = col(2, f32) + leaf_val
        grad, hess = grad_fn(score, col(1, f32), col(0, i32), it_ref[0])
        grad, hess = grad.astype(f32), hess.astype(f32)

        # ---- write: the 12 changed bytes back into the tile ----
        # bytes as [32, R] i8, placed by a one-hot dot contracting the byte
        # axis (phase B of the split kernel), selected against the lane mask
        words = jnp.concatenate(
            [jax.lax.bitcast_convert_type(x, i32).reshape(1, R)
             for x in (grad, hess, score)] + [jnp.zeros((5, R), i32)], axis=0)
        byts = jnp.concatenate(
            [(words >> (8 * k)) & 255 for k in range(4)], axis=0)
        byts = ((byts ^ 128) - 128).astype(i8)               # [32, R]
        placed = jax.lax.dot_general(
            byts, _byte_onehot(cw, wr, i8), TN,
            preferred_element_type=i32)                      # [R, cw]
        lanes = jax.lax.broadcasted_iota(i32, (1, cw), 1)
        changed = (((lanes >= voff) & (lanes < voff + 8))
                   | ((lanes >= voff + 16) & (lanes < voff + 20)))
        out_ref[...] = jnp.where(changed, (placed & 255).astype(jnp.uint8),
                                 tile)

        # ---- the next tree's root totals, lane-dense ----
        @pl.when(i == 0)
        def _init():
            sums_ref[...] = jnp.zeros_like(sums_ref)

        sums_ref[0:8, :] += jnp.sum(grad.reshape(npk // 8, 8, _LANE), axis=0)
        sums_ref[8:16, :] += jnp.sum(hess.reshape(npk // 8, 8, _LANE), axis=0)

    return kernel


def row_state_pass(rows, begins, values, grad_fn, it, *, voff: int,
                   tile: int = 0, interpret: bool = False):
    """The pass as a Pallas kernel: grid over row tiles, the store aliased
    input to output, so a tile's bytes are read once and written once where
    they lie.  Same arguments and results as :func:`advance_row_state_xla`
    (positions past ``n`` hold zero gradients, so the sums need no ``n``)."""
    n_arr, W = rows.shape
    c0, cw, planned = _blocking(voff, W)
    tile = tile or planned
    assert n_arr % tile == 0 and tile % (8 * _LANE) == 0, (n_arr, tile)
    L = begins.shape[0]
    block = pl.BlockSpec((tile, cw), lambda i, *_: (i, c0 // cw))
    rows_out, sums = pl.pallas_call(
        _make_kernel(grad_fn, L=L, R=tile, cw=cw, voff=voff - c0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_arr // tile,),
            in_specs=[block],
            out_specs=[block,
                       pl.BlockSpec((16, _LANE), lambda i, *_: (0, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_arr, W), jnp.uint8),
                   jax.ShapeDtypeStruct((16, _LANE), jnp.float32)],
        input_output_aliases={3: 0},
        interpret=interpret,
        name="row_state_pass",
    )(begins, values, jnp.reshape(it, (1,)).astype(jnp.int32), rows)
    return rows_out, jnp.sum(sums[0:8]), jnp.sum(sums[8:16])


def advance_row_state(rows, begins, values, grad_fn, it, *, voff: int, n: int,
                      use_pallas: bool, interpret: bool = False):
    if use_pallas:
        return row_state_pass(rows, begins, values, grad_fn, it, voff=voff,
                              interpret=interpret)
    return advance_row_state_xla(rows, begins, values, grad_fn, it,
                                 voff=voff, n=n)
