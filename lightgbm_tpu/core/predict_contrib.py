"""Device-side ``pred_contrib``: fused TreeSHAP path-decomposition kernels.

``GBDT.predict_contrib`` used to loop per tree over a per-row PYTHON
TreeSHAP recursion (``Tree.predict_contrib_row`` — the Lundberg & Lee
exact algorithm the reference runs inside ``Tree::PredictContrib``,
tree.h:133).  That made explanations the last serving surface still on
the host: any request needing SHAP values with its scores lost the whole
fused-engine win.  This module is the accelerator-native formulation
(GPUTreeShap, Mitchell et al.): decompose each tree into its root->leaf
paths at STACK time, then one device program computes per-(row, leaf-path)
unwound permutation weights for G-tree blocks and contracts them into a
``[N, F+1]`` phi matrix — the same tree-blocked scan structure, shape
bucket ladder and predictor cache as the round-8 score engine.

**Exactness contract.**  The kernel is an op-for-op replay of the host
recursion:

- the per-leaf op SCHEDULE (extend / unwind / unwound-sum, exactly the
  ``_extend_path`` / ``_unwind_path`` / ``_unwound_path_sum`` sequence the
  recursion performs on the way to that leaf) is row-INDEPENDENT, so it is
  harvested on the host once per (tree, leaf);
- every row-independent operand (cover-fraction products, path lengths)
  is precomputed on the host with the same f64 expressions the recursion
  evaluates, and every row-DEPENDENT operand is a {0,1} "hot bit" product
  (did the row follow the path direction at every node splitting on this
  feature?) — exactly representable;
- pweight math runs in f64 on device (the kernel dispatches under
  ``jax.enable_x64`` — its jit cache entries are keyed apart
  from the f32 score programs);
- phi accumulation order is CANONICAL (per tree: expected value, then
  leaves in index order, then path positions in order) on both sides:
  ``Tree.predict_contrib_row`` accumulates in the same order, and within
  one leaf path features are unique so there are no unordered collisions.

What that buys, precisely (tests/test_predict_contrib.py): ROUTING is
bit-exact (leaf paths, hot bits, NaN/categorical/EFB decisions — integer
and boolean structure, robust against any compiler), the raw and BINNED
paths are pinned bitwise IDENTICAL on training data, and device-vs-host
phi agrees to a few ULPs with the sum-to-raw-score invariant held at
f64 precision.  Full per-bit equality of the f64 weight arithmetic
against the host is NOT claimed: in eager execution the replay IS
bitwise the host's (pinned by the disable_jit test), but under jit
XLA:CPU legally refolds multiply/divide chains and contracts mul+add
into FMAs — and it strips ``lax.optimization_barrier`` from the
optimized module entirely, so no HLO-level fence survives to pin per-op
rounding (measured: 214 barriers in, 0 out; PERF.md round 19 has the
full post-mortem).  The barriers below are kept where rounding points
matter most — they are free at runtime and DO fence on backends that
honor them.

Routing decisions reuse the score engine's decide verbatim — the raw
``decide_raw`` f32 pipeline or the BINNED integer-compare fast path with
the exact ``_route_left`` semantics (EFB unfold, categorical bin-bitsets,
NaN/missing routing) — so contrib inherits every routing golden the score
path is pinned by.

Cost note: TreeSHAP is O(D^2) per (row, leaf) against O(D) for a score,
so the contrib program is intentionally the expensive sibling of
``scan_blocks``; G is sized by the round-18 planner budget against the
REAL per-tree schedule footprint (site ``contrib_fused``), so deep trees
get narrow blocks and the program stays VMEM-honest.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..plan import device_specs as _device_specs
from ..plan import state as _plan_state
from .predict import stack_ensemble_host
from .predict_fused import BLOCK_MAX, _block, _decide
from .tree import Tree


class ContribSchedule(NamedTuple):
    """Host-harvested per-(tree, leaf) TreeSHAP op schedules, stacked to
    common [T, L, ...] shapes (or [T/G, G, L, ...] blocked).  All f64
    fields are the exact host-computed operands; ``*_os`` fields index the
    path step whose hot-bit prefix product supplies the op's one-fraction
    (-1 = constant 1.0).  Pad trees/leaves/slots are inactive and
    contribute exact zeros."""
    depth: jax.Array       # [T, L] i32 — root->leaf internal-node count
    path_node: jax.Array   # [T, L, D] i32 — node ids along the path
    path_dir: jax.Array    # [T, L, D] bool — True = path goes left
    prev_occ: jax.Array    # [T, L, D] i32 — last earlier step with the
    #                        same feature (-1 none): the o-product chain
    ext_act: jax.Array     # [T, L, D+1] bool
    ext_n: jax.Array       # [T, L, D+1] f64 — index appended (= len before)
    ext_z: jax.Array       # [T, L, D+1] f64 — the extend's zero fraction
    ext_os: jax.Array      # [T, L, D+1] i32
    unw_act: jax.Array     # [T, L, D] bool
    unw_n: jax.Array       # [T, L, D] f64 — len-1 at the unwind
    unw_z: jax.Array       # [T, L, D] f64 — unwound entry's zero fraction
    unw_os: jax.Array      # [T, L, D] i32
    sum_act: jax.Array     # [T, L, S] bool
    sum_n: jax.Array       # [T, L, S] f64 — final len-1
    sum_z: jax.Array       # [T, L, S] f64
    sum_os: jax.Array      # [T, L, S] i32
    leaf_value: jax.Array  # [T, L] f64 (the host's f64 values, NOT the
    #                        score path's f32 copies)
    expected: jax.Array    # [T] f64 — per-tree expected value (phi[-1])
    gather_idx: jax.Array  # [T, C, R] i32 — flat (leaf*S + slot) term
    #                        index per (feature column, rank); L*S = the
    #                        zero sentinel.  Rank order is (leaf asc,
    #                        slot asc): the canonical accumulation order.


def _leaf_paths(tree: Tree):
    """[(leaf, [(node, go_left), ...])] in LEAF-INDEX order."""
    if tree.num_leaves == 1:
        return []
    out = {}
    stack = [(0, [])]
    while stack:
        node, path = stack.pop()
        for child, d in ((tree.left_child[node], True),
                         (tree.right_child[node], False)):
            cpath = path + [(int(node), d)]
            if child < 0:
                out[~int(child)] = cpath
            else:
                stack.append((int(child), cpath))
    return [(leaf, out[leaf]) for leaf in sorted(out)]


def harvest_contrib_host(trees: List[Tree], ncol: int) -> ContribSchedule:
    """Walk every (tree, leaf) path once, simulating the host recursion's
    path bookkeeping in f64, and emit the stacked numpy schedule arrays.
    ``ncol`` is ``max_feature_idx + 2`` (phi width, last column = expected
    value)."""
    t_cnt = len(trees)
    l_dim = max(max(t.num_leaves, 1) for t in trees)
    per_tree = []
    d_max, s_max, r_max = 0, 0, 0
    for tree in trees:
        leaves = {}
        for leaf, path in _leaf_paths(tree):
            d = len(path)
            d_max = max(d_max, d)
            # simulate the recursion's path-entry list: (feature, z, step)
            entries = [(-1, np.float64(1.0), -1)]
            exts = [(True, 0, np.float64(1.0), -1)]
            unws = []
            feats_so_far: List[int] = []
            prev = []
            for k, (node, go_left) in enumerate(path):
                f = int(tree.split_feature[node])
                # prev_occ: the o-product chain for step k
                p_occ = -1
                for j in range(k - 1, -1, -1):
                    if feats_so_far[j] == f:
                        p_occ = j
                        break
                prev.append(p_occ)
                feats_so_far.append(f)
                # duplicate-feature unwind (after the step-k extend)
                dup = next((i for i, e in enumerate(entries) if e[0] == f),
                           None)
                izf = np.float64(1.0)
                if dup is not None:
                    ent = entries.pop(dup)
                    izf = ent[1]
                    unws.append((True, len(entries), ent[1], ent[2]))
                else:
                    unws.append((False, 0, np.float64(1.0), -1))
                # the extend entering the path child: its zero fraction is
                # the child's cover ratio times the unwound entry's — the
                # exact host expression (row-independent: the path child's
                # count is used whether the row ran hot or cold there)
                child = (tree.left_child[node] if go_left
                         else tree.right_child[node])
                r = (tree._node_count(int(child))
                     / max(tree._node_count(int(node)), 1e-300))
                z = np.float64(r) * izf
                exts.append((True, len(entries), z, k))
                entries.append((f, z, k))
            sums = [(True, len(entries) - 1, e[1], e[2], e[0])
                    for e in entries[1:]]
            s_max = max(s_max, len(sums))
            leaves[leaf] = (path, prev, exts, unws, sums)
        per_tree.append(leaves)
    c = int(ncol)

    def zeros(shape, dtype):
        return np.zeros(shape, dtype=dtype)

    depth = zeros((t_cnt, l_dim), np.int32)
    p_node = zeros((t_cnt, l_dim, d_max), np.int32)
    p_dir = zeros((t_cnt, l_dim, d_max), bool)
    p_prev = np.full((t_cnt, l_dim, d_max), -1, np.int32)
    e_act = zeros((t_cnt, l_dim, d_max + 1), bool)
    e_n = zeros((t_cnt, l_dim, d_max + 1), np.float64)
    e_z = zeros((t_cnt, l_dim, d_max + 1), np.float64)
    e_os = np.full((t_cnt, l_dim, d_max + 1), -1, np.int32)
    u_act = zeros((t_cnt, l_dim, d_max), bool)
    u_n = zeros((t_cnt, l_dim, d_max), np.float64)
    u_z = np.ones((t_cnt, l_dim, d_max), np.float64)
    u_os = np.full((t_cnt, l_dim, d_max), -1, np.int32)
    s_act = zeros((t_cnt, l_dim, s_max), bool)
    s_n = zeros((t_cnt, l_dim, s_max), np.float64)
    s_z = np.ones((t_cnt, l_dim, s_max), np.float64)
    s_os = np.full((t_cnt, l_dim, s_max), -1, np.int32)
    lv = zeros((t_cnt, l_dim), np.float64)
    ev = zeros((t_cnt,), np.float64)
    # gather ranks: per (tree, feature) the terms in (leaf asc, slot asc)
    # order — the canonical accumulation order both sides replay
    ranks = [dict() for _ in range(t_cnt)]
    for i, tree in enumerate(trees):
        ev[i] = tree.expected_value()
        lv[i, :tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
        for leaf, (path, prev, exts, unws, sums) in per_tree[i].items():
            d = len(path)
            depth[i, leaf] = d
            for k, (node, go_left) in enumerate(path):
                p_node[i, leaf, k] = node
                p_dir[i, leaf, k] = go_left
                p_prev[i, leaf, k] = prev[k]
            for k, (act, n, z, os_) in enumerate(exts):
                e_act[i, leaf, k] = act
                e_n[i, leaf, k] = float(n)
                e_z[i, leaf, k] = z
                e_os[i, leaf, k] = os_
            for k, (act, n, z, os_) in enumerate(unws):
                u_act[i, leaf, k] = act
                u_n[i, leaf, k] = float(n)
                u_z[i, leaf, k] = z
                u_os[i, leaf, k] = os_
            for s, (act, n, z, os_, feat) in enumerate(sums):
                s_act[i, leaf, s] = act
                s_n[i, leaf, s] = float(n)
                s_z[i, leaf, s] = z
                s_os[i, leaf, s] = os_
                ranks[i].setdefault(int(feat), []).append(
                    int(leaf) * s_max + s)
        if ranks[i]:
            r_max = max(r_max, max(len(v) for v in ranks[i].values()))
    sentinel = l_dim * s_max
    g_idx = np.full((t_cnt, c, r_max), sentinel, np.int32)
    for i in range(t_cnt):
        for feat, flat in ranks[i].items():
            g_idx[i, feat, :len(flat)] = flat
    return ContribSchedule(
        depth=depth, path_node=p_node, path_dir=p_dir, prev_occ=p_prev,
        ext_act=e_act, ext_n=e_n, ext_z=e_z, ext_os=e_os,
        unw_act=u_act, unw_n=u_n, unw_z=u_z, unw_os=u_os,
        sum_act=s_act, sum_n=s_n, sum_z=s_z, sum_os=s_os,
        leaf_value=lv, expected=ev, gather_idx=g_idx)


def contrib_bytes_per_tree(sched: ContribSchedule, dec) -> int:
    """Per-tree device footprint of one stacked (schedule + decide) tree —
    the planner's sizing input (the schedule, not the score path matrix,
    dominates for contrib)."""
    t = max(int(sched.depth.shape[0]), 1)
    total = sum(int(np.asarray(a).nbytes) for a in sched)
    total += sum(int(np.asarray(a).nbytes) for a in dec)
    return max(total // t, 1)


def contrib_tree_block(t: int, per_tree_bytes: int,
                       vmem_bytes: Optional[int] = None) -> int:
    """Trees per contrib scan block under the planner budget (round 18:
    a pinned/tuned plan's predict block budget wins, else the device-spec
    constant), rebalanced so the last block is not ragged — the same
    discipline as ``predict_fused.tree_block`` but priced on the REAL
    harvested schedule footprint."""
    if vmem_bytes is None:
        vmem_bytes = (_plan_state.predict_block_vmem()
                      or _device_specs.PREDICT_BLOCK_VMEM_BYTES)
    cap = max(1, min(BLOCK_MAX, int(vmem_bytes) // max(per_tree_bytes, 1),
                     max(t, 1)))
    n_blocks = -(-max(t, 1) // cap)
    return -(-max(t, 1) // n_blocks)


def stack_contrib_blocked(trees: List[Tree], ncol: int, dataset=None,
                          kind: str = "raw",
                          g: Optional[int] = None) -> Tuple[tuple, int]:
    """Harvest + block the contrib program inputs: returns
    ``((decide_blocked, schedule_blocked), g)``.  The decide ensemble is
    the SAME stacked node arrays the score path uses (raw f32 thresholds
    or the binned integer-compare fields), re-blocked at the contrib G so
    both halves scan together.  Device arrays are created under x64 so the
    f64 schedule operands survive the transfer."""
    if kind == "binned":
        from .predict_fused import stack_ensemble_binned_host
        dec_host = stack_ensemble_binned_host(trees, dataset)
    else:
        dec_host = stack_ensemble_host(trees)
    sched_host = harvest_contrib_host(trees, ncol)
    if g is None:
        g = contrib_tree_block(
            len(trees), contrib_bytes_per_tree(sched_host, dec_host))
    with jax.enable_x64(True):
        dec = _block(dec_host, g)
        sched = _block(sched_host, g)
    return (dec, sched), int(g)


def contrib_scan(blocks, rows: jax.Array) -> jax.Array:
    """The tree-blocked contrib core (traceable; jitted wrappers below):
    one scan step per G-tree block replays every leaf's host op schedule
    vectorized over (row, tree-in-block, leaf), then contracts the emitted
    terms into phi [N, C] in the canonical order.  Must be traced under
    x64 (the jitted wrappers' callers hold ``enable_x64``)."""
    dec0, sc0 = blocks
    n = rows.shape[0]
    c = sc0.gather_idx.shape[2]

    def block_step(phi, blk):
        dec, sc = blk
        g, l_dim, d = sc.path_node.shape
        p = d + 1                       # max path length during the walk
        r_dim = sc.gather_idx.shape[2]
        s_dim = sc.sum_act.shape[2]
        go_left = _decide(rows, dec)                         # [N, G, M]
        g_i = jnp.arange(g)[:, None, None]
        if d:
            hot = (go_left[:, g_i, sc.path_node]
                   == sc.path_dir[None])                     # [N, G, L, D]
            live = (jnp.arange(d)[None, None]
                    < sc.depth[..., None])                   # [G, L, D]
            hot = hot | ~live[None]
            # o prefix products: opre[..., k] = AND of the row's hot bits
            # over steps j <= k splitting on step k's feature (the chain
            # rides prev_occ so each step is one gather, not a mask scan)
            opre_list = []
            for k in range(d):
                h = hot[..., k]
                if k == 0:
                    opre_list.append(h)
                    continue
                stack = jnp.stack(opre_list, axis=-1)        # [N, G, L, k]
                prev = sc.prev_occ[..., k]                   # [G, L]
                sel = jnp.take_along_axis(
                    stack, jnp.clip(prev, 0, k - 1)[None, :, :, None],
                    axis=-1)[..., 0]
                opre_list.append(h & jnp.where(prev[None] < 0, True, sel))
            opre = jnp.stack(opre_list, axis=-1)             # [N, G, L, D]
        else:
            opre = jnp.ones((n, g, l_dim, 0), bool)

        def o_of(os_idx):
            if d == 0:
                return jnp.ones((n, g, l_dim), jnp.float64)
            sel = jnp.take_along_axis(
                opre, jnp.clip(os_idx, 0, d - 1)[None, ..., None],
                axis=-1)[..., 0]
            return jnp.where(os_idx[None] < 0, True,
                             sel).astype(jnp.float64)

        # pweights: P tensors [N, G, L] f64, updated sequentially by the
        # slot replay (ext_0, unw_0, ext_1, ..., unw_{D-1}, ext_D, sums)
        zero = jnp.zeros((n, g, l_dim), jnp.float64)
        w = [zero for _ in range(p)]
        for k in range(d + 1):
            # ---- extend slot k (the host _extend_path, op for op) ----
            act = sc.ext_act[..., k][None]
            n_f = sc.ext_n[..., k][None]
            z = sc.ext_z[..., k][None]
            o = o_of(sc.ext_os[..., k])
            np1 = n_f + 1.0
            init = jnp.where(n_f == 0.0, 1.0, 0.0)
            for i in range(min(k, p - 1) + 1):
                w[i] = jnp.where(act & (n_f == i), init, w[i])
            for i in range(min(k - 1, p - 2), -1, -1):
                act_i = act & (n_f > i)
                t1 = ((o * w[i]) * (i + 1.0)) / np1
                w[i + 1] = jnp.where(act_i, w[i + 1] + t1, w[i + 1])
                t2 = ((z * w[i]) * (n_f - i)) / np1
                w[i] = jnp.where(act_i, t2, w[i])
            if k >= d:
                break
            # ---- unwind slot k (the host _unwind_path) ----
            act = sc.unw_act[..., k][None]
            n_f = sc.unw_n[..., k][None]
            z = sc.unw_z[..., k][None]
            o = o_of(sc.unw_os[..., k])
            np1 = n_f + 1.0
            hi = min(k, p - 1)
            nxt = w[0]
            for i in range(1, hi + 1):
                nxt = jnp.where(n_f == i, w[i], nxt)
            hot_sel = o != 0.0
            for i in range(hi - 1, -1, -1):
                act_i = act & (n_f > i)
                w_hot = (nxt * np1) / ((i + 1.0) * o)
                n_hot = w[i] - (((w_hot * z) * (n_f - i)) / np1)
                w_cold = (w[i] * np1) / (z * (n_f - i))
                w_new = jnp.where(hot_sel, w_hot, w_cold)
                nxt = jnp.where(act_i & hot_sel, n_hot, nxt)
                w[i] = jnp.where(act_i, w_new, w[i])
        # ---- unwound-sum slots (the host _unwound_path_sum + emit) ----
        # optimization_barrier between the replay and the sums: pweights
        # are division results, and the sum loop divides them again —
        # XLA's (a/b)/c -> a/(b*c) simplification across the stage
        # boundary would round once where the host rounds twice
        w = list(jax.lax.optimization_barrier(tuple(w)))
        terms = []
        for s in range(s_dim):
            act = sc.sum_act[..., s][None]
            n_f = sc.sum_n[..., s][None]
            z = sc.sum_z[..., s][None]
            o = o_of(sc.sum_os[..., s])
            np1 = n_f + 1.0
            nxt = w[0]
            for i in range(1, p):
                nxt = jnp.where(n_f == i, w[i], nxt)
            hot_sel = o != 0.0
            z_ok = z != 0.0
            total = zero
            _ob = jax.lax.optimization_barrier
            for j in range(p - 2, -1, -1):
                act_j = act & (n_f > j)
                # optimization_barrier on EVERY f64 intermediate of this
                # loop: XLA legally rewrites division/multiply chains
                # ((a/b)/c -> a/(b*c), a*(b/c) refolding, duplicated
                # subexpressions re-fused with different contraction),
                # each rounding differently from the host's op sequence
                # — which breaks the bit-exactness contract.  The
                # barriers pin the host's exact rounding points; note
                # the host computes q FIRST here (``(n - i) / (n + 1)``
                # is parenthesized in ``_unwound_path_sum``, unlike
                # ``_unwind_path``).
                q = _ob((n_f - j) / np1)
                tmp = _ob((nxt * np1) / ((j + 1.0) * o))
                tot_hot = _ob(total + tmp)
                n_hot = _ob(w[j] - _ob((tmp * z) * q))
                tot_cold = _ob(total + _ob(w[j] / z) / q)
                new_tot = jnp.where(hot_sel, tot_hot,
                                    jnp.where(z_ok, tot_cold, total))
                total = _ob(jnp.where(act_j, new_tot, total))
                nxt = _ob(jnp.where(act_j & hot_sel, n_hot, nxt))
            v = sc.leaf_value[None]
            terms.append(jnp.where(act, _ob(_ob(total * (o - z)) * v), 0.0))
        if terms:
            tflat = jnp.stack(terms, axis=-1).reshape(n, g, l_dim * s_dim)
        else:
            tflat = jnp.zeros((n, g, 0), jnp.float64)
        tflat = jnp.concatenate(
            [tflat, jnp.zeros((n, g, 1), jnp.float64)], axis=-1)
        # optimization_barrier: the term products otherwise fuse through
        # the rank gathers into the phi adds, where the backend contracts
        # mul+add into an FMA — one rounding where the host has two —
        # breaking the bit-exactness contract
        tflat = jax.lax.optimization_barrier(tflat)
        # canonical contraction: per tree in block order, a PER-TREE
        # subtotal (expected value, then every feature's terms in
        # (leaf asc, slot asc) rank order — ordered f64 adds, never an
        # unordered reduction: within one leaf features are unique, so
        # each rank-add lands at most one real term per column; sentinel
        # ranks add exact zeros) and then one matrix add into phi — the
        # exact association of the host's ``out += tree.predict_contrib``
        for gi in range(g):
            phi_t = jnp.zeros((n, c), jnp.float64)
            phi_t = phi_t.at[:, c - 1].add(sc.expected[gi])
            for r in range(r_dim):
                phi_t = phi_t + tflat[:, gi, sc.gather_idx[gi, :, r]]
            phi = phi + phi_t
        return phi, None

    phi0 = jnp.zeros((n, c), jnp.float64)
    phi, _ = jax.lax.scan(block_step, phi0, blocks)
    return phi


predict_contrib_blocked = jax.jit(contrib_scan)
"""Jitted tree-blocked contrib dispatch: phi [N, C] f64 for a raw [N, F]
f32 chunk or a binned [N, num_groups] u8/u16 chunk.  Call under
``jax.enable_x64`` (the f64 schedule operands and phi)."""

# the degraded-mode contrib program: the same core over a g=1 re-blocking,
# jitted into its OWN cache so a failure of the big blocked program cannot
# poison the fallback (the predict_scan_fallback discipline)
predict_contrib_scan_fallback = jax.jit(contrib_scan)


def contrib_compile_count() -> int:
    """Compiled-program count of the contrib dispatch (the no-recompile
    contrib-serving contract is pinned against this going flat)."""
    return predict_contrib_blocked._cache_size()
