"""Round-7 size-bucketed fused-kernel variants (interpret mode).

Three contracts pinned here:

1. Every variant — the single-chunk small-window kernel and each CHUNK
   bucket of the pipelined kernel — matches the plain-XLA reference
   (partition_hist_xla) on the usual tolerances: partition and left count
   exact, histogram to 1e-4.
2. Variants are BIT-EXACT against each other on the same window (rows, nl
   and the folded histogram via array_equal): the kernels share the
   phase-A/histogram building blocks, so dispatch-boundary retunes can
   never shift numerics.  Bucket-boundary windows (CHUNK-1, CHUNK, CHUNK+1
   rows) are covered for each bucket, plus the bpc=2 and nibble-packed
   fallbacks.
3. The fused tree-build path with buckets ENGAGED (build_tree_partitioned
   dispatching through jax.lax.switch, and the whole fused lax.scan
   boosting path) produces bit-identical trees to the same build pinned to
   the single large-bucket plan.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.core.partition import (CHUNK, SMALL_CHUNK, _ALIGN,
                                         _MID_MAX, bucket_name, bucket_of,
                                         fold_hist, fused_bucket_plan,
                                         level_plan,
                                         partition_hist_level_pallas,
                                         partition_hist_pallas,
                                         partition_hist_xla)
from test_partition_kernel import VOFF, make_rows

N_PAD = 3 * CHUNK


def run_variant(wb, wc, *, small, chunk, f=6, num_bins=32, seed=0, thr=11,
                mt=0, dbin=0, is_cat=0, bitset=None, hist_left=1,
                use_unfold=0, eoff=1, gcol=2, nb=None, bpc=1, packed=False,
                n_pad=N_PAD):
    assert wb + wc <= n_pad - CHUNK, "window contract: spare CHUNK of slack"
    rows = make_rows(n_pad, f, num_bins, seed=seed, bpc=bpc, packed=packed)
    nb = num_bins if nb is None else nb
    scal = np.zeros(12 + num_bins // 32, dtype=np.int32)
    scal[:12] = [wb, wc, gcol, thr, 1, mt, nb, dbin, is_cat, hist_left,
                 use_unfold, eoff]
    if bitset is not None:
        scal[12:12 + len(bitset)] = np.asarray(bitset,
                                               np.uint32).view(np.int32)
    r_jax, s_jax = jnp.asarray(rows), jnp.asarray(scal)
    got_rows, got_h4, got_nl = partition_hist_pallas(
        r_jax, s_jax, num_features=f, num_bins=num_bins, voff=VOFF,
        bpc=bpc, packed=packed, interpret=True, chunk=chunk, small=small)
    want_rows, want_hist, want_nl = partition_hist_xla(
        r_jax, s_jax, num_features=f, num_bins=num_bins, voff=VOFF,
        bpc=bpc, packed=packed)
    assert int(got_nl[0, 0]) == int(want_nl)
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))
    got_hist = np.asarray(fold_hist(got_h4, f, num_bins))
    np.testing.assert_allclose(got_hist, np.asarray(want_hist),
                               rtol=1e-4, atol=1e-4)
    return np.asarray(got_rows), got_hist, int(got_nl[0, 0])


def assert_bitwise(a, b):
    """(rows, hist, nl) triples bit-identical across kernel variants."""
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert a[2] == b[2]


SMALL_MAX = SMALL_CHUNK - _ALIGN


@pytest.mark.parametrize("wb,wc", [
    (0, 0),                       # empty window (dead builder iteration)
    (777, 5),                     # tiny unaligned
    (0, SMALL_MAX),               # the dispatch bound itself
    (31, SMALL_MAX),              # max head offset + max window
    (2 * CHUNK - 700, 700),       # window ends AT the spare-CHUNK contract
                                  # edge (wb + wc == n_pad - CHUNK), wb
                                  # unaligned (head offset 4)
])
def test_small_kernel_vs_reference_and_full(wb, wc):
    got_s = run_variant(wb, wc, small=True, chunk=SMALL_CHUNK)
    got_f = run_variant(wb, wc, small=False, chunk=CHUNK)
    assert_bitwise(got_s, got_f)


def test_small_kernel_missing_and_hist_side():
    a = run_variant(50, 900, small=True, chunk=SMALL_CHUNK, mt=1, seed=8)
    b = run_variant(50, 900, small=False, chunk=CHUNK, mt=1, seed=8)
    assert_bitwise(a, b)
    a = run_variant(100, 800, small=True, chunk=SMALL_CHUNK, hist_left=0,
                    seed=7)
    b = run_variant(100, 800, small=False, chunk=CHUNK, hist_left=0, seed=7)
    assert_bitwise(a, b)


def test_small_kernel_categorical_and_unfold():
    bs = (1 << 1) | (1 << 5) | (1 << 17) | (1 << 30)
    a = run_variant(300, 950, small=True, chunk=SMALL_CHUNK, is_cat=1,
                    bitset=[bs], seed=10)
    b = run_variant(300, 950, small=False, chunk=CHUNK, is_cat=1,
                    bitset=[bs], seed=10)
    assert_bitwise(a, b)
    a = run_variant(300, 700, small=True, chunk=SMALL_CHUNK, use_unfold=1,
                    eoff=4, nb=9, seed=11)
    b = run_variant(300, 700, small=False, chunk=CHUNK, use_unfold=1,
                    eoff=4, nb=9, seed=11)
    assert_bitwise(a, b)


def test_small_kernel_packed_and_bpc2():
    a = run_variant(321, 930, small=True, chunk=SMALL_CHUNK, thr=7, nb=16,
                    seed=13, packed=True)
    b = run_variant(321, 930, small=False, chunk=CHUNK, thr=7, nb=16,
                    seed=13, packed=True)
    assert_bitwise(a, b)
    a = run_variant(55, 880, small=True, chunk=SMALL_CHUNK, num_bins=512,
                    thr=300, seed=15, bpc=2)
    b = run_variant(55, 880, small=False, chunk=CHUNK, num_bins=512,
                    thr=300, seed=15, bpc=2)
    assert_bitwise(a, b)


@pytest.mark.parametrize("wc", [SMALL_CHUNK - 1, SMALL_CHUNK,
                                SMALL_CHUNK + 1])
def test_mid_chunk_bucket_boundaries(wc):
    """chunk=1024 pipelined variant at its own chunk boundary — the windows
    where per-chunk bookkeeping (partial groups, k-chunk totals windows with
    totk=8) is most likely to break."""
    run_variant(123, wc, small=False, chunk=SMALL_CHUNK, seed=21)


@pytest.mark.parametrize("wc", [CHUNK - 1, CHUNK, CHUNK + 1])
def test_large_chunk_bucket_boundaries(wc):
    """Both CHUNK buckets at the 4096-row boundary, bit-exact against each
    other (4096+1 rows = 5 chunks of 1024: exercises a partial totals
    group)."""
    a = run_variant(123, wc, small=False, chunk=SMALL_CHUNK, seed=22)
    b = run_variant(123, wc, small=False, chunk=CHUNK, seed=22)
    assert_bitwise(a, b)


def test_mid_chunk_packed_and_bpc2():
    run_variant(100, 2500, small=False, chunk=SMALL_CHUNK, thr=7, nb=16,
                seed=14, packed=True)
    run_variant(55, 2800, small=False, chunk=SMALL_CHUNK, num_bins=512,
                thr=300, seed=15, bpc=2)


def test_mid_chunk_multi_group_totals():
    """> totk chunks (8 x 1024 = one full totals group + change): the group
    DMA fires mid-window, not only at the epilogue.  Needs a 4*CHUNK store
    so the 2-chunk-plus window keeps its spare-CHUNK contract slack."""
    a = run_variant(40, 2 * CHUNK + 900, small=False, chunk=SMALL_CHUNK,
                    seed=23, n_pad=4 * CHUNK)
    b = run_variant(40, 2 * CHUNK + 900, small=False, chunk=CHUNK, seed=23,
                    n_pad=4 * CHUNK)
    assert_bitwise(a, b)


def test_bucket_plan_shapes():
    plan = fused_bucket_plan(1 << 20)
    assert plan[0][0] is True and plan[0][2] == SMALL_MAX
    assert plan[-1][2] is None and plan[-1][1] == CHUNK
    bounds = [b for (_, _, b) in plan[:-1]]
    assert bounds == sorted(bounds)
    # small stores never compile unreachable buckets
    small_plan = fused_bucket_plan(8192)
    assert small_plan[-1][1] == SMALL_CHUNK and len(small_plan) == 2


# ---- the fused tree-build + fused lax.scan boosting path with buckets
# engaged (interpret mode; TPU-only in production) ----


def _toy_booster(n, monkeypatch_learner=None, iters=2, **params):
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective

    rng = np.random.RandomState(3)
    X = rng.normal(size=(n, 8))
    y = X[:, 0] * 1.5 + np.sin(X[:, 1]) + rng.normal(scale=0.1, size=n)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=16)
    base = dict(objective="regression", num_leaves=8, num_iterations=iters,
                min_data_in_leaf=2)
    base.update(params)
    cfg = Config(base)
    booster = GBDT(cfg, ds, create_objective("regression", cfg))
    if monkeypatch_learner is not None:
        monkeypatch_learner(booster.learner)
    return booster


@pytest.mark.parametrize("rows,bucket", [
    (0, "small"), (SMALL_CHUNK - _ALIGN, "small"),
    (SMALL_CHUNK - _ALIGN + 1, "c1024"), (_MID_MAX, "c1024"),
    (_MID_MAX + 1, "c4096"), (1 << 20, "c4096")])
def test_bucket_of_agrees_with_the_builders_searchsorted(rows, bucket):
    """The host's ``bucket_of`` names the variant the builder's dispatch
    takes: same bounds, same side."""
    plan = fused_bucket_plan(1 << 20)
    names = [bucket_name(s, c) for s, c, _ in plan]
    assert names == ["small", "c1024", "c4096"]
    # as build_tree_partitioned's _fused_split selects its branch
    bounds = jnp.asarray([b for (_, _, b) in plan[:-1]], jnp.int32)
    builder = int(jnp.searchsorted(bounds, jnp.int32(rows)))
    assert int(bucket_of(rows, plan)) == builder
    assert names[builder] == bucket
    assert bucket_of([rows, rows], plan).tolist() == [builder, builder]


def test_bucket_of_a_plan_of_one_bucket():
    plan = fused_bucket_plan(512)
    assert [bucket_name(s, c) for s, c, _ in plan] == ["c1024"]
    assert bucket_of([0, 17, 512], plan).tolist() == [0, 0, 0]


def _pin_interpret(learner):
    learner.use_pallas = True
    learner.pallas_interpret = True


# ---- round 12: LEVEL-BATCHED multi-window launches ----
# One partition_hist_level_pallas call must be bit-exact against the same
# windows run as sequential single-window launches of the same kernel
# variant: same rows after all partitions, same per-window histograms and
# left counts.  Windows with wc=0 (dead frontier slots / out-of-class
# windows) must be exact no-ops.


def run_level_vs_sequential(windows, *, small, chunk, f=6, num_bins=32,
                            seed=0, thr=11, bpc=1, packed=False,
                            n_pad=N_PAD):
    rows = make_rows(n_pad, f, num_bins, seed=seed, bpc=bpc, packed=packed)
    S = 12 + num_bins // 32
    scals = np.zeros((len(windows), S), dtype=np.int32)
    for i, (wb, wc) in enumerate(windows):
        assert wb + wc <= n_pad - CHUNK, "window contract"
        scals[i, :12] = [wb, wc, 2, thr, 1, 0, num_bins, 0, 0, 1, 0, 1]
    r = jnp.asarray(rows)
    r_seq = r
    seq_h, seq_nl = [], []
    for i in range(len(windows)):
        r_seq, h, nl = partition_hist_pallas(
            r_seq, jnp.asarray(scals[i]), num_features=f, num_bins=num_bins,
            voff=VOFF, bpc=bpc, packed=packed, interpret=True, chunk=chunk,
            small=small)
        seq_h.append(np.asarray(h))
        seq_nl.append(int(nl[0, 0]))
    r_lvl, h_lvl, nl_lvl = partition_hist_level_pallas(
        r, jnp.asarray(scals), num_features=f, num_bins=num_bins, voff=VOFF,
        bpc=bpc, packed=packed, interpret=True, chunk=chunk, small=small)
    np.testing.assert_array_equal(np.asarray(r_lvl), np.asarray(r_seq))
    for i in range(len(windows)):
        np.testing.assert_array_equal(np.asarray(h_lvl)[i], seq_h[i])
        assert int(nl_lvl[i, 0]) == seq_nl[i]
    return seq_nl


def test_level_launch_two_and_three_window_frontiers():
    """2- and 3-window frontiers of the small kernel, incl. a dead wc=0
    slot riding the launch (the class-masking the level dispatcher uses)."""
    nls = run_level_vs_sequential([(64, 700), (960, 800)],
                                  small=True, chunk=SMALL_CHUNK)
    assert sum(nls) > 0
    run_level_vs_sequential([(0, 500), (512, 0), (777, 900)],
                            small=True, chunk=SMALL_CHUNK, seed=5)


def test_level_launch_full_frontier():
    """A full level's worth of adjacent sub-chunk windows — the 255-leaf
    deep-frontier shape ONE launch must cover."""
    step = 640
    windows = [(i * step, step) for i in range(12)]
    run_level_vs_sequential(windows, small=True, chunk=SMALL_CHUNK, seed=9)


@pytest.mark.parametrize("wc", [CHUNK - 1, CHUNK, CHUNK + 1])
def test_level_launch_chunk_boundary_windows(wc):
    """Multi-window pipelined launches with window counts straddling the
    CHUNK boundary (partial chunks + partial totals groups per window)."""
    run_level_vs_sequential([(0, wc), (CHUNK + 256, wc)],
                            small=False, chunk=CHUNK, seed=21,
                            n_pad=4 * CHUNK)


def test_level_launch_mid_chunk_and_unaligned():
    run_level_vs_sequential([(33, SMALL_CHUNK + 77), (2048 + 17, 3000)],
                            small=False, chunk=SMALL_CHUNK, seed=23,
                            n_pad=4 * CHUNK)


def test_level_launch_packed_and_bpc2():
    run_level_vs_sequential([(64, 700), (960, 800)], small=True,
                            chunk=SMALL_CHUNK, thr=7, num_bins=32, seed=13,
                            packed=True)
    run_level_vs_sequential([(55, 880), (1111, 640)], small=True,
                            chunk=SMALL_CHUNK, num_bins=512, thr=300,
                            seed=15, bpc=2)


def test_level_plan_matches_bucket_plan():
    assert level_plan(1 << 20) == fused_bucket_plan(1 << 20)
    assert level_plan(8192) == fused_bucket_plan(8192)


def test_fused_scan_with_buckets():
    """GBDT.train_chunk down the fused lax.scan path with the Pallas fused
    split pass in interpret mode: the bucketed dispatch (small + mid kernels
    engaged as leaf windows shrink) must produce bit-identical trees and
    scores to the single-large-bucket plan (the round-6 status quo)."""
    n = 4096  # multiple of CHUNK: the fused path engages without padding

    results = {}
    for name in ("buckets", "single"):
        def pin(learner, name=name):
            learner.use_pallas = True
            learner.pallas_interpret = True
            if name == "single":
                learner.bucket_plan = ((False, CHUNK, None),)

        b = _toy_booster(n, pin, iters=2)
        assert b._can_fuse_iters()
        b.train_chunk(2)
        assert b.num_trees == 2
        leaf_values = np.concatenate(
            [np.asarray(t.leaf_value) for t in b.models])
        thresholds = np.concatenate(
            [np.asarray(t.threshold) for t in b.models])
        scores = np.asarray(b.train_score)
        results[name] = (leaf_values, thresholds, scores)
        del b

    got, want = results["buckets"], results["single"]
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


# ---- round 12: tree_grow_mode=level through the fused lax.scan ----


def _model_trees(booster):
    """Model string with the parameter echo stripped (tree content only)."""
    s = booster.save_model_to_string()
    return s.split("parameters:", 1)[0]


def test_level_mode_complete_tree_bitwise_vs_leaf():
    """In the complete-tree regime (num_leaves=2^D, max_depth=D, every
    frontier leaf splittable) BFS and best-first growth perform the SAME
    split set, so level mode must produce bit-identical scores and the same
    per-leaf values as leaf mode — the strongest cross-mode pin available
    without a frozen artifact."""
    n = 4096
    out = {}
    for mode in ("leaf", "level"):
        b = _toy_booster(n, _pin_interpret, iters=2, tree_grow_mode=mode,
                         max_depth=3)
        assert b._can_fuse_iters()
        if mode == "level":
            assert b.learner.effective_grow_mode() == "level"
        b.train_chunk(2)
        assert b.num_trees == 2
        out[mode] = (np.asarray(b.train_score),
                     [np.sort(np.asarray(t.leaf_value[:t.num_leaves]))
                      for t in b.models],
                     [sorted(t.split_feature[:t.num_leaves - 1].tolist())
                      for t in b.models])
    np.testing.assert_array_equal(out["leaf"][0], out["level"][0])
    for lv_leaf, lv_level in zip(out["leaf"][1], out["level"][1]):
        np.testing.assert_array_equal(lv_leaf, lv_level)
    assert out["leaf"][2] == out["level"][2]


@pytest.mark.slow
def test_level_mode_pinned_golden():
    """Level-mode growth against a pinned golden: run-to-run determinism
    plus frozen structural/metric values (budget-limited non-power-of-two
    leaf count, no max_depth => ceil(log2(L)) level schedule).  Slow: the
    L=6 budget is a config-unique interpret compile."""
    runs = []
    for _ in range(2):
        b = _toy_booster(4096, _pin_interpret, iters=2,
                         tree_grow_mode="level", num_leaves=6)
        b.train_chunk(2)
        runs.append((_model_trees(b), np.asarray(b.train_score)))
    assert runs[0][0] == runs[1][0], "level mode must be deterministic"
    np.testing.assert_array_equal(runs[0][1], runs[1][1])
    b = _toy_booster(4096, _pin_interpret, iters=2, tree_grow_mode="level",
                     num_leaves=6)
    b.train_chunk(2)
    leaves = [t.num_leaves for t in b.models]
    assert leaves == [6, 6], leaves
    depths = [int(np.max(t.leaf_depth[:t.num_leaves])) for t in b.models]
    assert max(depths) <= 3  # ceil(log2(6)) = 3 levels
    # metric golden (rtol guards against op-reassociation, not semantics);
    # leaf-wise growth at this config lands at 1.8609 — two lr=0.1 trees
    # only shave ~30% off var(y)=2.598, so the pin is the frozen value, not
    # a "learned well" bar
    mse = float(np.mean((np.asarray(b.train_score)[0]
                         - np.asarray(b.train_data.metadata.label)) ** 2))
    assert np.isclose(mse, 1.8735743, rtol=1e-4), mse


@pytest.mark.slow
def test_level_mode_respects_leaf_budget_mid_level():
    """num_leaves smaller than a full frontier: the budget cuts a level
    mid-frontier (lowest leaf ids win) and growth stops at the cap.
    Slow: config-unique interpret compile."""
    b = _toy_booster(4096, _pin_interpret, iters=1, tree_grow_mode="level",
                     num_leaves=5, max_depth=4)
    b.train_chunk(1)
    t = b.models[0]
    assert t.num_leaves == 5
    assert int(np.max(t.leaf_depth[:t.num_leaves])) <= 4


def test_level_mode_falls_back_without_fused_path():
    """tree_grow_mode=level on a non-fused learner must warn and grow
    leaf-wise (bit-identical to tree_grow_mode=leaf)."""
    b_level = _toy_booster(4096, None, iters=1, tree_grow_mode="level")
    assert b_level.learner.effective_grow_mode() == "leaf"
    b_leaf = _toy_booster(4096, None, iters=1)
    b_level.train_chunk(1)
    b_leaf.train_chunk(1)
    assert _model_trees(b_level) == _model_trees(b_leaf)


def test_trees_per_chunk_model_identical():
    """trees_per_chunk>1 groups scan steps only — trees and scores must be
    bit-identical to trees_per_chunk=1 (3 = 2+1 exercises the remainder
    scan)."""
    outs = {}
    for tpc in (1, 2):
        b = _toy_booster(4096, _pin_interpret, iters=3, trees_per_chunk=tpc)
        assert b._can_fuse_iters()
        b.train_chunk(3)
        assert b.num_trees == 3
        outs[tpc] = (_model_trees(b), np.asarray(b.train_score))
    assert outs[1][0] == outs[2][0]
    np.testing.assert_array_equal(outs[1][1], outs[2][1])


@pytest.mark.slow
def test_trees_per_chunk_with_level_mode():
    """The two round-12 knobs compose: grouped scan steps over level-grown
    trees stay bit-identical to the ungrouped leaf-complete-tree run."""
    b_ref = _toy_booster(4096, _pin_interpret, iters=2, max_depth=3)
    b_ref.train_chunk(2)
    b = _toy_booster(4096, _pin_interpret, iters=2, tree_grow_mode="level",
                     max_depth=3, trees_per_chunk=2)
    b.train_chunk(2)
    np.testing.assert_array_equal(np.asarray(b.train_score),
                                  np.asarray(b_ref.train_score))
