"""Bring-up proof on the chip: train -> save -> load -> score, once, at Higgs width.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # the cross-chip path and its control only

One process, no network, data made from a seed, everything written under
``chip_smoke_out/``.  Drives the system through the entry points a user calls
(``GBDT.train_chunk`` as the CLI does, ``lightgbm_tpu.train``, the CLI,
``Booster.predict``, ``pred_contrib``, ``lgb.serve``) on the benchmark's own
task (``benchmarks/datagen.py`` with the generator of
``benchmarks/configs/higgs-10m5.json``) with the COMPILED
Pallas kernels, and checks what comes out by the repo's own means: the kernels
against their plain-XLA references, the Pallas learner against the XLA learner
every CPU test uses, loaded-model scores against in-memory ones, SHAP
additivity, served answers against ``predict``.  Stops at the first failure.

Exits nonzero with ``"ok": false`` where JAX finds no TPU — there is no CPU
branch.  Every time printed is from one run and includes compilation (loads
from the compile cache where it was warm: the device phase prints how many
entries it held at the start) — a sign of life, not a benchmark.  The last
stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chip_smoke_out")

SEED = 0
F = 28                      # Higgs width
MAX_BIN = 255
LEAVES = 255
ROWS_TRAIN = 10_500_000     # Higgs rows (the higgs_train cell's shape)
ROWS_HELD_OUT = 1_050_000   # scored through the fused predictor
ROWS_SMALL = 1 << 20        # entry-point / agreement phases
ROWS_CLI = 32_768           # file-backed CLI dataset (text parse bound)
ROWS_CONTRIB_TRAIN = 65_536
ROWS_4CHIP = 4 << 20
ITERS = 5
ITERS_SMALL = 4
VOFF = 28                   # row-store layout build_tree_partitioned gives F=28
# Held-out AUC that phase_entry_points' lightgbm_tpu.train call must pass.
# The same call on the CPU (XLA learner; ROWS_SMALL rows, ITERS_SMALL rounds,
# seed SEED + 1) reads 0.78165, and 8192 rows x 16 rounds x 15 leaves read
# 0.78906 (tests/test_chip_smoke_data.py); no model passes the task's Bayes
# AUC, the generator's ``bayes_auc`` (0.860).
AUC_FLOOR = 0.76


def say(msg):
    print(msg, flush=True)


def benchmark_table(n, n_test, seed):
    """``n`` training and ``n_test`` held-out rows of the ``higgs_train``
    cell's task: read from the benchmark, not copied (Bayes AUC 0.860)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        import datagen
    finally:
        sys.path.pop(0)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "higgs-10m5.json")) as fh:
        gen = json.load(fh)["generator"]
    X, y = datagen.make(seed, n + n_test, F, gen)
    return X[:n], y[:n], X[n:], y[n:]


def auc(y, score):
    from lightgbm_tpu.metric.binary import weighted_auc
    return float(weighted_auc(y, np.asarray(score, np.float64), None))


def logloss(y, raw):
    raw = np.asarray(raw, np.float64)
    return float(np.mean(np.logaddexp(0.0, raw) - y * raw))


def assert_no_fallbacks():
    from lightgbm_tpu import resilience
    from lightgbm_tpu.plan import cache as plan_cache
    counts = dict(resilience.fallback_counts())
    if plan_cache.fallback_count():
        counts["plan_cache"] = plan_cache.fallback_count()
    assert not counts, "degraded paths served part of the run: %r" % counts


def assert_compiled_pallas(learner):
    """With these two flags build_tree_partitioned dispatches the compiled
    kernels or raises — it has no other path (PR 23 removed the silent ones)."""
    assert learner.use_pallas and not learner.pallas_interpret, (
        "learner is not on the compiled Pallas path: use_pallas=%r "
        "pallas_interpret=%r" % (learner.use_pallas,
                                 learner.pallas_interpret))


def pallas_kernels_in(program_text):
    return program_text.count("tpu_custom_call")


def binary_config(**kw):
    from lightgbm_tpu.config import Config
    base = dict(objective="binary", num_leaves=LEAVES, learning_rate=0.1,
                max_bin=MAX_BIN, verbosity=-1)
    base.update(kw)
    return Config(**base)


def make_gbdt(ds, cfg, **kw):
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.objective import create_objective
    return GBDT(cfg, ds, create_objective("binary", cfg), **kw)


def check_trees(trees, leaves):
    nl = [int(t.num_leaves) for t in trees]
    assert min(nl) > 1, "a tree did not split: leaves per tree %r" % nl
    if nl[-1] != leaves:
        say("  last tree stopped at %d < %d leaves: no further split met "
            "min_data_in_leaf / min_gain_to_split" % (nl[-1], leaves))
    return nl


# ---- phases (one chip) ----------------------------------------------------

def phase_device(ctx):
    import jax
    from lightgbm_tpu.plan import device_specs
    from lightgbm_tpu.utils.compile_cache import enable_compilation_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            "chip_smoke.py needs a TPU: jax found platform=%r (%s)"
            % (dev.platform, dev.device_kind))
    assert len(jax.devices()) == ctx["chips"], (
        "asked for %d chip(s), jax sees %d" % (ctx["chips"],
                                               len(jax.devices())))
    spec = device_specs.spec_for(dev.device_kind)   # unknown kind raises
    ctx["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    cache = enable_compilation_cache()
    say("  device %r, spec row %r, compile cache %s (%d entries at start)"
        % (ctx["device"], spec.kind, cache,
           len(os.listdir(cache)) if os.path.isdir(cache) else 0))


def _row_store(n_pad, num_bins, seed, integer_values=False):
    rng = np.random.RandomState(seed)
    rows = np.zeros((n_pad, 128), np.uint8)
    rows[:, :F] = rng.randint(0, num_bins, size=(n_pad, F)).astype(np.uint8)
    if integer_values:      # what core/quant.py stores: small integers
        grad = rng.randint(-127, 128, size=n_pad).astype(np.float32)
        hess = rng.randint(0, 256, size=n_pad).astype(np.float32)
    else:
        grad = rng.normal(size=n_pad).astype(np.float32)
        hess = rng.uniform(0.1, 1.0, size=n_pad).astype(np.float32)
    for off, col in ((VOFF, grad), (VOFF + 4, hess),
                     (VOFF + 8, np.arange(n_pad, dtype=np.int32))):
        rows[:, off:off + 4] = col.view(np.uint8).reshape(n_pad, 4)
    return rows


def _scal(num_bins, wb, wc, gcol, thr, hist_left):
    s = np.zeros(12 + num_bins // 32, np.int32)
    s[:12] = [wb, wc, gcol, thr, 1, 0, num_bins, 0, 0, hist_left, 0, 1]
    return s


def phase_kernels(ctx):
    """The guard tests/test_tpu_numerics.py used to be (round 4: a Mosaic
    miscompile zeroed 28% of the histogram mass with every CPU test green),
    at real widths, compiled, for every bucket of the fused split kernel."""
    import jax.numpy as jnp
    from lightgbm_tpu.core import histogram as H
    from lightgbm_tpu.core import partition as P
    n_pad = 32 * P.CHUNK
    plan = P.fused_bucket_plan(n_pad)
    assert len(plan) == 3, plan
    # one window per bucket: (begin, count), end <= n_pad - CHUNK
    windows = {(True, 1024): (1234, 700), (False, 1024): (4321, 9000),
               (False, 4096): (777, 40000)}
    kw = dict(num_features=F, voff=VOFF)
    for num_bins in (256, 64):
        rows = jnp.asarray(_row_store(n_pad, num_bins, seed=num_bins))
        bins, values = H.rows_split_xla(rows, F, VOFF)
        got = H.histogram_pallas_rows(rows, num_bins, jnp.int32(313),
                                      jnp.int32(100000), **kw)
        want = H.histogram_xla_masked(bins, values, num_bins, jnp.int32(313),
                                      jnp.int32(100000))
        dev = float(jnp.max(jnp.abs(got - want)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
        say("  bins=%d histogram_pallas_rows vs histogram_xla: max|d|=%.3g"
            % (num_bins, dev))
        for small, chunk, _ in plan:
            wb, wc = windows[(small, chunk)]
            for hist_left in (1, 0):
                scal = jnp.asarray(_scal(num_bins, wb, wc, 2, num_bins // 3,
                                         hist_left))
                g_rows, g_h, g_nl = P.partition_hist_pallas(
                    rows, scal, num_bins=num_bins, chunk=chunk, small=small,
                    **kw)
                w_rows, w_h, w_nl = P.partition_hist_xla(
                    rows, scal, num_bins=num_bins, **kw)
                assert int(g_nl[0, 0]) == int(w_nl), (int(g_nl[0, 0]),
                                                      int(w_nl))
                np.testing.assert_array_equal(np.asarray(g_rows),
                                              np.asarray(w_rows))
                g_hist = P.fold_hist(g_h, F, num_bins)
                dev = float(jnp.max(jnp.abs(g_hist - w_h)))
                np.testing.assert_allclose(np.asarray(g_hist),
                                           np.asarray(w_h),
                                           rtol=2e-3, atol=2e-3)
                say("  bins=%d fused split small=%s chunk=%d window=%d "
                    "hist_left=%d: rows equal, nl=%d equal, hist max|d|=%.3g"
                    % (num_bins, small, chunk, wc, hist_left, int(w_nl),
                       dev))

    _check_right_block_phases()
    _check_placement_windows()
    _check_row_state_pass(n_pad)

    # the two variants that are off by default (hist_precision=quantized,
    # tree_grow_mode=level) compile too, so they owe the chip the same check
    num_bins = 256
    rows_q = jnp.asarray(_row_store(n_pad, num_bins, seed=7,
                                    integer_values=True))
    rows_l = jnp.asarray(_row_store(n_pad, num_bins, seed=8))
    for small, chunk, _ in plan:
        wb, wc = windows[(small, chunk)]
        scal = jnp.asarray(_scal(num_bins, wb, wc, 5, 100, 1))
        g_rows, g_h, g_nl = P.partition_hist_pallas(
            rows_q, scal, num_bins=num_bins, chunk=chunk, small=small,
            quantized=True, **kw)
        w_rows, w_h, w_nl = P.partition_hist_xla(rows_q, scal,
                                                 num_bins=num_bins, **kw)
        assert int(g_nl[0, 0]) == int(w_nl)
        np.testing.assert_array_equal(np.asarray(g_rows), np.asarray(w_rows))
        np.testing.assert_array_equal(      # integer sums: exact
            np.asarray(P.fold_hist(g_h, F, num_bins, quantized=True)),
            np.asarray(w_h))
        # level-batched launch: three disjoint windows of this bucket class
        # (and one empty slot) in ONE launch vs sequential reference splits
        stride = 2 * wc + P.CHUNK
        wins = [(wb + i * stride, wc - 17 * i) for i in range(3)
                if wb + i * stride + wc <= n_pad - P.CHUNK] + [(0, 0)]
        scals = np.stack([_scal(num_bins, b, c, 3 + i, 90 + i, i % 2)
                          for i, (b, c) in enumerate(wins)])
        l_rows, l_h, l_nl = P.partition_hist_level_pallas(
            rows_l, jnp.asarray(scals), num_bins=num_bins, chunk=chunk,
            small=small, **kw)
        ref = rows_l
        for i, (b, c) in enumerate(wins):
            if c == 0:
                assert int(l_nl[i, 0]) == 0
                continue
            ref, w_h, w_nl = P.partition_hist_xla(
                ref, jnp.asarray(scals[i]), num_bins=num_bins, **kw)
            assert int(l_nl[i, 0]) == int(w_nl), (i, int(l_nl[i, 0]),
                                                  int(w_nl))
            np.testing.assert_allclose(
                np.asarray(P.fold_hist(l_h[i], F, num_bins)),
                np.asarray(w_h), rtol=2e-3, atol=2e-3)
        np.testing.assert_array_equal(np.asarray(l_rows), np.asarray(ref))
        say("  bins=256 small=%s chunk=%d: quantized split exact; level "
            "launch of %d windows equals sequential splits"
            % (small, chunk, len(wins)))


def _check_right_block_phases():
    """The split kernel's copy-back and finals at chosen right-block phases
    and sizes, compiled.  On the chip the flush rings' word view is a bitcast
    of the VMEM ref (row 4 i + k taken to be byte k of word i); interpret
    mode cannot store through one and runs the value-level bitcast, so
    tier-1's cases (tests/test_partition_blend.py) owe the chip the same
    comparison with ``partition_hist_xla``, every byte of the store."""
    from tests.test_partition_blend import (CHUNKS, PHASES, RIGHT_ROWS,
                                            check_right_block)
    for chunk in CHUNKS:
        for ph in PHASES:
            for nr in RIGHT_ROWS:
                for hist_left in (1, 0):
                    check_right_block(chunk, ph, nr, interpret=False,
                                      hist_left=hist_left)
        say("  chunk=%d: right blocks of %s rows copied back at phases %s, "
            "both histogram sides: rows, nl and histogram equal"
            % (chunk, RIGHT_ROWS, PHASES))


def _check_placement_windows():
    """The placement stage's block flushes and register-carried open tiles,
    compiled: windows whose chunks finish chosen numbers of one stream's
    tiles, long enough that both flush rings wrap, and lopsided windows (3%
    right, 3% left) with an unaligned head, at both chunk sizes
    (tests/test_partition_blend.py's cases since PR 40), every byte of the
    store against ``partition_hist_xla``."""
    from tests.test_partition_blend import (CHUNKS, WINDOW_CASES,
                                            check_window, lopsided,
                                            tile_patterns)
    for chunk, name, wb in WINDOW_CASES:
        for hist_left in (1, 0):
            check_window(chunk, wb, tile_patterns(chunk)[name],
                         interpret=False, hist_left=hist_left, seed=wb)
    for chunk in CHUNKS:
        for share_left in (0.03, 0.97):
            check_window(chunk, 2 * chunk + 19, lopsided(chunk, share_left),
                         interpret=False,
                         hist_left=1 if share_left < 0.5 else 0, seed=11)
    say("  %d windows a chunk at a time (%s) and lopsided ones at chunks "
        "%s, both histogram sides: rows, nl and histogram equal"
        % (len(WINDOW_CASES), ", ".join(sorted(tile_patterns(CHUNKS[0]))),
           CHUNKS))


def _check_row_state_pass(n_pad):
    """The carried store's hand-over pass (core/row_state.py) against its
    plain form: every byte of the store, for both carried objectives, the
    bagging hash inside Mosaic included."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.gbdt import _carried_fns
    from lightgbm_tpu.core import partition as P
    from lightgbm_tpu.core import row_state as RS
    from lightgbm_tpu.objective import create_objective
    n = n_pad - P.CHUNK
    rng = np.random.RandomState(9)
    host = _row_store(n_pad, 256, seed=9)
    cols = ((VOFF + 8, np.concatenate([rng.permutation(n),
                                       np.arange(n, n_pad)]).astype(np.int32)),
            (VOFF + 12, (rng.choice([-1.0, 1.0], n_pad)
                         * rng.choice([1.0, 3.0], n_pad)).astype(np.float32)),
            (VOFF + 16, rng.normal(size=n_pad).astype(np.float32)))
    for off, col in cols:
        host[:, off:off + 4] = col.view(np.uint8).reshape(n_pad, 4)
    rows = jnp.asarray(host)
    begin = np.concatenate([[0], np.sort(rng.choice(
        np.arange(1, n), LEAVES - 1, replace=False))]).astype(np.int32)
    wcount = np.diff(np.concatenate([begin, [n]])).astype(np.int32)
    slots = rng.permutation(LEAVES)          # leaves are not in window order
    begins, values = RS.leaf_windows(
        jnp.asarray(begin[slots]), jnp.asarray(wcount[slots]),
        jnp.asarray(rng.normal(scale=0.1, size=LEAVES).astype(np.float32)),
        jnp.int32(LEAVES), n)
    gh = np.zeros(128, bool)
    gh[VOFF:VOFF + 8] = True
    for objective, bag in (("binary", (0.8, 5)), ("regression", None)):
        _, grad_fn = _carried_fns(
            create_objective(objective, binary_config(objective=objective)),
            n - 300, bag, 3)
        got, got_g, got_h = jax.jit(lambda r: RS.row_state_pass(
            r, begins, values, grad_fn, jnp.int32(6), voff=VOFF))(rows)
        want, want_g, want_h = jax.jit(lambda r: RS.advance_row_state_xla(
            r, begins, values, grad_fn, jnp.int32(6), voff=VOFF, n=n))(rows)
        got, want = np.asarray(got), np.asarray(want)
        # score, order, aux, bins: the same bytes; gradients: the same
        # numbers up to what Mosaic's exp and XLA's may differ in
        np.testing.assert_array_equal(got[:, ~gh], want[:, ~gh])
        assert np.any(got[:, VOFF + 16:VOFF + 20] != host[:, VOFF + 16:VOFF + 20])
        g = [np.ascontiguousarray(m[:, VOFF:VOFF + 8]).view(np.float32)
             for m in (got, want)]
        np.testing.assert_allclose(g[0], g[1], rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose([float(got_g), float(got_h)],
                                   [float(want_g), float(want_h)], rtol=1e-5)
        say("  row_state_pass %s%s: store bytes equal the plain form's; "
            "%d of %d gradient words differ in a bit"
            % (objective, " bagged" if bag else "",
               int(np.sum(g[0].view(np.int32) != g[1].view(np.int32))),
               g[0].size))


def phase_train(ctx):
    """The fused k-iteration path at the full Higgs shape, as
    ``benchmarks/run.py`` and CLI task=train run it."""
    import jax.numpy as jnp
    from lightgbm_tpu.io.dataset import BinnedDataset
    n = ROWS_TRAIN
    t0 = time.perf_counter()
    X, y, Xt, yt = benchmark_table(n, ROWS_HELD_OUT, SEED)
    t1 = time.perf_counter()
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=MAX_BIN)
    t2 = time.perf_counter()
    del X
    booster = make_gbdt(ds, binary_config(num_iterations=ITERS))
    assert_compiled_pallas(booster.learner)
    booster.train_chunk(ITERS)
    booster.train_score.block_until_ready()
    t3 = time.perf_counter()
    assert not booster._fuse_failed, "training left the fused path"
    fused = next(iter(booster._fused_cache.values()))
    kernels = pallas_kernels_in(
        fused.lower(booster.train_score, (), jnp.int32(0)).as_text())
    assert kernels >= 4, (
        "the fused train program holds %d Pallas kernels; the root "
        "histogram and three split buckets make 4" % kernels)
    score = np.asarray(booster.train_score)[0, :n]
    assert np.isfinite(score).all(), "non-finite training scores"
    nl = check_trees(booster.models, LEAVES)
    held = booster.predict(Xt, raw_score=True)
    say("  trained %d rows x %d features, %d iterations, %d bins, %d leaves: "
        "fused path, %d Pallas kernels in the program; leaves per tree %r"
        % (n, F, ITERS, MAX_BIN, LEAVES, kernels, nl))
    say("  seconds: make data %.1f, bin %.1f, construct+compile+train "
        "%.1f; train logloss %.5f, held-out AUC %.5f (%d rows)"
        % (t1 - t0, t2 - t1, t3 - t2, logloss(y, score), auc(yt, held),
           len(yt)))
    ctx.update(booster=booster, Xt=Xt, yt=yt)


def phase_save_load_score(ctx):
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    booster, Xt, yt = ctx["booster"], ctx["Xt"], ctx["yt"]
    path = os.path.join(OUT, "higgs_model.txt")
    booster.save_model(path)
    in_memory = booster.predict(Xt)
    # the training booster holds the binned store and the scores: drop it
    del ctx["booster"], booster
    gc.collect()
    loaded = lgb.Booster(model_file=path)
    first = loaded.predict(Xt)
    compiled = obs.recompile.total()
    second = loaded.predict(Xt)
    assert obs.recompile.total() == compiled, (
        "a second identical predict recompiled")
    np.testing.assert_array_equal(first, second)
    np.testing.assert_array_equal(first, in_memory)
    assert np.isfinite(first).all()
    say("  saved %s (%d bytes), loaded, scored %d held-out rows through the "
        "fused predictor: equal to the in-memory booster, AUC %.5f, no "
        "recompile on the second predict"
        % (os.path.relpath(path, ROOT), os.path.getsize(path), len(Xt),
           auc(yt, first)))
    ctx["loaded"] = loaded


def categorical_table(n, n_test, seed):
    """The benchmark's table with its first two columns handed over as
    categories: column 0 cut into 40 levels at its quantiles and column 1 into
    3, each level's code drawn from a fixed shuffle, so that a code's order
    says nothing and only a set of codes can follow the label."""
    X, y, Xt, yt = benchmark_table(n, n_test, seed)
    rng = np.random.default_rng(seed)
    for column, levels in ((0, 40), (1, 3)):
        edges = np.quantile(X[:, column], np.linspace(0, 1, levels + 1)[1:-1])
        codes = rng.permutation(levels).astype(np.float32)
        for part in (X, Xt):
            part[:, column] = codes[np.searchsorted(edges, part[:, column])]
    return X, y, Xt, yt


def phase_categorical(ctx):
    """A categorical decision on the chip: two categorical columns (one
    searched many-vs-many, one a category against the rest) through the fused
    path, saved, loaded and scored, against the benchmark's plain walk."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.obs import categorical
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        import plain_categorical
    finally:
        sys.path.pop(0)
    n = ROWS_SMALL
    X, y, Xt, yt = categorical_table(n, n // 8, SEED + 2)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=MAX_BIN,
                                   categorical_feature=[0, 1])
    categorical.reset()
    booster = make_gbdt(ds, binary_config(num_iterations=ITERS_SMALL,
                                          categorical_feature=[0, 1]))
    assert_compiled_pallas(booster.learner)
    assert booster.learner.has_categorical
    booster.train_chunk(ITERS_SMALL)
    booster.train_score.block_until_ready()
    assert not booster._fuse_failed, "training left the fused path"
    check_trees(booster.models, LEAVES)
    counts = categorical.counts()
    many = counts["cat.splits"] - counts["cat.onehot_splits"]
    assert many > 0 and counts["cat.onehot_splits"] > 0, counts
    assert counts["cat.scan_steps"] == 32, counts
    path = os.path.join(OUT, "categorical_model.txt")
    booster.save_model(path)
    in_memory = booster.predict(Xt, raw_score=True)
    loaded = lgb.Booster(model_file=path)
    scores = loaded.predict(Xt, raw_score=True)
    np.testing.assert_array_equal(scores, in_memory)
    walked = plain_categorical.walk(loaded._booster.models, Xt)
    gap = float(np.max(np.abs(np.asarray(scores, np.float64) - walked)))
    assert gap <= 1e-5, "scores are %.3g from the plain walk" % gap
    say("  trained %d rows, %d trees on the fused path with 2 categorical "
        "columns: %d many-vs-many splits and %d of a category against the "
        "rest, scans of %d steps; saved, loaded, scored %d held-out rows: "
        "equal to the in-memory booster, %.3g from the plain walk of the "
        "loaded trees, AUC %.5f"
        % (n, ITERS_SMALL, many, counts["cat.onehot_splits"],
           counts["cat.scan_steps"], len(Xt), gap,
           auc(yt, scores)))


def phase_entry_points(ctx):
    """lightgbm_tpu.train (per-iteration path) and the CLI, in-process: the
    chip has one owner, so no ``python -m lightgbm_tpu`` child."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import cli, obs
    X, y, Xh, yh = benchmark_table(ROWS_SMALL, ROWS_SMALL // 8, SEED + 1)
    tele = os.path.join(OUT, "api_train.jsonl")
    params = dict(objective="binary", num_leaves=LEAVES, learning_rate=0.1,
                  max_bin=MAX_BIN, verbosity=-1, telemetry_out=tele)
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=ITERS_SMALL)
    assert_compiled_pallas(bst._booster.learner)
    spans = [e for e in obs.read_events(tele)
             if e["kind"] == "span" and e["name"] == "tree_build"]
    assert len(spans) == ITERS_SMALL, "tree_build spans: %d" % len(spans)
    nl = check_trees(bst._booster.models, LEAVES)
    a = auc(yh, bst.predict(Xh))
    assert a > AUC_FLOOR, a
    say("  lightgbm_tpu.train: %d rows x %d, %d rounds on the per-iteration "
        "path (%d tree_build spans), leaves %r, held-out AUC %.5f"
        % (len(X), F, ITERS_SMALL, len(spans), nl, a))
    ctx.update(small=(X, y, Xh, yh))

    def write_tsv(path, Xs, ys):
        with open(path, "w") as fh:
            for row, lab in zip(Xs, ys):
                fh.write("%g\t" % lab
                         + "\t".join("%.9g" % v for v in row) + "\n")

    train_f = os.path.join(OUT, "cli.train")
    test_f = os.path.join(OUT, "cli.test")
    model_f = os.path.join(OUT, "cli_model.txt")
    pred_f = os.path.join(OUT, "cli_pred.txt")
    cli_tele = os.path.join(OUT, "cli_train.jsonl")
    write_tsv(train_f, X[:ROWS_CLI], y[:ROWS_CLI])
    write_tsv(test_f, Xh[:4096], yh[:4096])
    rc = cli.main(["task=train", "data=" + train_f, "objective=binary",
                   "num_trees=%d" % ITERS_SMALL, "num_leaves=%d" % LEAVES,
                   "learning_rate=0.1", "max_bin=%d" % MAX_BIN,
                   "output_model=" + model_f, "telemetry_out=" + cli_tele,
                   "verbosity=-1"])
    assert rc == 0 and os.path.exists(model_f)
    chunks = [e for e in obs.read_events(cli_tele)
              if e["kind"] == "train_chunk"]
    assert chunks and all(e["fused"] for e in chunks), (
        "CLI task=train left the fused path: %r" % chunks)
    rc = cli.main(["task=predict", "data=" + test_f,
                   "input_model=" + model_f, "output_result=" + pred_f,
                   "verbosity=-1"])
    assert rc == 0
    cli_pred = np.loadtxt(pred_f)
    want = lgb.Booster(model_file=model_f).predict(Xh[:4096])
    np.testing.assert_allclose(cli_pred, want, rtol=1e-5, atol=1e-7)
    say("  CLI task=train on %d file-backed rows: %d fused chunk(s); "
        "task=predict equals Booster.predict, AUC %.5f"
        % (ROWS_CLI, len(chunks), auc(yh[:4096], cli_pred)))


def phase_agree_with_xla_learner(ctx):
    """Same data, seed and config through the compiled Pallas kernels and
    through the XLA learner every CPU test uses, both on the chip."""
    from lightgbm_tpu.io.dataset import BinnedDataset
    X, y, Xh, yh = ctx["small"]
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=MAX_BIN)
    res = {}
    for name in ("pallas", "xla"):
        booster = make_gbdt(ds, binary_config(num_iterations=ITERS_SMALL))
        if name == "xla":
            # 2^20 rows need no padding either way, so the flag alone
            # selects the learner (tests flip it the same way)
            assert booster.learner.padded_rows == 0
            booster.learner.use_pallas = False
        else:
            assert_compiled_pallas(booster.learner)
        t0 = time.perf_counter()
        booster.train_chunk(ITERS_SMALL)
        score = np.asarray(booster.train_score)[0, :len(y)]
        assert not booster._fuse_failed
        res[name] = (logloss(y, score),
                     auc(yh, booster.predict(Xh, raw_score=True)),
                     time.perf_counter() - t0)
        del booster
        gc.collect()
    (ll_p, auc_p, s_p), (ll_x, auc_x, s_x) = res["pallas"], res["xla"]
    say("  %d rows, %d iterations: Pallas learner logloss %.6f AUC %.5f "
        "(%.1f s); XLA learner logloss %.6f AUC %.5f (%.1f s)"
        % (len(y), ITERS_SMALL, ll_p, auc_p, s_p, ll_x, auc_x, s_x))
    assert abs(auc_p - auc_x) <= 0.002, (auc_p, auc_x)
    assert abs(ll_p - ll_x) <= 1e-3 * ll_x, (ll_p, ll_x)
    del ctx["small"]


def phase_contrib(ctx):
    import lightgbm_tpu as lgb
    X, y, _, _ = benchmark_table(ROWS_CONTRIB_TRAIN, 0, SEED + 2)
    # a SMALL model: the contrib program is O(depth^2) and its compile is
    # the cost here (a finding for the roadmap, not something this fixes)
    bst = lgb.train(dict(objective="binary", num_leaves=15, max_bin=MAX_BIN,
                         verbosity=-1), lgb.Dataset(X, label=y),
                    num_boost_round=4)
    Xq = X[:8192]
    t0 = time.perf_counter()
    phi = bst.predict(Xq, pred_contrib=True)
    dt = time.perf_counter() - t0
    assert phi.shape == (len(Xq), F + 1), phi.shape
    raw = np.zeros(len(Xq))
    for tree in bst._booster.models:
        raw += tree.predict(np.asarray(Xq, np.float32))   # f64 host walk
    np.testing.assert_allclose(phi.sum(axis=1), raw, rtol=1e-9, atol=1e-12)
    say("  pred_contrib on %d rows, 4 trees x 15 leaves: sum(phi) equals the "
        "f64 raw score (max|d|=%.3g); first call %.1f s (compile)"
        % (len(Xq), float(np.max(np.abs(phi.sum(axis=1) - raw))), dt))


def phase_serve(ctx):
    import lightgbm_tpu as lgb
    loaded, Xt = ctx["loaded"], ctx["Xt"]
    server = lgb.serve({"higgs": loaded})
    try:
        for n in (1, 200, 5000):
            got = server.predict("higgs", Xt[:n])
            want = loaded.predict(Xt[:n])
            if n >= 512:    # below that predict takes the f64 host path
                np.testing.assert_array_equal(got, want)
            else:
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
        stats = server.stats()
        assert stats["dropped"] == 0 and stats["failed"] == 0, stats
    finally:
        server.close(timeout=60)
    assert not server._thread.is_alive(), "server thread did not join"
    say("  served 1, 200 and 5000 rows: equal to predict; %d completed, "
        "0 dropped, dispatcher joined" % stats["completed"])


# ---- --chips 4: the cross-chip path and what it is compared with ----------

def phase_data_parallel(ctx):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.core.predict_fused import FusedPredictor
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.parallel import (DataParallelTreeLearner, default_mesh,
                                       sharded_predict)
    n = ROWS_4CHIP
    X, y, Xh, yh = benchmark_table(n, n // 8, SEED + 3)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=MAX_BIN)
    mesh = default_mesh(4)
    booster = make_gbdt(
        ds, binary_config(num_iterations=ITERS_SMALL, tree_learner="data"),
        mesh=mesh)
    learner = booster.learner
    assert type(learner) is DataParallelTreeLearner, type(learner)
    assert_compiled_pallas(learner)
    shards = learner.bins.addressable_shards
    assert len({s.device for s in shards}) == 4, shards
    assert all(s.data.shape[0] * 4 == learner.bins.shape[0]
               for s in shards), "a device holds more than its quarter"
    zeros = jnp.zeros((n,), jnp.float32)
    g, h, fm = learner._prep_train(zeros, zeros, None)
    text = learner._build_fn.lower(
        learner.bins, g, h, jnp.int32(n), fm, learner.feat,
        jnp.int32(0)).compile().as_text()
    collectives = {op: text.count(op) for op in
                   ("all-reduce", "reduce-scatter", "all-gather")}
    assert collectives["all-reduce"] + collectives["reduce-scatter"] > 0, (
        "no collective in the compiled data-parallel step")
    assert pallas_kernels_in(text) > 0, "no Pallas kernel in the step"
    t0 = time.perf_counter()
    booster.train_chunk(ITERS_SMALL)
    booster.train_score.block_until_ready()
    dt = time.perf_counter() - t0
    nl = check_trees(booster.models, LEAVES)
    auc_dp = auc(yh, booster.predict(Xh, raw_score=True))
    say("  tree_learner=data on %d chips: %d rows x %d, row store %r over "
        "devices %r, collectives in the step %r; %d iterations %.1f s, "
        "leaves %r, held-out AUC %.5f"
        % (len(shards), n, F, tuple(learner.bins.shape),
           sorted(s.device.id for s in shards), collectives, ITERS_SMALL, dt,
           nl, auc_dp))

    serial = make_gbdt(ds, binary_config(num_iterations=ITERS_SMALL))
    assert type(serial.learner).__name__ == "SerialTreeLearner"
    assert_compiled_pallas(serial.learner)
    t0 = time.perf_counter()
    serial.train_chunk(ITERS_SMALL)
    serial.train_score.block_until_ready()
    dt = time.perf_counter() - t0
    auc_s = auc(yh, serial.predict(Xh, raw_score=True))
    say("  control, serial learner on one of the chips: %.1f s, "
        "held-out AUC %.5f" % (dt, auc_s))
    assert abs(auc_dp - auc_s) <= 0.002, (auc_dp, auc_s)

    fp = FusedPredictor(booster.models)
    sharded = sharded_predict(fp.ens, Xh, mesh)
    np.testing.assert_array_equal(sharded, fp(Xh))
    say("  sharded_predict of %d rows over 4 chips equals the single-device "
        "scores" % len(Xh))


ONE_CHIP = (("device", phase_device), ("kernels vs reference", phase_kernels),
            ("train", phase_train),
            ("save / load / score", phase_save_load_score),
            ("categorical columns: train, save, load, score",
             phase_categorical),
            ("train, the two public entry points", phase_entry_points),
            ("agree with the plain learner", phase_agree_with_xla_learner),
            ("contrib", phase_contrib), ("serve", phase_serve))
FOUR_CHIPS = (("device", phase_device),
              ("data-parallel train, control, sharded predict",
               phase_data_parallel))


def run(phases, ctx):
    """Run ``phases`` in order; the first exception ends the run."""
    import jax
    for name, fn in phases:
        ctx["phase"] = name
        say("[%s]" % name)
        t0 = time.perf_counter()
        fn(ctx)
        assert_no_fallbacks()
        stats = jax.devices()[0].memory_stats() or {}
        say("  done in %.1f s; fallback counters empty; peak device "
            "memory so far %.2f GB"
            % (time.perf_counter() - t0,
               stats.get("peak_bytes_in_use", 0) / 1e9))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)
    ctx = {"chips": args.chips, "phase": "start"}
    try:
        run(FOUR_CHIPS if args.chips == 4 else ONE_CHIP, ctx)
    except BaseException as exc:   # report the phase, then fail
        traceback.print_exc()
        print(json.dumps({"ok": False, "phase": ctx["phase"],
                          "error": "%s: %s" % (type(exc).__name__, exc)}),
              flush=True)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": ctx["device"]}), flush=True)


if __name__ == "__main__":
    main()
