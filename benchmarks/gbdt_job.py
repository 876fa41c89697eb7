"""What every GBDT training job kind of the benchmark shares: data from the
seed, the program's counters, and the checks that decide ``correct``.

Only this file and the kinds import the program (``lightgbm_tpu``)."""
from __future__ import annotations

import time

import numpy as np

import datagen
import plain_reference

WALK_ROWS = 2048
# the program sums leaf values in f32 on the device, the walk in f64 here
WALK_ATOL = 1e-5

clock = time.perf_counter


def quiet():
    """The program's log lines off: standard output carries the benchmark's."""
    from lightgbm_tpu.utils.log import Log
    Log.reset_level(Log.level_from_verbosity(-1))


def tree_index(wl, key, warmup, unit):
    """The traffic file's tree count under ``key``, or None where it states
    none.  It has to be the ``warmup`` trees plus a whole number of units of
    ``unit`` trees, at least one: anything else is an error, not a rounding,
    since the point is that every commit runs the same trees."""
    if wl.get(key) is None:
        return None
    index = int(wl[key])
    if index <= warmup or (index - warmup) % unit:
        raise ValueError(
            "%s %d is not the %d warm-up trees plus a whole number (1 or "
            "more) of units of %d trees" % (key, index, warmup, unit))
    return index


def trace_first_tree(wl, warmup, unit):
    """The tree index at which a traced run of the traffic mix ``wl`` starts
    its traced units, or None for a mix that states none (it cannot be
    traced)."""
    return tree_index(wl, "trace_first_tree", warmup, unit)


def window_end_tree(wl, warmup, unit):
    """The tree count at which an untraced run of the traffic mix ``wl`` ends
    its window, or None for a mix that states none (its window goes by the
    clock)."""
    return tree_index(wl, "window_end_tree", warmup, unit)


# a window that ends at a tree count still ends once this many times
# ``--seconds`` have passed, at the last finished unit
CEILING = 3


def untraced_goes_on(job, tracer, seconds, trees):
    """Whether the stretch of a run after the warm-up goes on once the booster
    holds ``trees`` trees.  In a run that is not traced: up to the traffic
    file's ``window_end_tree``, or until ``CEILING`` x ``seconds`` have passed
    if that comes first (said on standard output); by the clock alone where
    the job has no such tree.  The tree at which such a window ends, its own
    or the ceiling's, is kept as ``job.window_ended_at`` for
    :func:`window_check`.
    In a traced run: up to the traffic file's ``trace_first_tree``."""
    if tracer is None:
        elapsed = clock() - job.t_start
        end = getattr(job, "window_end_tree", None)
        if end is None:
            return elapsed < seconds
        goes_on = trees < end
        if goes_on and elapsed >= CEILING * seconds:
            print("ceiling: window cut at tree %d, %.3f s after it opened "
                  "(%g x %g s); the traffic file's window ends at tree %d"
                  % (trees, elapsed, CEILING, seconds, end), flush=True)
            goes_on = False
        if not goes_on:
            job.window_ended_at = trees
        return goes_on
    if job.trace_first_tree is None:
        raise ValueError("a traced run needs trace_first_tree in the traffic "
                         "file")
    return trees < job.trace_first_tree


def make_data(cfg, seed, rehearse_rows=None):
    """Training and held-out rows of ``cfg`` from ``seed``: (X, y, Xh, yh).
    ``rehearse_rows`` shrinks both (CPU rehearsal only)."""
    rows, held = int(cfg["rows"]), int(cfg["heldout_rows"])
    if rehearse_rows:
        rows, held = int(rehearse_rows), max(int(rehearse_rows) // 4, WALK_ROWS)
    X, y = datagen.make(seed, rows + held, int(cfg["features"]),
                        cfg["generator"])
    return X[:rows], y[:rows], X[rows:], y[rows:]


def logloss(y, raw):
    raw = np.asarray(raw, np.float64)
    return float(np.mean(np.logaddexp(0.0, raw) - np.asarray(y, np.float64) * raw))


def fallbacks():
    """{degraded path: times it served} — every value must be 0."""
    from lightgbm_tpu import resilience
    from lightgbm_tpu.plan import cache as plan_cache
    counts = dict(resilience.fallback_counts())
    counts["plan_cache"] = plan_cache.fallback_count()
    return counts


def check_root_split(gbdt, dataset, y, params):
    """Guarantee: tree 0's root split is the plain NumPy best (or a near-tie)."""
    groups = dataset.feature_groups
    if any(len(g) != 1 for g in groups):
        return False, "bundled feature groups %r: the plain reference reads " \
                      "one bin-code column per feature" % (groups,)
    tree = gbdt.models[0]
    if tree.num_leaves <= 1:
        return False, "tree 0 did not split"
    gains = plain_reference.root_gains(
        dataset.binned, y, num_bins=int(params["max_bin"]) + 1,
        min_data_in_leaf=int(params["min_data_in_leaf"]),
        min_sum_hessian_in_leaf=float(params["min_sum_hessian_in_leaf"]))
    return plain_reference.root_split_agrees(
        gains, int(tree.split_feature_inner[0]), int(tree.threshold_in_bin[0]))


def check_walk(gbdt, Xh, trees):
    """Guarantee: the program's raw scores equal a plain walk of its trees."""
    X = Xh[:WALK_ROWS]
    got = np.asarray(gbdt.predict(X, raw_score=True, num_iteration=trees),
                     np.float64).reshape(-1)
    want = plain_reference.walk(gbdt.models[:trees], X)
    err = float(np.max(np.abs(got - want)))
    return err <= WALK_ATOL, ("plain walk of %d trees on %d held-out rows: "
                              "max |d| %.3g (allowed %.0e)"
                              % (trees, len(X), err, WALK_ATOL))


def heldout_auc(gbdt, Xh, yh, trees):
    raw = np.asarray(gbdt.predict(Xh, raw_score=True, num_iteration=trees),
                     np.float64).reshape(-1)
    return datagen.auc(yh, raw)


def train_scores(gbdt, score=None):
    """The training rows' raw scores on the host (padding rows cut off)."""
    score = gbdt.train_score if score is None else score
    return np.asarray(score)[0, :gbdt.num_data]


# ---- what a kind's Job calls once its window has ended ---------------------
# A job has: gbdt, dataset, y, Xh, yh, cfg, auc_trees, counters, t_start,
# t_end, window_trees, score_after_warmup.

def read_counters(job):
    """Copy the program's counters; call right after the window's last wait."""
    from lightgbm_tpu import obs
    job.counters["recompiles_in_window"] = float(obs.recompile.total())
    per_tree = obs.launches.per_tree()
    if per_tree is not None:
        job.counters["launches_per_tree"] = float(per_tree)


def end_to_end(job):
    rate = job.gbdt.num_data * job.window_trees / (job.t_end - job.t_start)
    auc = heldout_auc(job.gbdt, job.Xh, job.yh, job.auc_trees)
    print("held-out AUC of the first %d trees on %d rows: %.6f"
          % (job.auc_trees, len(job.yh), auc), flush=True)
    return {"train_row_trees_per_s": rate, "heldout_auc": auc}


def checks(job, must_stay_fused, skip=()):
    """[(guarantee, holds, what was found)] — the configuration's guarantees
    that a run can show.  ``must_stay_fused``: the kind drives the fused
    ``train_chunk`` path, and leaving it is a degraded path.  ``skip`` names
    the checks a kind replaces with its own: they are not computed."""
    counts = fallbacks()
    left = bool(must_stay_fused and job.gbdt._fuse_failed)
    n = job.counters["recompiles_in_window"]
    before = logloss(job.y, train_scores(job.gbdt, job.score_after_warmup))
    after = logloss(job.y, train_scores(job.gbdt))
    found = [
        ("no_degraded_path", lambda: (
            not any(counts.values()) and not left,
            "fallbacks %r, left the fused path: %r" % (counts, left))),
        ("no_recompile_in_window", lambda: (
            n == 0, "%d recompiles in the window" % n)),
        ("plain_root_split", lambda: check_root_split(
            job.gbdt, job.dataset, job.y, job.cfg["params"])),
        ("plain_walk", lambda: check_walk(job.gbdt, job.Xh, job.auc_trees)),
        ("training_loss_falls", lambda: (
            after < before,
            "training logloss %.5f after the warm-up, %.5f after %d trees"
            % (before, after, job.gbdt.iter_))),
    ]
    return [(name,) + check() for name, check in found if name not in skip]


def window_check(job):
    """[(guarantee, holds, what was found)] of an untraced window that ends at
    the traffic file's ``window_end_tree``, else [].  A window that the
    ceiling cut holds fewer, earlier and cheaper trees: its rate is not over
    the cell's trees, so the run is not correct."""
    ended = getattr(job, "window_ended_at", None)
    if ended is None:
        return []
    end = job.window_end_tree
    return [("window_reached_its_end", ended >= end,
             "the window ended at tree %d; the traffic file's window ends at "
             "tree %d" % (ended, end))]
