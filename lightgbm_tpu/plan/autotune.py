"""The empirical planner: microbenchmark candidate tilings, persist winners.

For each (shape-class, device_kind) the tuner:

1. enumerates **candidate plans** (:func:`candidate_plans`) — the analytic
   plan plus structured variations of the knobs PERF.md's
   "tried and rejected" table shows were hand-raced on v5e (bucket ladder
   shape: small kernel on/off, 1024- vs 4096-row chunks, mid-bucket
   bound; predict tree-block VMEM budget);
2. **measures** each candidate by running the REAL dispatches — a serial
   tree build with the candidate's ``bucket_plan`` pinned on the learner,
   and the blocked predict program at the candidate's tree-block G — with
   walls recorded into the compile-accounting machinery
   (:class:`~..obs.compile.CompileAccounting`): the first dispatch is a
   noted miss, repeats build the steady sample, and candidates are ranked
   on ``steady_p50_s`` so compiles and persistent-cache **warm loads
   never pollute the ranking** (obs/compile.py's whole reason to exist,
   per ROADMAP item 4);
3. **persists** the winner per shape-class into the atomic, versioned
   JSON plan cache (``plan/cache.py``) next to the XLA compilation cache.

Any candidate is numerics-safe: plans change dispatch shape only, and
every kernel variant is pinned bit-exact against the others — the tuner
races performance, never correctness.  Off-TPU the fused kernels run in
interpret mode (walls are mechanism-proof, not evidence; the race has
not been run on the chip).

Driven by ``tools/bench_autotune.py``; tested with an injected timer in
tests/test_plan.py (ranking logic is deterministic under synthetic
walls).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from . import cache as _cache
from . import planner


class Candidate(NamedTuple):
    name: str
    plan: planner.Plan


def candidate_plans(sc: planner.ShapeClass) -> Tuple[Candidate, ...]:
    """The race field for one shape class: analytic first (the incumbent
    every winner's margin is quoted against), then the bucket-ladder and
    predict-block variations that are valid for this row count."""
    from ..core.partition import CHUNK, SMALL_CHUNK, _ALIGN, _MID_MAX
    base = planner.analytic_plan(sc)
    out: List[Candidate] = [Candidate("analytic", base)]
    n = sc.n_rows
    small_max = SMALL_CHUNK - _ALIGN

    def add(name: str, **fields) -> None:
        plan = base._replace(provenance="tuned", **fields)
        try:
            planner.validate_plan(plan, n)
        except ValueError:
            return  # variant invalid for this shape: not a candidate
        if any(c.plan[:-1] == plan[:-1] for c in out):
            return  # collapsed onto an existing candidate at this n
        out.append(Candidate(name, plan))

    def sched(name: str, bucket_plan) -> None:
        bucket_plan = tuple(bucket_plan)
        add(name, bucket_plan=bucket_plan, level_ladder=bucket_plan)

    # ladder variants (round-7 knobs): one-size large pipeline (the
    # round-6 status quo), one-size 1024-chunk pipeline, small kernel
    # disabled, and a mid bucket stretched to 2x its hand-tuned bound
    sched("single-large", ((False, CHUNK, None),))
    sched("single-mid", ((False, SMALL_CHUNK, None),))
    if small_max < n:
        no_small = [e for e in base.bucket_plan if not e[0]]
        if no_small:
            sched("no-small", no_small)
    if 2 * _MID_MAX < n:
        sched("wide-mid", ((True, SMALL_CHUNK, small_max),
                           (False, SMALL_CHUNK, 2 * _MID_MAX),
                           (False, CHUNK, None)))
    # round 22: quantized-gradient histograms halve the factored
    # accumulator per group, so the same VMEM gate admits doubled groups
    # and a wider mid/level window — raced as candidates, never assumed
    if getattr(sc, "quantized", False):
        add("quant-2xgroups", hist_groups=int(base.hist_groups) * 2)
        if 4 * _MID_MAX < n:
            sched("quant-wide-level", ((True, SMALL_CHUNK, small_max),
                                       (False, SMALL_CHUNK, 4 * _MID_MAX),
                                       (False, CHUNK, None)))
    # predict tree-block VMEM budget: half and double the 1 MiB default
    pb = int(base.predict_block_vmem_bytes)
    add("predict-halfvmem", predict_block_vmem_bytes=pb // 2)
    add("predict-2xvmem", predict_block_vmem_bytes=pb * 2)
    return tuple(out)


def _default_timer(fn) -> float:
    """Wall-seconds of one completed dispatch (device work drained)."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def _steady_of(acct, fn: str, bucket: str) -> Optional[Dict[str, Any]]:
    snap = acct.snapshot()
    return (snap.get("keys") or {}).get("%s|%s" % (fn, bucket))


class TuneDriver:
    """Owns the synthetic workload of ONE shape class and measures
    candidates against it.  ``timer`` is injectable for tests."""

    def __init__(self, sc: planner.ShapeClass, *, reps: int = 4,
                 interpret: Optional[bool] = None, timer=None,
                 trees: int = 8, seed: int = 11) -> None:
        import jax
        self.sc = sc
        self.reps = max(2, int(reps))
        self.timer = timer or _default_timer
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = bool(interpret)
        self.trees = int(trees)
        self.seed = int(seed)
        self._fixture = None
        # one accountant per driver: keys are (site, candidate) so every
        # candidate's steady median lives beside its compile cost in the
        # artifact's candidate table
        from ..obs.compile import CompileAccounting
        self.acct = CompileAccounting()

    # ---- fixture: dataset + learner + a small trained model ----

    def _fixture_parts(self):
        if self._fixture is not None:
            return self._fixture
        import numpy as np

        from ..boosting.gbdt import GBDT
        from ..config import Config
        from ..core.partition import CHUNK
        from ..io.dataset import BinnedDataset
        from ..objective import create_objective

        sc = self.sc
        n = max(CHUNK, -(-sc.n_rows // CHUNK) * CHUNK)
        f = max(2, sc.num_features)
        max_bin = max(3, min(sc.num_bins - 1, 255))
        rng = np.random.RandomState(self.seed)
        X = rng.normal(size=(n, f)).astype(np.float32)
        y = (X[:, 0] * 1.5 + np.sin(X[:, 1])
             + rng.normal(scale=0.1, size=n))
        ds = BinnedDataset.from_matrix(X, label=y, max_bin=max_bin)
        cfg = Config(objective="regression", num_leaves=15,
                     num_iterations=self.trees, min_data_in_leaf=2,
                     verbosity=-1)
        booster = GBDT(cfg, ds, create_objective("regression", cfg))
        grad = rng.normal(size=n).astype(np.float32)
        hess = np.abs(rng.normal(size=n)).astype(np.float32) + 0.1
        self._fixture = (booster, grad, hess, X)
        return self._fixture

    def _trained_trees(self):
        booster, _, _, _ = self._fixture_parts()
        if booster.num_trees == 0:
            booster.train()
        return list(booster.models)

    # ---- per-candidate measurements ----

    def measure_train(self, cand: Candidate) -> Optional[Dict[str, Any]]:
        """One serial tree build per rep with the candidate's
        ``bucket_plan`` pinned on the learner — the composite the bucket
        schedule actually serves.  Key = ("train_tree", name)."""
        import jax.numpy as jnp
        booster, grad, hess, _ = self._fixture_parts()
        learner = booster.learner
        prev = (learner.bucket_plan, learner.use_pallas,
                learner.pallas_interpret)
        learner.bucket_plan = tuple(cand.plan.bucket_plan)
        learner.use_pallas = True
        learner.pallas_interpret = self.interpret
        g = jnp.asarray(grad)
        h = jnp.asarray(hess)
        n = int(grad.shape[0])
        try:
            for rep in range(self.reps + 1):
                wall = self.timer(lambda: learner.train(g, h, n))
                self.acct.note(None, "train_tree", cand.name, wall,
                               1 if rep == 0 else 0)
        finally:
            (learner.bucket_plan, learner.use_pallas,
             learner.pallas_interpret) = prev
        return _steady_of(self.acct, "train_tree", cand.name)

    def measure_predict(self, cand: Candidate) -> Optional[Dict[str, Any]]:
        """The blocked predict program at the candidate's tree-block G
        (pure XLA — measurable on any backend).  Key =
        ("predict_block", name)."""
        import jax.numpy as jnp

        from ..core.predict_fused import (predict_blocked, shape_bucket,
                                          stack_ensemble_blocked)
        trees = self._trained_trees()
        if not trees:
            return None
        _, _, _, X = self._fixture_parts()
        host_m = max(max(t.num_leaves - 1, 1) for t in trees)
        host_l = max(t.num_leaves for t in trees)
        g = planner.tree_block_for(cand.plan, len(trees), host_m, host_l)
        ens = stack_ensemble_blocked(trees, g)
        bucket = shape_bucket(min(len(X), cand.plan.predict_buckets[0]))
        rows = jnp.asarray(X[:bucket])
        for rep in range(self.reps + 1):
            wall = self.timer(lambda: predict_blocked(ens, rows))
            self.acct.note(None, "predict_block", cand.name, wall,
                           1 if rep == 0 else 0)
        return _steady_of(self.acct, "predict_block", cand.name)


def tune_shape(sc: planner.ShapeClass, *, reps: int = 4,
               interpret: Optional[bool] = None, timer=None,
               driver: Optional[TuneDriver] = None) -> Dict[str, Any]:
    """Race every candidate for one shape class; returns the candidate
    table + the merged winner (best bucket ladder x best predict block —
    the two site families are independent dispatches, so their winners
    compose)."""
    driver = driver or TuneDriver(sc, reps=reps, interpret=interpret,
                                  timer=timer)
    cands = candidate_plans(sc)
    table: List[Dict[str, Any]] = []
    for cand in cands:
        is_pred = cand.name.startswith("predict-")
        row: Dict[str, Any] = {
            "name": cand.name,
            "plan": planner.plan_to_dict(cand.plan),
        }
        if not is_pred:
            st = driver.measure_train(cand)
            if st:
                row["train_steady_p50_s"] = st.get("steady_p50_s")
                row["train_compile_s"] = st.get("compile_s")
        if is_pred or cand.name == "analytic":
            st = driver.measure_predict(cand)
            if st:
                row["predict_steady_p50_s"] = st.get("steady_p50_s")
                row["predict_compile_s"] = st.get("compile_s")
        table.append(row)

    def best(metric: str, rows) -> Optional[Dict[str, Any]]:
        scored = [r for r in rows if r.get(metric) is not None]
        return min(scored, key=lambda r: r[metric]) if scored else None

    base = next(r for r in table if r["name"] == "analytic")
    tb = best("train_steady_p50_s", table)
    pb = best("predict_steady_p50_s", table)
    winner = planner.plan_from_dict(base["plan"])
    parts = []
    margin: Dict[str, Any] = {}
    if tb is not None and tb["name"] != "analytic":
        w = planner.plan_from_dict(tb["plan"])
        winner = winner._replace(bucket_plan=w.bucket_plan,
                                 level_ladder=w.level_ladder)
        parts.append(tb["name"])
    if tb is not None and base.get("train_steady_p50_s"):
        margin["train"] = (float(base["train_steady_p50_s"])
                           / max(float(tb["train_steady_p50_s"]), 1e-12))
    if pb is not None and pb["name"] != "analytic":
        w = planner.plan_from_dict(pb["plan"])
        winner = winner._replace(
            predict_block_vmem_bytes=w.predict_block_vmem_bytes)
        parts.append(pb["name"])
    if pb is not None and base.get("predict_steady_p50_s"):
        margin["predict"] = (float(base["predict_steady_p50_s"])
                             / max(float(pb["predict_steady_p50_s"]), 1e-12))
    winner = winner._replace(provenance="tuned")
    planner.validate_plan(winner, sc.n_rows)
    return {
        "key": planner.plan_key(sc),
        "shape": list(sc),
        "candidates": table,
        "winner": {"name": "+".join(parts) or "analytic",
                   "plan": planner.plan_to_dict(winner)},
        "margin": margin,
    }


def run_sweep(shapes, *, cache_path: Optional[str] = None, reps: int = 4,
              interpret: Optional[bool] = None, timer=None,
              device_kind: Optional[str] = None,
              fixture_rows: Optional[int] = None, trees: int = 8,
              progress=None) -> Dict[str, Any]:
    """Tune every shape class, persist the winners, return the report
    ``tools/bench_autotune.py`` prints and writes to its ``--json`` path.

    ``fixture_rows`` caps the synthetic workload's row count (off-TPU
    smoke runs) while the persisted entry stays keyed by the REQUESTED
    class — a capped fixture must not pollute a real class's key.
    ``progress`` is an optional ``fn(sc, res)`` callback per shape."""
    from . import device_specs
    if device_kind is None:
        device_kind = device_specs.current_device_kind()
    cache = _cache.PlanCache(device_kind=str(device_kind), path=cache_path)
    results = []
    for sc in shapes:
        sc = sc._replace(device_kind=str(device_kind))
        fx = sc.n_rows if fixture_rows is None else min(sc.n_rows,
                                                        int(fixture_rows))
        driver = TuneDriver(sc._replace(n_rows=fx), reps=reps,
                            interpret=interpret, timer=timer, trees=trees)
        res = tune_shape(sc._replace(n_rows=fx), driver=driver)
        res["key"] = planner.plan_key(sc)
        res["fixture_rows"] = fx
        cache.put(sc, planner.plan_from_dict(res["winner"]["plan"]),
                  metrics=res["margin"])
        results.append(res)
        if progress is not None:
            progress(sc, res)
    path = cache.save(cache_path) if results else None
    return {"device_kind": str(device_kind), "cache": path,
            "shapes": results}
