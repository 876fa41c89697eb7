"""Persisted plan cache: tuned winners, versioned + atomic + fail-safe.

The autotuner (``plan/autotune.py``) microbenchmarks candidate tilings
once per (shape-class, device_kind) and persists the winners here — a
single JSON document living in the compilation cache directory
(``utils/compile_cache.py``), written through the
same retry/fsync/rename discipline as every other artifact
(``utils.file_io.atomic_write``).

Failure contract (acceptance-pinned): a corrupt, stale, or
version-mismatched cache NEVER degrades a run — it degrades to analytic
plans with ONE process-wide warning and an always-on
``plan_cache_fallbacks`` counter (same always-on discipline as
``resilience.note_fallback`` / the recompile gauge: one int add, live
whether or not telemetry is).  ``tools/fault_injection.py``'s
``plan-cache`` scenario doctors the file and pins the whole chain:
fallback -> counter -> bit-exact run.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, Optional

from ..utils.compile_cache import cache_dir
from . import planner

CACHE_VERSION = 1

_lock = threading.Lock()
_fallbacks = 0
_warned = False


def _note_fallback(reason: str, path: str) -> None:
    """Count (always-on) + warn ONCE per process + telemetry breadcrumb."""
    global _fallbacks, _warned
    with _lock:
        _fallbacks += 1
        first = not _warned
        _warned = True
    if first:
        from ..utils.log import Log
        Log.warning("plan cache %s unusable (%s); falling back to analytic "
                    "plans — fix the path, or regenerate the cache with "
                    "tools/bench_autotune.py", path, reason)
    try:
        from ..obs import active as _active
        tele = _active()
        if tele is not None:
            tele.counter("plan_cache_fallbacks").inc()
            tele.event("plan_fallback", path=str(path), reason=str(reason))
    except Exception:  # noqa: BLE001 - the counter must never fail a run
        pass


def fallback_count() -> int:
    """Always-on process counter: how many cache loads/lookups degraded
    to analytic plans (exposed on /metrics next to the resilience
    counters; perf_gate budgets it at 0 for steady-state claims)."""
    with _lock:
        return _fallbacks


def reset_fallbacks() -> None:
    """Test hook (mirrors resilience.reset_fallbacks)."""
    global _fallbacks, _warned
    with _lock:
        _fallbacks = 0
        _warned = False


def default_cache_path() -> str:
    """The plan cache's home: inside the compilation cache directory
    (``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed in-checkout
    one — same rule as ``utils.compile_cache``)."""
    return os.path.join(cache_dir(), "plan_cache.json")


class PlanCache:
    """Tuned plans per shape-class key, plus the autotuner's metrics."""

    def __init__(self, device_kind: str = "",
                 path: Optional[str] = None) -> None:
        self.device_kind = str(device_kind)
        self.path = path
        # key -> {"plan": dict, "metrics": dict}
        self.entries: Dict[str, Dict[str, Any]] = {}

    def put(self, sc: planner.ShapeClass, plan: planner.Plan,
            metrics: Optional[Dict[str, Any]] = None) -> str:
        key = planner.plan_key(sc)
        self.entries[key] = {
            "plan": planner.plan_to_dict(
                plan._replace(provenance="tuned")),
            "metrics": dict(metrics or {}),
            "shape": list(sc),
        }
        return key

    def lookup(self, sc: planner.ShapeClass) -> Optional[planner.Plan]:
        """The tuned plan of ``sc``'s class, VALIDATED — an entry that no
        longer parses or fails the dispatch-shape gate counts as a
        fallback (stale schema drift must not reach the kernels)."""
        ent = self.entries.get(planner.plan_key(sc))
        if ent is None:
            return None
        try:
            plan = planner.plan_from_dict(ent["plan"])
            plan = plan._replace(provenance="tuned")
            planner.validate_plan(plan, sc.n_rows)
        except Exception as exc:  # noqa: BLE001 - degrade, never raise
            _note_fallback("invalid tuned entry %s: %s"
                           % (planner.plan_key(sc), exc),
                           self.path or "<memory>")
            return None
        return plan

    def to_doc(self) -> Dict[str, Any]:
        return {
            "version": CACHE_VERSION,
            "plan_schema": planner.PLAN_SCHEMA_VERSION,
            "device_kind": self.device_kind,
            "entries": self.entries,
        }

    def save(self, path: Optional[str] = None) -> str:
        """Atomic write (tmp + fsync + rename, bounded IO retry) so a
        concurrent reader never sees a torn cache."""
        from ..utils.file_io import atomic_write
        path = path or self.path or default_cache_path()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        atomic_write(path, (json.dumps(self.to_doc(), indent=1,
                                       sort_keys=True) + "\n").encode())
        self.path = path
        return path


def load_cache(path: str,
               device_kind: Optional[str] = None) -> Optional[PlanCache]:
    """Load + validate a persisted cache; ``None`` (analytic mode) on any
    defect — missing is silent (the documented no-cache default), corrupt
    / version-mismatched / wrong-device is a counted, warned-once
    fallback."""
    if not os.path.exists(path):
        return None
    try:
        with open(path, "r") as fh:
            doc = json.load(fh)
    except Exception as exc:  # noqa: BLE001
        _note_fallback("unreadable: %s" % exc, path)
        return None
    if not isinstance(doc, dict):
        _note_fallback("not a JSON object", path)
        return None
    if int(doc.get("version", -1)) != CACHE_VERSION:
        _note_fallback("version %r != %d" % (doc.get("version"),
                                             CACHE_VERSION), path)
        return None
    if int(doc.get("plan_schema", -1)) != planner.PLAN_SCHEMA_VERSION:
        _note_fallback("plan schema %r != %d"
                       % (doc.get("plan_schema"),
                          planner.PLAN_SCHEMA_VERSION), path)
        return None
    if device_kind is None:
        from . import device_specs
        device_kind = device_specs.current_device_kind()
    cached_kind = str(doc.get("device_kind", ""))
    if cached_kind and cached_kind != str(device_kind):
        # a cache tuned on another device is STALE here: its timings do
        # not transfer; analytic is the honest choice
        _note_fallback("tuned for device_kind %r, running on %r"
                       % (cached_kind, device_kind), path)
        return None
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        _note_fallback("entries block missing", path)
        return None
    cache = PlanCache(device_kind=cached_kind, path=path)
    cache.entries = entries
    return cache
