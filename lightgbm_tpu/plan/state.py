"""Process-global plan engagement — the ONE entry point callers use.

``resolve()`` is consumed by every planning site: the serial tree learner
(``bucket_plan`` + level ladder, which ``gbdt.py``'s fused-scan paths
inherit through the learner), the histogram layout chooser, the fused
predictor (tree-block G), and the serving registry's warmup.  Resolution
precedence:

1. a **pinned** plan (:func:`pinned` context manager / :func:`pin`) —
   tests and the autotuner's candidate sweeps;
2. a **tuned** cache entry (:func:`configure` engages a persisted
   ``plan/cache.py`` document; the CLI/engine do this from the
   ``plan_cache`` param or the default location next to the XLA cache);
3. the **analytic** plan — byte-equal to the historical constants, always
   available, never fails.

Every resolution can be stamped into the active telemetry run
(:func:`stamp`): a ``kind="plan"`` event per (site, key) plus a
``tele.plan_stamps`` dict the summary renders as the "plan" block, so a
run's summary records which plan it ran.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional

from . import cache as _cache
from . import planner

_lock = threading.Lock()
_state: Dict[str, Any] = {"cache": None, "path": None, "pinned": None,
                          "explicit": False}


def configure(path: Optional[str] = None, *,
              discover: bool = True) -> Optional[_cache.PlanCache]:
    """Engage a persisted plan cache for the process.

    An explicit ``path`` is authoritative: a missing file there is a
    counted fallback (the operator asked for a cache that isn't usable),
    and the engagement survives later default-discovery probes from
    entry points.  ``path=None`` with ``discover`` probes the default
    location (next to the XLA compilation cache) — a missing file is the
    documented analytic default, silent — and NEVER disengages a cache
    an explicit :func:`configure` call installed.  An unusable file
    warns once and counts (``plan/cache.py``).  Returns the engaged
    cache or ``None``."""
    if path is None:
        if not discover:
            return None
        with _lock:
            if _state["explicit"] and _state["cache"] is not None:
                return _state["cache"]
        default = _cache.default_cache_path()
        loaded = _cache.load_cache(default)
        with _lock:
            if _state["explicit"] and _state["cache"] is not None:
                return _state["cache"]
            _state["cache"] = loaded
            _state["path"] = default if loaded is not None else None
            _state["explicit"] = False
        return loaded
    path = str(path)
    import os
    if not os.path.exists(path):
        _cache._note_fallback("explicitly requested cache is missing",
                              path)
        loaded = None
    else:
        loaded = _cache.load_cache(path)
    with _lock:
        _state["cache"] = loaded
        _state["path"] = path if loaded is not None else None
        _state["explicit"] = loaded is not None
    return loaded


def configure_from_config(config) -> Optional[_cache.PlanCache]:
    """Param-driven engagement (engine.train / engine.serve / CLI): an
    explicit ``plan_cache`` path is loaded (and its absence is loud via
    the fallback path), otherwise the default location is probed —
    without disturbing a cache the user engaged via
    :func:`lightgbm_tpu.plan.configure`."""
    path = str(getattr(config, "plan_cache", "") or "")
    return configure(path or None, discover=True)


def active_cache() -> Optional[_cache.PlanCache]:
    with _lock:
        return _state["cache"]


def configured_path() -> Optional[str]:
    with _lock:
        return _state["path"]


def reset() -> None:
    """Test hook: drop the engaged cache and any pin."""
    with _lock:
        _state["cache"] = None
        _state["path"] = None
        _state["pinned"] = None
        _state["explicit"] = False


def pin(plan: Optional[planner.Plan]) -> None:
    """Pin one plan for every subsequent resolution (provenance forced to
    ``"pinned"``); ``None`` unpins.  Validated on the way in — a pin is a
    test/tuner instrument and must fail loudly, not at dispatch."""
    if plan is not None:
        plan = plan._replace(provenance="pinned")
        planner.validate_plan(plan)
    with _lock:
        _state["pinned"] = plan


@contextlib.contextmanager
def pinned(plan: planner.Plan):
    """Scoped :func:`pin` (the autotuner wraps each candidate in one)."""
    prev = _state["pinned"]
    pin(plan)
    try:
        yield
    finally:
        with _lock:
            _state["pinned"] = prev


def resolve(n_rows: int, num_features: int, num_bins: int, *,
            bpc: int = 1, packed: bool = False, num_class: int = 1,
            device_kind: Optional[str] = None,
            quantized: bool = False) -> planner.Plan:
    """The planner entry point: pinned > tuned (engaged cache, validated)
    > analytic.  Never raises, never returns None."""
    sc = planner.shape_class(n_rows, num_features, num_bins, bpc=bpc,
                             packed=packed, num_class=num_class,
                             device_kind=device_kind, quantized=quantized)
    with _lock:
        pinned_plan = _state["pinned"]
        cache = _state["cache"]
    if pinned_plan is not None:
        return pinned_plan
    if cache is not None:
        tuned = cache.lookup(sc)
        if tuned is not None:
            return tuned
    return analytic(sc)


def analytic(sc: planner.ShapeClass) -> planner.Plan:
    return planner.analytic_plan(sc)


# ---- site overrides consulted by code that predates the Plan object ----

def hist_layout_override(num_features: int, num_bins: int) -> Optional[bool]:
    """Factored-vs-classic override for ``histogram._use_factored``: only
    a PINNED plan may flip the layout (engage-time decision — the layout
    is baked into compiled programs, so it must not drift mid-process
    under a cache swap).  ``None`` = analytic choice."""
    with _lock:
        pinned_plan = _state["pinned"]
    if pinned_plan is None:
        return None
    del num_features, num_bins  # one pin governs the process
    return bool(pinned_plan.hist_factored)


def predict_block_vmem() -> Optional[int]:
    """Tree-block VMEM budget override for ``predict_fused.tree_block``:
    a pinned plan wins; else the engaged cache's tuned budget — but ONLY
    when every cache entry agrees on it.  ``tree_block`` is called with
    a model shape, not a data shape-class, so a per-class budget cannot
    be attributed here; with disagreeing tuned budgets the honest choice
    is the analytic default, never the lexicographically-first entry's."""
    with _lock:
        pinned_plan = _state["pinned"]
        cache = _state["cache"]
    if pinned_plan is not None:
        return int(pinned_plan.predict_block_vmem_bytes)
    if cache is not None:
        vals = set()
        for ent in cache.entries.values():
            try:
                v = int(ent["plan"]["predict_block_vmem_bytes"])
            except Exception:  # noqa: BLE001 - lookup() polices entries
                continue
            if v > 0:
                vals.add(v)
        if len(vals) == 1:
            return vals.pop()
    return None


def current_provenance() -> str:
    """What a resolution WOULD report right now (for sites that only
    need the stamp, e.g. serving warmup)."""
    with _lock:
        if _state["pinned"] is not None:
            return "pinned"
        if _state["cache"] is not None and _state["cache"].entries:
            return "tuned"
    return "analytic"


# ---- provenance stamping (telemetry) ----

def stamp(tele, site: str, provenance: str,
          key: Optional[str] = None, **fields: Any) -> None:
    """Record which plan a site dispatched under: one ``kind="plan"``
    event per (site, key, provenance) per run plus the ``plan_stamps``
    dict ``obs/report.py`` folds into the summary.  Callers gate on
    ``tele is not None`` (zero-overhead-off contract)."""
    if tele is None:
        return
    provenance = (str(provenance) if provenance in planner.PROVENANCES
                  else "analytic")
    stamps = getattr(tele, "plan_stamps", None)
    if stamps is None:
        with _lock:
            stamps = getattr(tele, "plan_stamps", None)
            if stamps is None:
                stamps = tele.plan_stamps = {}
    tag = (str(site), str(key or ""), provenance)
    entry = stamps.get(site)
    if entry is not None and entry.get("_tag") == tag:
        return
    stamps[site] = {
        "_tag": tag,
        "provenance": provenance,
        "key": key,
        **{k: v for k, v in fields.items()},
    }
    tele.event("plan", site=str(site), provenance=provenance,
               key=str(key or ""), **fields)
