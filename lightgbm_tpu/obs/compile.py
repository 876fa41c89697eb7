"""Compile accounting: wall-seconds per (function, shape-bucket) miss.

``obs/recompile.py`` counts jit cache misses; this module prices them.
Every dispatch site that reports misses also knows its host dispatch wall,
and the difference between a miss-bearing dispatch and the same key's
steady-state dispatch wall IS the compile cost — no profiler needed, no
extra sync.  Three things fall out of that subtraction:

- ``compile_seconds_total`` becomes a live gauge (and a summary section):
  how much of a run's wall clock went to XLA/Mosaic compilation, per
  (function, shape-bucket) key — the empirical substrate the kernel
  planner's autotuner ranks candidate tilings with (ROADMAP item 4).
- **Persistent-cache warm loads** are distinguished from true compiles:
  the CLI keeps the XLA compilation cache on disk (``cli.py
  enable_compilation_cache``), so a repeat invocation's "miss" only pays
  executable deserialization — its excess wall over steady state is tiny.
  A miss whose excess is at or under ``warm_load_max_s`` counts as a warm
  load, not a compile (the autotuner must not rank a tiling by its
  deserialization time).
- Per-key **steady-state dispatch walls** ride along (`steady_p50_s`),
  so one artifact carries both the compile cost AND the amortized rate a
  tiling would be ranked on.

Attribution protocol: a miss-bearing dispatch is held PENDING until its
key sees a clean (miss-free) dispatch; the pending wall minus the steady
median is the compile estimate.  Keys that never reach steady state (the
run died, or the shape was dispatched once) resolve at snapshot time with
the full dispatch wall as an upper bound and ``resolved: false``.

What jax itself measured is kept beside that estimate, always on (the
listener at the end of this file): every jit's trace, lowering, backend
compile and persistent-cache retrieval become in-memory spans ``jax.trace``,
``jax.lower``, ``jax.backend_compile`` and ``jax.cache_load``
(``obs.spans.totals()``), so a warm start's cache loads and a cold start's
compiles are read, not guessed from dispatch walls.

Run-owned like the rest of the plane: the accountant lives on the active
:class:`~.registry.Telemetry` (``tele.compile_acct``), every site gates on
``obs.active() is None`` first, and a telemetry-off run constructs nothing
and notes nothing (spy-pinned in tests/test_obs_forensics.py).  Each
miss also emits a ``kind="compile"`` JSONL event so
``tools/obs_report.py`` can rebuild the section for a died run.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, Optional

import jax.monitoring

from . import spans as _spans

# a miss whose excess wall over the steady median is at or under this is a
# persistent-cache warm load (executable deserialization), not a compile
WARM_LOAD_MAX_S = 0.05
# steady-state dispatch walls kept per key for the median estimate
STEADY_SAMPLE_CAP = 128


def _median(vals) -> float:
    s = sorted(vals)
    n = len(s)
    if n % 2:
        return float(s[n // 2])
    return float(s[n // 2 - 1] + s[n // 2]) / 2.0


class _KeyState:
    __slots__ = ("steady", "pending", "compiles", "warm_loads",
                 "compile_s", "first_dispatch_s")

    def __init__(self) -> None:
        # recent clean dispatch walls (median = the steady estimate)
        self.steady: "deque" = deque(maxlen=STEADY_SAMPLE_CAP)
        # miss-bearing dispatch walls awaiting a steady baseline: (wall, n)
        self.pending: list = []
        self.compiles = 0
        self.warm_loads = 0
        self.compile_s = 0.0
        self.first_dispatch_s: Optional[float] = None


class CompileAccounting:
    """Per-(function, shape-bucket) compile wall-seconds for one run."""

    def __init__(self, warm_load_max_s: float = WARM_LOAD_MAX_S) -> None:
        self.warm_load_max_s = float(warm_load_max_s)
        self._keys: Dict[tuple, _KeyState] = {}
        self._lock = threading.Lock()

    def note(self, tele, fn: str, bucket, dispatch_s: float,
             misses: int) -> None:
        """Record one dispatch of ``(fn, bucket)``: its host wall and how
        many jit cache misses it carried (0 = clean/steady).  Called at
        dispatch granularity from sites that are already telemetry-gated,
        never per row."""
        key = (str(fn), str(bucket))
        dispatch_s = float(dispatch_s)
        resolved = []
        with self._lock:
            st = self._keys.get(key)
            if st is None:
                st = self._keys[key] = _KeyState()
            if st.first_dispatch_s is None:
                st.first_dispatch_s = dispatch_s
            if misses > 0:
                st.pending.append((dispatch_s, int(misses)))
            else:
                st.steady.append(dispatch_s)
                if st.pending:
                    resolved = self._resolve_locked(st)
        if misses > 0 and tele is not None:
            # the JSONL breadcrumb a died run is recovered from: the raw
            # dispatch wall (recovery cannot subtract a steady state that
            # may never have existed)
            tele.counter("compiles_noted").inc(int(misses))
            tele.event("compile", fn=str(fn), bucket=str(bucket),
                       n=int(misses), dispatch_s=dispatch_s)
        for comp_s, _n, warm in resolved:
            if tele is not None and not warm:
                # true compiles only: a warm load's ~ms excess would drag
                # the compile-cost quantiles toward zero
                tele.histogram("compile_s").observe(comp_s)

    def _resolve_locked(self, st: _KeyState):
        """Price every pending miss of ``st`` against its steady median;
        returns [(compile_s, n, warm)] for the caller to surface outside
        the lock."""
        steady = _median(st.steady)
        out = []
        for wall, n in st.pending:
            comp_s = max(wall - steady, 0.0)
            warm = comp_s <= self.warm_load_max_s
            if warm:
                st.warm_loads += n
            else:
                st.compiles += n
                st.compile_s += comp_s
            out.append((comp_s, n, warm))
        st.pending = []
        return out

    def snapshot(self) -> Dict[str, Any]:
        """The summary/exposition view.  Pending misses on keys that never
        went steady are priced at their FULL dispatch wall (an upper
        bound) and flagged unresolved — honest for died runs and
        single-dispatch shapes."""
        with self._lock:
            keys_out = {}
            total_s = 0.0
            total_compiles = 0
            total_warm = 0
            unresolved = 0
            for (fn, bucket), st in sorted(self._keys.items()):
                comp_s = st.compile_s
                compiles = st.compiles
                warm = st.warm_loads
                pend_s = sum(w for w, _ in st.pending)
                pend_n = sum(n for _, n in st.pending)
                if pend_n:
                    # no steady baseline yet: the whole wall is the bound
                    comp_s += pend_s
                    compiles += pend_n
                    unresolved += pend_n
                entry = {
                    "compiles": compiles,
                    "warm_loads": warm,
                    "compile_s": round(comp_s, 6),
                    "first_dispatch_s": (round(st.first_dispatch_s, 6)
                                         if st.first_dispatch_s is not None
                                         else None),
                    "steady_p50_s": (round(_median(st.steady), 6)
                                     if st.steady else None),
                    "steady_n": len(st.steady),
                }
                if pend_n:
                    entry["unresolved"] = pend_n
                keys_out["%s|%s" % (fn, bucket)] = entry
                total_s += comp_s
                total_compiles += compiles
                total_warm += warm
        if not keys_out:
            return {}
        return {"compile_seconds_total": round(total_s, 6),
                "compiles": total_compiles,
                "warm_loads": total_warm,
                "unresolved": unresolved,
                "keys": keys_out}


def accountant(tele, create: bool = False) -> Optional[CompileAccounting]:
    """The compile accountant of run ``tele`` (None when the run is None,
    or has none and ``create`` is False).  Lives on the run; dies with
    it."""
    if tele is None:
        return None
    acct = getattr(tele, "compile_acct", None)
    if acct is None and create:
        with _create_lock:
            acct = getattr(tele, "compile_acct", None)
            if acct is None:
                acct = tele.compile_acct = CompileAccounting()
    return acct


_create_lock = threading.Lock()


def note_dispatch(tele, fn: str, bucket, dispatch_s: float,
                  misses: int) -> None:
    """Site-facing helper: create-on-first-use + note.  Callers are
    REQUIRED to gate on ``tele is not None`` first (the zero-overhead-off
    contract lives at the site, like every obs hook)."""
    acct = accountant(tele, create=True)
    if acct is not None:
        acct.note(tele, fn, bucket, dispatch_s, misses)


# ---- jax's own compile-phase durations as spans ----------------------------
# jax times a persistent-cache retrieval INSIDE its backend-compile event, so
# the retrieval is taken out again: ``jax.backend_compile`` is what the
# compiler took, ``jax.cache_load`` what reading a compiled program back took.
# The count of ``jax.cache_load`` is the cache's hits, the count of
# ``jax.backend_compile`` every compile request; nested jits each report
# their own ``jax.trace``, so its total counts an inner trace twice.
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_SPAN_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    _BACKEND_COMPILE: "jax.backend_compile",
    _CACHE_LOAD: "jax.cache_load",
}
_loading = threading.local()


def _on_jax_duration(event: str, duration: float, **_kw) -> None:
    name = _SPAN_OF.get(event)
    if name is None:
        return
    if event == _CACHE_LOAD:
        _loading.s = getattr(_loading, "s", 0.0) + duration
    elif event == _BACKEND_COMPILE:
        duration = max(duration - getattr(_loading, "s", 0.0), 0.0)
        _loading.s = 0.0
    _spans.note(name, duration)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
