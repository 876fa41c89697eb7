"""Spans: the one way the program brackets host work.

``with span("gbdt.poll_stop"):`` does three things, in this order of cost:

1. **Always** keeps a bounded in-memory record ``(name, parent, start, end)``
   on ``time.perf_counter`` (a ring of :data:`RING` records) and per-name
   totals (``count`` / ``total_s`` / ``max_s``), read by :func:`records` and
   :func:`totals` and cleared by :func:`reset`.  Same contract as
   ``obs.recompile`` and ``obs.launches``, for the same reason: the benchmark
   and the tests read them without configuring a run.  Spans are allowed at
   DISPATCH granularity only (a few per chunk on the fused path, about ten
   per tree on the per-iteration path), never per split or per row.
2. **Always** opens a ``jax.profiler.TraceAnnotation(name)``: under any
   profiler session (``jax.profiler.start_trace``, ``GET /debug/profile``)
   the span sits on the host line of the trace, on the same clock as the
   device's events, so an idle gap on the device reads as the program's own
   phase (``benchmarks/trace_reduce.py::attribute_gaps`` takes the innermost
   host span over a gap's start).  With no session the annotation is inert.
3. **With a telemetry run active** emits the ``kind="span"`` JSONL event
   whose fields are all scalars, so it rides the ordinary schema
   (``validate_event`` accepts it unchanged)::

    {"v": 1, "ts": ..., "kind": "span", "name": "queue_wait",
     "trace_id": "9f..", "span_id": "04..", "parent_id": "c1..",
     "t0": <unix s start>, "dur_s": <seconds>, ...extra scalars}

   ``trace_id`` groups the spans of one logical operation (a serving
   request, a training run), ``parent_id`` nests them, and ``t0``/``dur_s``
   anchor them on the wall clock so ``tools/obs_report.py --trace`` renders
   nested Chrome-trace lifelines.  With NO run active a span makes no
   ``Telemetry`` call, draws no id (:func:`new_id`) and writes nothing: the
   in-memory record is all it does (pinned in tests/test_telemetry.py).

Three recording styles:

- :func:`span` — a context manager for code that brackets its own work.
  Parent propagation is automatic through a thread-local stack; the trace id
  defaults to the enclosing span's, else the active run's ``trace_id``.
- :func:`note` — an in-memory record of an operation that has just ended and
  whose duration somebody else measured (jax's own compile-phase durations,
  ``obs/compile.py``).  It cannot be back-dated into a profiler trace.
- :func:`record_span` — after-the-fact emission of the JSONL event alone on
  a telemetry run (the serving scheduler measures queue wait at claim time,
  long after submit).
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

RING = 65536          # most records kept; the totals never forget

_tls = threading.local()
_lock = threading.Lock()
_seq = itertools.count(1)
_ring: "deque" = deque(maxlen=RING)     # (seq, parent seq, name, start, end)
_totals: Dict[str, List[float]] = {}    # name -> [count, total_s, max_s]

_active_fn = None


def _active():
    # late-bound to dodge the package-import cycle (obs/__init__ imports
    # this module); one global read + call once bound
    global _active_fn
    if _active_fn is None:
        from . import active as fn
        _active_fn = fn
    return _active_fn()


def new_id() -> str:
    """A fresh 64-bit hex id (trace or span) for the JSONL export."""
    return os.urandom(8).hex()


def current() -> Optional["Span"]:
    """The innermost open span on THIS thread (None outside any span)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def _keep(seq: int, parent: int, name: str, start: float, end: float) -> None:
    dur = end - start
    with _lock:
        _ring.append((seq, parent, name, start, end))
        tot = _totals.get(name)
        if tot is None:
            _totals[name] = [1, dur, dur]
        else:
            tot[0] += 1
            tot[1] += dur
            if dur > tot[2]:
                tot[2] = dur


class Span:
    """One open span; use via :func:`span` (context manager).  ``tele`` is
    the telemetry run to export to, or None (in-memory record only)."""

    __slots__ = ("tele", "name", "trace_id", "span_id", "parent_id",
                 "fields", "t0", "seq", "_parent_seq", "_pc0", "_ann")

    def __init__(self, tele, name: str, trace_id: Optional[str],
                 parent_id: Optional[str], fields) -> None:
        self.tele = tele
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_id() if tele is not None else None
        self.parent_id = parent_id
        self.fields = fields
        self.seq = next(_seq)

    def __enter__(self) -> "Span":
        parent = current()
        self._parent_seq = parent.seq if parent is not None else 0
        if self.tele is not None and self.trace_id is None:
            if parent is not None and parent.trace_id is not None:
                self.trace_id = parent.trace_id
                if self.parent_id is None:
                    self.parent_id = parent.span_id
            else:
                self.trace_id = getattr(self.tele, "trace_id", None) \
                    or new_id()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        self.t0 = time.time()
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._pc0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        _keep(self.seq, self._parent_seq, self.name, self._pc0, end)
        if self.tele is not None:
            self.tele.event("span", name=self.name, trace_id=self.trace_id,
                            span_id=self.span_id, parent_id=self.parent_id,
                            t0=self.t0, dur_s=end - self._pc0, **self.fields)


def span(name: str, **fields: Any) -> Span:
    """Bracket a timed operation: recorded in memory and annotated for the
    profiler always, exported as a span of the active run's trace when
    telemetry is on (``fields`` ride the exported event only)."""
    return Span(_active(), name, None, None, fields)


def note(name: str, dur_s: float) -> None:
    """Keep an in-memory record of an operation of ``dur_s`` seconds that
    ends now, under the innermost open span of this thread."""
    end = time.perf_counter()
    parent = current()
    _keep(next(_seq), parent.seq if parent is not None else 0, name,
          end - float(dur_s), end)


def records(name: Optional[str] = None) -> List[Dict[str, Any]]:
    """The ring's records, oldest first: ``{"id", "parent", "name", "start",
    "end"}`` with ``start``/``end`` on ``time.perf_counter`` and ``parent``
    the ``id`` of the enclosing span (0 for none).  ``name`` filters."""
    with _lock:
        kept = list(_ring)
    return [{"id": s, "parent": p, "name": n, "start": a, "end": b}
            for s, p, n, a, b in kept if name is None or n == name]


def totals() -> Dict[str, Dict[str, float]]:
    """``{name: {"count", "total_s", "max_s"}}`` since the last reset; unlike
    the ring, the totals count every span."""
    with _lock:
        return {n: {"count": int(c), "total_s": t, "max_s": m}
                for n, (c, t, m) in sorted(_totals.items())}


def seconds() -> Dict[str, float]:
    """``{name: total_s}``: where the host's time went, span by span (the
    run summary's ``host_phases``, the watchdog's diagnostics)."""
    with _lock:
        return {n: t for n, (_, t, _) in _totals.items()}


def summary() -> str:
    """The end-of-run dump (``verbosity>=2``): every span's total, the
    longest first, with its count."""
    lines = ["LightGBM-TPU host timing summary:"]
    for name, tot in sorted(totals().items(),
                            key=lambda kv: -kv[1]["total_s"]):
        lines.append("  %s: %.6f s in %d" % (name, tot["total_s"],
                                             tot["count"]))
    return "\n".join(lines)


def reset() -> None:
    """Forget every record and total (spans still open are kept when they
    close)."""
    with _lock:
        _ring.clear()
        _totals.clear()


def record_span(tele, name: str, t0: float, dur_s: float,
                trace_id: Optional[str] = None,
                parent_id: Optional[str] = None,
                span_id: Optional[str] = None, **fields: Any) -> str:
    """Emit one already-measured span on ``tele``; returns its span id so
    the caller can parent further spans under it.  ``t0`` is the unix-time
    start, ``dur_s`` the measured duration."""
    sid = span_id or new_id()
    tele.event("span", name=name,
               trace_id=trace_id or getattr(tele, "trace_id", None)
               or new_id(),
               span_id=sid, parent_id=parent_id, t0=float(t0),
               dur_s=float(dur_s), **fields)
    return sid
