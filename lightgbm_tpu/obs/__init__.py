"""Unified telemetry: metrics registry, JSONL events, recompile accounting,
spans on the profiler's clock, MFU estimation, end-of-run reports — and,
since round 14, the LIVE observability plane: an HTTP scrape surface
(:mod:`.exporter`: ``/metrics`` ``/healthz`` ``/summary.json``),
request-scoped spans (:mod:`.spans`) and rank-aware pod shard sinks.

The observability layer the reference ships as layer 0
(``Common::Timer``/``global_timer``, common.h:1032-1093) rebuilt for the
TPU runtime: one ACTIVE :class:`~.registry.Telemetry` instance per process
(``configure`` / ``active`` / ``disable``), consulted by the training,
inference and checkpoint paths at chunk/dispatch granularity.  With no
instance configured — the default — every instrumentation site is a
``None`` check and the hot loops make zero telemetry calls (pinned by
tests/test_telemetry.py).

Enable from any entry point with the ``telemetry_out`` (JSONL path) and
``telemetry_freq`` (per-iteration event cadence) params; ``engine.train``
and the CLI finalize the run into
``<telemetry_out>.summary.json`` via :func:`~.report.finalize_run`.
``metrics_port`` additionally serves the run live over HTTP.  Under a
multi-process pod each host writes its own ``<out>.rank<k>.jsonl`` shard
(every event rank-stamped; ``tools/obs_report.py --merge`` reassembles the
pod view) and only the leader writes the summary.
Recompile accounting (:mod:`.recompile`) is the one always-on piece: it
costs an integer compare per dispatch and is what turns the "steady-state
serving never recompiles" invariant into a readable gauge.
"""
from __future__ import annotations

import os as _os
import threading
from typing import Any, Optional

from . import recompile  # noqa: F401  (re-export)
from .registry import (EVENT_SCHEMA_VERSION, Counter, Gauge, Histogram,
                       MetricsRegistry, Telemetry, iter_events, read_events,
                       shard_path, validate_event)

__all__ = ["Telemetry", "MetricsRegistry", "Counter", "Gauge", "Histogram",
           "EVENT_SCHEMA_VERSION", "read_events", "iter_events",
           "validate_event", "shard_path", "configure", "active", "disable",
           "recompile", "spans", "quality",
           "devmem", "profiling", "alerts"]
# NOTE: the compile-accounting submodule is reachable as obs.compile but
# deliberately NOT in __all__ — a star-import must not shadow the
# builtin compile()

_lock = threading.Lock()
_active: Optional[Telemetry] = None

# forces a pod rank without a jax distributed runtime (the 8-device dryrun
# and the tests simulate multi-host shard sinks through it)
RANK_ENV = "LIGHTGBM_TPU_TELEMETRY_RANK"


def _resolve_rank(rank: Optional[int]):
    """(rank, pod_mode): explicit arg > env override > jax process index.
    ``pod_mode`` turns the JSONL sink into a per-rank shard; a plain
    single-process run keeps rank None and the unsharded path."""
    if rank is not None:
        return int(rank), True
    env = _os.environ.get(RANK_ENV)
    if env:
        return int(env), True
    try:
        # the import is real (not sys.modules-gated): a pod CLI process
        # that configures telemetry before its first jit would otherwise
        # resolve single-host and d hosts would truncate/interleave ONE
        # JSONL path — the corruption the old leader-only gate prevented.
        # Every real run imports jax moments later anyway; environments
        # without jax degrade to single-host.
        import jax
        if jax.process_count() > 1:
            return int(jax.process_index()), True
    except Exception:
        pass
    return None, False


def configure(out: Optional[str] = None, freq: int = 1,
              rank: Optional[int] = None, metrics_port: int = 0,
              metrics_addr: str = "127.0.0.1",
              alert_rules: Optional[str] = None,
              alert_interval_s: float = 1.0,
              flight_recorder: bool = False, **meta: Any) -> Telemetry:
    """Install the process-active telemetry run (closing any previous one).

    ``out`` is the JSONL sink path (None keeps events in memory); under a
    pod (multi-process jax, an explicit ``rank``, or the
    ``LIGHTGBM_TPU_TELEMETRY_RANK`` override) the sink becomes the
    per-host shard ``<out>.rank<k>.jsonl`` and every event is
    rank-stamped.  ``metrics_port > 0`` starts the live HTTP exporter
    (``/metrics`` ``/healthz`` ``/summary.json``) on the run; it is shut
    down by ``Telemetry.close()``/:func:`disable`.  Extra kwargs land on
    the ``run_start`` event."""
    global _active
    rank, pod = _resolve_rank(rank)
    sink = shard_path(out, rank) if (out and pod) else out
    tele = Telemetry(out=sink, freq=freq, meta=meta, rank=rank,
                     summary_base=out)
    with _lock:
        prev, _active = _active, tele
    if prev is not None:
        # close (and release any exporter port) BEFORE binding the new
        # listener: back-to-back runs may reuse one fixed metrics_port
        prev.close()
    if int(metrics_port) > 0:
        from .exporter import start_exporter
        start_exporter(tele, port=int(metrics_port), addr=metrics_addr)
    # performance-forensics plane (round 16): a rules file arms the live
    # alert engine, flight_recorder arms the one-shot incident capture —
    # both owned by the run and torn down by Telemetry.close()
    if alert_rules:
        from . import alerts as _alerts
        _alerts.install(tele, rules_path=str(alert_rules),
                        interval_s=float(alert_interval_s))
    if flight_recorder:
        from . import profiling as _profiling
        _profiling.arm_flight_recorder(tele)
    return tele


def active() -> Optional[Telemetry]:
    """The process-active telemetry run, or None (telemetry off)."""
    return _active


def disable() -> None:
    """Close and clear the active telemetry run."""
    global _active
    with _lock:
        prev, _active = _active, None
    if prev is not None:
        prev.close()


# spans is re-exported here (placed after active() exists to dodge the
# cycle); exporter is NOT imported eagerly — it drags http.server into
# every telemetry-off `import lightgbm_tpu`, and all its call sites
# (configure, serving.Server, Telemetry.close) reach it lazily
from . import spans  # noqa: E402,F401
from . import quality  # noqa: E402,F401
# forensics-plane modules (round 16): compile accounting and devmem are
# light (stdlib + lazy jax touches); profiling and alerts are imported
# lazily by their call sites like exporter — alerts only when a rules
# file arms it, profiling only on capture/arm
from . import compile  # noqa: E402,F401,A004
from . import devmem  # noqa: E402,F401
