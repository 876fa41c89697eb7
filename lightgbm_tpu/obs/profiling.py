"""Triggered profiler capture: on-demand and flight-recorder traces.

``obs/spans.py``'s profiler annotations only light up when someone separately
starts ``jax.profiler`` — which nobody does at 3am when the p99 is
burning.  This module makes capture a RUN capability:

- :func:`capture` runs ``jax.profiler.trace`` for a bounded window into a
  run-scoped artifact directory (``<telemetry_out>.profiles/
  capture_<n>_<reason>/`` with a ``capture.json`` metadata file next to
  the xplane protobufs) — the exporter serves it at
  ``GET /debug/profile?seconds=N``, so an operator can pull a device
  trace from a live process with curl;
- **flight recorder**: :func:`arm_flight_recorder` arms ONE automatic
  capture per run, fired by the first watchdog stall or the first live
  SLO alert (:func:`on_incident`).  Bounded and never recursive: a second
  incident, or an incident during a capture, is a no-op — the recorder
  exists to attach evidence to the first failure, not to trace a death
  spiral.

``tools/profile_tree.py`` builds its artifacts through the same
:func:`open_capture`/:func:`trace_block` layout, so a standalone profile
and a triggered one aggregate identically.

Run-owned, zero-overhead-when-off: state lives on the active
:class:`~.registry.Telemetry` (``tele.profiling``); with telemetry off no
state exists and :func:`on_incident` is one ``active() is None`` check
(spy-pinned in tests/test_obs_forensics.py).  Import-safe without
``jax.profiler`` — a capture then records an error marker instead of a
trace, never an exception.
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import tempfile
import threading
import time
from typing import Any, Dict, Optional

# artifact root = <telemetry base> + this suffix
PROFILE_DIR_SUFFIX = ".profiles"
# /debug/profile bounds: a capture is a diagnostic window, not a logger
DEFAULT_SECONDS = 1.0
MAX_SECONDS = 60.0
# flight-recorder window (short: it runs synchronously before a watchdog
# abort, so it must fit inside the supervisor's grace period)
FLIGHT_SECONDS = 1.0

_SAFE = re.compile(r"[^0-9A-Za-z_.-]")


class ProfilingState:
    """Per-run capture state: artifact numbering, in-flight flag, and the
    one-shot flight-recorder arm."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.active = False          # a capture is running right now
        self.captures: list = []     # metadata dicts, in order
        self.armed = False           # flight recorder armed
        self.auto_seconds = FLIGHT_SECONDS
        self.auto_fired = False      # at most one automatic capture per run


def state(tele, create: bool = False) -> Optional[ProfilingState]:
    if tele is None:
        return None
    st = getattr(tele, "profiling", None)
    if st is None and create:
        with _create_lock:
            st = getattr(tele, "profiling", None)
            if st is None:
                st = tele.profiling = ProfilingState()
    return st


_create_lock = threading.Lock()


def artifact_root(tele) -> str:
    """The run's profile directory: next to the telemetry artifacts when
    the run has a sink, else a per-process tempdir (memory-sink runs still
    get somewhere durable to capture into)."""
    base = getattr(tele, "summary_base", None) or getattr(
        tele, "out_path", None)
    if base:
        return base + PROFILE_DIR_SUFFIX
    return os.path.join(tempfile.gettempdir(),
                        "lgbm_tpu_profiles_%d" % os.getpid())


def open_capture(root: str, n: int, reason: str) -> str:
    """Create and return the capture directory ``<root>/
    capture_<n>_<reason>/`` — the ONE layout both the triggered path and
    ``tools/profile_tree.py`` write, so downstream xplane aggregation
    never needs to know who captured."""
    outdir = os.path.join(root, "capture_%02d_%s"
                          % (int(n), _SAFE.sub("_", str(reason))[:48]))
    os.makedirs(outdir, exist_ok=True)
    return outdir


# ``detail`` of a capture -> the profiler's ``tpu_trace_mode``.  "kernel":
# the device plane also gets the ``Tensor Core Sync Flag`` line (every DMA
# and semaphore wait with its duration), beside what the process's own
# start-up flags already put there under any mode: with
# ``obs.scopes.KERNEL_TRACE_FLAGS`` in LIBTPU_INIT_ARGS the ``XLA TraceMe``
# line holds an event for every region a Pallas kernel opens
# (``obs.scopes.KERNEL_REGIONS``).  Without the flags the regions compile to
# nothing and the line is absent: the mode alone does not bring them.
DETAILS = {None: None, "kernel": "TRACE_COMPUTE_AND_SYNC"}


def profile_options(detail: Optional[str]):
    """``jax.profiler.ProfileOptions`` for a capture of ``detail`` (None:
    the profiler's defaults, passed as None)."""
    if detail not in DETAILS:
        raise ValueError("detail must be one of %r, got %r"
                         % (sorted(d for d in DETAILS if d), detail))
    if DETAILS[detail] is None:
        return None
    from jax import profiler
    options = profiler.ProfileOptions()
    options.advanced_configuration = {"tpu_trace_mode": DETAILS[detail]}
    return options


def trace_block(outdir: str, detail: Optional[str] = None):
    """Context manager running ``jax.profiler.trace`` into ``outdir``; a
    null context (still yielding) when the profiler is unavailable, so
    callers never need their own import guard."""
    try:
        from jax import profiler
        return profiler.trace(outdir,
                              profiler_options=profile_options(detail))
    except Exception:
        return contextlib.nullcontext()


def write_meta(outdir: str, **meta: Any) -> Dict[str, Any]:
    """Stamp ``capture.json`` into a capture directory (best-effort: a
    full disk must not fail the capture that just succeeded)."""
    doc = {"v": 1, "ts": time.time(), "dir": outdir}
    doc.update(meta)
    try:
        from ..utils.file_io import atomic_write
        atomic_write(os.path.join(outdir, "capture.json"),
                     json.dumps(doc, indent=1, default=str))
    except OSError:
        pass
    return doc


def capture(tele, seconds: float = DEFAULT_SECONDS,
            reason: str = "manual",
            detail: Optional[str] = None) -> Dict[str, Any]:
    """Run one bounded profiler capture on ``tele``'s run; returns the
    capture metadata (or ``{"error": ...}`` when a capture is already in
    flight — never recursive, never concurrent).  Blocks for ``seconds``;
    the /debug/profile handler calls this from its own request thread so
    scrapes stay live meanwhile.  Callers gate on ``tele is not None``.
    ``detail="kernel"``: see :data:`DETAILS`."""
    seconds = min(max(float(seconds), 0.05), MAX_SECONDS)
    options = profile_options(detail)      # a wrong detail raises here
    st = state(tele, create=True)
    with st.lock:
        if st.active:
            return {"busy": True,
                    "error": "a profiler capture is already in progress",
                    "captures": len(st.captures)}
        st.active = True
        n = len(st.captures) + 1
    t0 = time.time()
    err = None
    outdir = None
    meta = {"n": n, "reason": str(reason), "seconds": seconds, "t0": t0}
    if detail is not None:
        meta["detail"] = detail
    try:
        try:
            root = artifact_root(tele)
            outdir = open_capture(root, n, reason)
            try:
                from jax import profiler
            except Exception as exc:
                err = "jax.profiler unavailable: %s" % exc
            else:
                try:
                    with profiler.trace(outdir, profiler_options=options):
                        time.sleep(seconds)
                except Exception as exc:  # a broken backend must not
                    err = "%s: %s" % (type(exc).__name__, exc)  # kill the run
        except OSError as exc:
            err = "cannot create capture dir: %s" % exc
        meta["dur_s"] = round(time.time() - t0, 3)
        if outdir is not None:
            meta["dir"] = outdir
            write_meta(outdir, **meta)
        if err is not None:
            meta["error"] = err
    finally:
        # append + release TOGETHER: a capture started between the two
        # would recompute the same n from len(captures) and reuse (and
        # corrupt) this capture's artifact directory
        with st.lock:
            st.captures.append(meta)
            st.active = False
    tele.counter("profile_captures").inc()
    tele.event("profile_capture", **{k: v for k, v in meta.items()
                                     if not isinstance(v, dict)})
    from ..utils.log import Log
    Log.warning("profiler capture #%d (%s): %s", n, reason,
                err if err else outdir)
    return meta


def arm_flight_recorder(tele, seconds: float = FLIGHT_SECONDS) -> None:
    """Arm ONE automatic capture for this run, fired by the first
    incident (:func:`on_incident`): watchdog stall or live SLO alert."""
    st = state(tele, create=True)
    with st.lock:
        st.armed = True
        st.auto_seconds = min(max(float(seconds), 0.05), MAX_SECONDS)


def on_incident(reason: str) -> Optional[Dict[str, Any]]:
    """Incident hook (watchdog stall, alert firing): capture once per run
    when the flight recorder is armed; a no-op in every other state —
    disarmed, already fired, mid-capture, telemetry off.  Synchronous:
    the watchdog calls this BEFORE aborting, so the artifact exists when
    the supervisor reads the exit code."""
    from . import active
    tele = active()
    if tele is None:
        return None
    st = state(tele)
    if st is None:
        return None
    with st.lock:
        if not st.armed or st.auto_fired or st.active:
            return None
        st.auto_fired = True
        seconds = st.auto_seconds
    return capture(tele, seconds=seconds, reason=str(reason))


def snapshot(tele) -> Dict[str, Any]:
    """The summary view: captures taken, flight-recorder arm state."""
    st = state(tele)
    if st is None:
        return {}
    with st.lock:
        if not st.captures and not st.armed:
            return {}
        return {"captures": list(st.captures),
                "flight_recorder_armed": st.armed,
                "flight_recorder_fired": st.auto_fired}
