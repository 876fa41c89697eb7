"""Runtime supervision: the failures that actually dominate TPU fleets.

PR 4 made a *crashed* run recoverable (atomic checkpoints, bit-exact
resume); this layer makes the runtime bend instead of break on the faults
that are not crashes:

- **SIGTERM/SIGINT preemption** (:func:`install_preemption_handler`): the
  signal handler only sets a flag — async-signal-safe by construction —
  and the training loop polls it at CHUNK boundaries (never mid-chunk:
  a fused lax.scan is one device program and must complete or be
  discarded whole).  On a set flag the loop drains in-flight device work,
  writes a leader-gated emergency checkpoint through the ordinary
  ``checkpoint.py`` path, and raises :class:`TrainingPreempted`; drivers
  convert that into :data:`EXIT_PREEMPTED` (75, ``EX_TEMPFAIL``: "retry
  me") so a supervisor can tell *resumable* from *failed*.

- **hung collectives** (:class:`Watchdog`): a dead peer host leaves a
  collective blocked forever with zero feedback.  Dispatch sites wrap
  their blocking calls in :func:`watch` sections; a monitor thread checks
  the open sections and, after ``watchdog_timeout_s`` with no progress,
  dumps a diagnostic artifact (section, live device set, recompile +
  timer state) to disk and the telemetry sink, then aborts the process
  with :data:`EXIT_STALLED` instead of hanging until the job scheduler's
  much larger timeout reaps it.

- **degraded serving** (:func:`note_fallback`): the always-on counter
  (same discipline as ``obs.recompile``) behind the predict fallbacks in
  ``core/predict_fused.py`` and ``parallel/learners.py`` — a fallback is
  a counted, timestamped event, never a silent behavior change.

Everything here is off until a driver opts in; the flag poll is one
``Event.is_set()`` per chunk and :func:`watch` returns a shared
``nullcontext`` when no watchdog is active.
"""
from __future__ import annotations

import contextlib
import json
import os
import signal
import threading
import time
from typing import Any, Callable, Dict, Optional

from .utils.log import LightGBMError, Log

# sysexits.h semantics: 75 = EX_TEMPFAIL ("temporary failure; the user is
# invited to retry") — exactly what a preempted-but-checkpointed run is.
EXIT_PREEMPTED = 75
# outside the sysexits range so supervisors can distinguish a watchdog
# abort (peer dead / dispatch hung: reschedule elsewhere) from EX_* codes
EXIT_STALLED = 79


class TrainingPreempted(LightGBMError):
    """Training was interrupted by SIGTERM/SIGINT after writing an
    emergency checkpoint; the run is RESUMABLE (exit EXIT_PREEMPTED)."""

    def __init__(self, iteration: int, checkpoint_path: Optional[str] = None,
                 signum: Optional[int] = None) -> None:
        self.iteration = int(iteration)
        self.checkpoint_path = checkpoint_path
        self.signum = signum
        where = (" (emergency checkpoint %s)" % checkpoint_path
                 if checkpoint_path else "")
        super().__init__(
            "training preempted at iteration %d%s; rerun the same command "
            "to resume" % (iteration, where))


# ---- signal-safe preemption flag ----

_PREEMPT_FLAG = threading.Event()
_PREEMPT_SIGNUM: Optional[int] = None
_PREV_HANDLERS: Dict[int, Any] = {}


def _on_preempt_signal(signum, frame) -> None:
    """The installed handler: ONLY sets a flag (plus a signum note for the
    log).  No allocation, no locks, no I/O — everything heavy happens at
    the next chunk boundary in the training loop's own thread."""
    global _PREEMPT_SIGNUM
    _PREEMPT_SIGNUM = signum
    _PREEMPT_FLAG.set()


def install_preemption_handler(signals=(signal.SIGTERM, signal.SIGINT)):
    """Route ``signals`` to the preemption flag; previous handlers are
    remembered and restored by :func:`uninstall_preemption_handler`.

    Returns the tuple of signals THIS call newly installed — the caller
    owns exactly those and should pass them back to
    :func:`uninstall_preemption_handler` on its way out; an empty tuple
    (falsy) means an earlier caller (an embedding host via
    ``LGBM_PreemptionInstall``, an outer driver) already holds every
    requested signal, and tearing any down here would silently disarm
    that owner.  Off the main thread (CPython restriction) installation
    degrades to a warning + empty ownership: the flag machinery
    (:func:`request_preemption` / :func:`preemption_requested`) still
    works, driven by whoever CAN observe the signal."""
    installed = []
    for sig in signals:
        if sig in _PREV_HANDLERS:
            continue
        try:
            _PREV_HANDLERS[sig] = signal.signal(sig, _on_preempt_signal)
        except ValueError:  # not the main thread
            Log.warning(
                "cannot install the %s preemption handler from a non-main "
                "thread; arm it from the main thread (or feed "
                "request_preemption() from your own watcher)",
                signal.Signals(sig).name)
            continue
        installed.append(sig)
    if installed:
        Log.debug("preemption handler installed for %s",
                  ", ".join(signal.Signals(s).name for s in installed))
    return tuple(installed)


def uninstall_preemption_handler(signals=None) -> None:
    """Restore the pre-installation handlers for ``signals`` (an ownership
    tuple from :func:`install_preemption_handler`); ``None`` restores
    everything — for the process-wide owner or test teardown only, never
    for a caller that might share the handlers with an outer owner."""
    sigs = list(_PREV_HANDLERS) if signals is None else list(signals)
    for sig in sigs:
        if sig not in _PREV_HANDLERS:
            continue
        prev = _PREV_HANDLERS.pop(sig)
        try:
            signal.signal(sig, prev)
        except (ValueError, TypeError):  # non-main thread / exotic handler
            pass


def preemption_requested() -> bool:
    """True once a handled signal (or :func:`request_preemption`) fired."""
    return _PREEMPT_FLAG.is_set()


def request_preemption() -> None:
    """Set the flag programmatically (tests, embedding hosts that receive
    the preemption notice out-of-band, e.g. a GCE metadata watcher)."""
    _PREEMPT_FLAG.set()


def clear_preemption() -> None:
    """Consume the flag.  The training loops call this when they HANDLE a
    preemption (emergency checkpoint written, TrainingPreempted about to
    raise): a later ``train()`` in the same process — the in-process
    resume — must start with a clean window instead of instantly
    re-preempting on the stale flag."""
    global _PREEMPT_SIGNUM
    _PREEMPT_SIGNUM = None
    _PREEMPT_FLAG.clear()


def arm_supervision(preempt: bool, watchdog_timeout_s: float,
                    artifact_base: Optional[str] = None):
    """One arming policy for every driver (engine.train, the CLI): install
    the preemption handler when asked (ownership-tracked per signal) and
    start the watchdog when a timeout is configured and none is already
    active.  Returns ``(owned_signals, owned_watchdog)`` for
    :func:`disarm_supervision`."""
    owned_signals = install_preemption_handler() if preempt else ()
    owned_wd = float(watchdog_timeout_s) > 0 and watchdog_active() is None
    if owned_wd:
        art = (artifact_base + ".stall.json") if artifact_base else None
        start_watchdog(float(watchdog_timeout_s), artifact=art)
    return owned_signals, owned_wd


def disarm_supervision(owned_signals, owned_wd: bool) -> None:
    """Tear down exactly what :func:`arm_supervision` armed — signals or
    a watchdog installed by an outer owner are left in place."""
    if owned_signals:
        uninstall_preemption_handler(owned_signals)
    if owned_wd:
        stop_watchdog()


def emergency_checkpoint(booster, prefix: str) -> Optional[str]:
    """Leader-gated emergency checkpoint through the ordinary atomic
    ``checkpoint.py`` path; returns the written path (None on non-leader
    processes — the leader's file is the shared resume point).  Timed into
    the ``preempt_checkpoint_s`` histogram so the drill can verify the
    shutdown fits inside the preemption grace window."""
    from .parallel.learners import is_write_leader
    if not is_write_leader(getattr(booster, "mesh", None)):
        return None
    t0 = time.perf_counter()
    path = booster.save_checkpoint(prefix)
    dt = time.perf_counter() - t0
    signame = (signal.Signals(_PREEMPT_SIGNUM).name
               if _PREEMPT_SIGNUM is not None else "request")
    Log.warning("preemption (%s): wrote emergency checkpoint %s in %.0f ms",
                signame, path, dt * 1e3)
    from .obs import active as _telemetry_active
    tele = _telemetry_active()
    if tele is not None:
        tele.counter("preemptions").inc()
        tele.histogram("preempt_checkpoint_s").observe(dt)
        tele.event("preempt_checkpoint", iteration=int(booster.iter_),
                   dt_s=dt, path=path, signal=signame)
        tele.flush()  # the process is about to exit; do not lose the tail
    return path


# ---- dispatch watchdog ----

# a dispatch's first-ever completion for a given compiled-program key may
# legitimately include an XLA compile (minutes on big programs); until one
# SUCCESSFUL completion proves that program cached, the stall bar for the
# (section, compile_key) pair is timeout * this grace
FIRST_DISPATCH_GRACE = 10.0


class Watchdog:
    """Monitor thread around blocking dispatch/collective calls.

    Sites wrap their blocking work in :meth:`section`; the monitor wakes a
    few times per timeout and, when an open section has made no progress
    for ``timeout_s``, writes a diagnostic artifact + telemetry event and
    aborts the process (``os._exit(EXIT_STALLED)``) — a hung collective
    holds the GIL-released C call forever, so raising in the stuck thread
    is not an option; a clean abort with diagnostics is.

    ``abort=False`` (tests, embedding hosts with their own supervision)
    records the stall and calls ``on_stall(diag)`` instead of exiting.
    """

    def __init__(self, timeout_s: float, artifact: Optional[str] = None,
                 abort: bool = True,
                 on_stall: Optional[Callable[[Dict], None]] = None,
                 first_dispatch_grace: float = FIRST_DISPATCH_GRACE) -> None:
        self.timeout_s = float(timeout_s)
        self.artifact = artifact
        self.abort = abort
        self.on_stall = on_stall
        self.first_dispatch_grace = max(1.0, float(first_dispatch_grace))
        self.fired: Optional[Dict] = None
        self._lock = threading.Lock()
        self._sections: Dict[int, tuple] = {}
        # (section name, compile_key) pairs that completed SUCCESSFULLY at
        # least once under this watchdog: their compiled program is proven
        # cached, so later dispatches of the same pair are held to the
        # plain timeout (not the grace bar).  compile_key is whatever the
        # site keys its compiled programs on (fused chunk length, predict
        # bucket, ...) — compiles are per program, not per call site
        self._completed: set = set()
        self._next_token = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="lgbm-tpu-watchdog", daemon=True)

    def start(self) -> "Watchdog":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2.0)

    @contextlib.contextmanager
    def section(self, name: str, compile_key: Any = None, **info: Any):
        """Mark a blocking dispatch: open = potentially stalled; a
        SUCCESSFUL close is the progress signal that also proves
        ``(name, compile_key)``'s program compiled — a dispatch that
        RAISED cached nothing and must not revoke the compile grace."""
        key = (name, compile_key)
        with self._lock:
            token = self._next_token
            self._next_token += 1
            self._sections[token] = (name, key, time.monotonic(), info)
        try:
            yield
        except BaseException:
            with self._lock:
                self._sections.pop(token, None)
            raise
        else:
            with self._lock:
                self._sections.pop(token, None)
                self._completed.add(key)

    def status(self) -> Dict[str, Any]:
        """Live view for /healthz (obs/exporter.py): open dispatch
        sections with the age of the oldest one, and whether this
        watchdog already fired."""
        now = time.monotonic()
        with self._lock:
            ages = [now - start for _, _, start, _ in
                    self._sections.values()]
        return {"active": True, "timeout_s": self.timeout_s,
                "open_sections": len(ages),
                "oldest_open_s": round(max(ages), 3) if ages else None,
                "fired": self.fired is not None}

    # ---- monitor ----

    def _bar_s(self, key) -> float:
        """Stall bar for a section: the plain timeout once its
        (name, compile_key) completed under this watchdog; grace-scaled
        before that (the first dispatch of a program may be compiling,
        and a compile is not a hang)."""
        return self.timeout_s * (1.0 if key in self._completed
                                 else self.first_dispatch_grace)

    def _run(self) -> None:
        poll = max(min(self.timeout_s / 4.0, 1.0), 0.01)
        while not self._stop.wait(poll):
            now = time.monotonic()
            with self._lock:
                stalled = [(name, now - start, info)
                           for name, key, start, info
                           in self._sections.values()
                           if now - start > self._bar_s(key)]
            if stalled and self.fired is None:
                # oldest section = the actual blocker
                name, elapsed, info = max(stalled, key=lambda s: s[1])
                self._handle_stall(name, elapsed, info)
                if self.abort:
                    os._exit(EXIT_STALLED)
                # a non-aborting watchdog is one-shot: its monitor exits
                # here, so hand the process-active slot back — otherwise
                # every later arm_supervision sees "already armed" and a
                # long-lived host silently loses stall detection forever
                global _WATCHDOG
                if _WATCHDOG is self:
                    _WATCHDOG = None
                return

    def _diagnostics(self, name: str, elapsed: float,
                     info: Dict[str, Any]) -> Dict[str, Any]:
        from .obs import recompile, spans
        diag: Dict[str, Any] = {
            "v": 1, "kind": "watchdog_stall", "ts": time.time(),
            "section": name, "stall_s": round(elapsed, 3),
            "timeout_s": self.timeout_s, "pid": os.getpid(),
            "info": {k: v for k, v in info.items()},
            "recompiles": recompile.as_flat_dict(),
            "host_phases": {k: round(v, 6)
                            for k, v in spans.seconds().items()},
        }
        try:  # the live device set: which peers the runtime still sees
            import jax
            diag["devices"] = [str(d) for d in jax.devices()]
            diag["process_index"] = int(jax.process_index())
        except Exception as exc:  # backend itself wedged — still report
            diag["devices"] = "unavailable: %s" % exc
        return diag

    def _handle_stall(self, name: str, elapsed: float,
                      info: Dict[str, Any]) -> None:
        global _LAST_STALL
        diag = self._diagnostics(name, elapsed, info)
        self.fired = diag
        _LAST_STALL = diag
        Log.warning("WATCHDOG: no progress in %r for %.1f s (timeout %.1f s)"
                    " — dumping diagnostics and aborting", name, elapsed,
                    self.timeout_s)
        from .obs import active as _telemetry_active
        tele = _telemetry_active()
        if tele is not None:
            tele.gauge("watchdog_stall_s").set(elapsed)
            tele.event("watchdog_stall", section=name, stall_s=elapsed,
                       timeout_s=self.timeout_s)
            # a stall is an SLO incident: surface it through the alert
            # stream (obs/alerts.py) and give the flight recorder its one
            # shot BEFORE the abort — the capture runs synchronously here
            # so the artifact exists when the supervisor reads exit 79.
            # Both are no-ops unless the run armed them.
            from .obs import alerts as _alerts
            from .obs import profiling as _profiling
            _alerts.note_incident(tele, "watchdog_stall", section=name,
                                  stall_s=elapsed)
            _profiling.on_incident("watchdog_stall")
            tele.flush()
        if self.artifact:
            try:
                from .utils.file_io import atomic_write
                atomic_write(self.artifact, json.dumps(diag, indent=1,
                                                       default=str))
                Log.warning("WATCHDOG: diagnostics written to %s",
                            self.artifact)
            except OSError as exc:  # must not mask the abort itself
                Log.warning("WATCHDOG: could not write diagnostics (%s)", exc)
        if self.on_stall is not None:
            self.on_stall(diag)


_WATCHDOG: Optional[Watchdog] = None
_NULL_CTX = contextlib.nullcontext()
# the last stall diagnostic, surviving the (one-shot) watchdog teardown so
# /healthz keeps reporting "stalled" after a non-aborting fire; cleared
# when a fresh watchdog arms
_LAST_STALL: Optional[Dict] = None


def last_stall() -> Optional[Dict]:
    """Diagnostics of the most recent watchdog stall (None when the
    current supervision generation has seen none)."""
    return _LAST_STALL


def clear_stall() -> None:
    """Drop the recorded stall evidence (tests; an embedding host that
    recovered out-of-band).  Arming a fresh watchdog clears it too."""
    global _LAST_STALL
    _LAST_STALL = None


def start_watchdog(timeout_s: float, artifact: Optional[str] = None,
                   abort: bool = True,
                   on_stall: Optional[Callable[[Dict], None]] = None,
                   first_dispatch_grace: float = FIRST_DISPATCH_GRACE
                   ) -> Watchdog:
    """Install (replacing any previous) the process-active watchdog."""
    global _LAST_STALL, _WATCHDOG
    _LAST_STALL = None  # fresh supervision generation, fresh evidence
    prev, _WATCHDOG = _WATCHDOG, Watchdog(
        timeout_s, artifact=artifact, abort=abort, on_stall=on_stall,
        first_dispatch_grace=first_dispatch_grace)
    if prev is not None:
        prev.stop()
    Log.debug("watchdog armed: timeout %.1f s%s", timeout_s,
              (", artifact %s" % artifact) if artifact else "")
    return _WATCHDOG.start()


def stop_watchdog() -> None:
    global _WATCHDOG
    prev, _WATCHDOG = _WATCHDOG, None
    if prev is not None:
        prev.stop()


def watchdog_active() -> Optional[Watchdog]:
    return _WATCHDOG


def watchdog_status() -> Optional[Dict[str, Any]]:
    """The active watchdog's :meth:`Watchdog.status` (None when no
    watchdog is armed) — the /healthz heartbeat source."""
    wd = _WATCHDOG
    return wd.status() if wd is not None else None


def watch(name: str, compile_key: Any = None, **info: Any):
    """Context manager marking a blocking dispatch for the active watchdog;
    a shared no-op when none is armed (the hot-path cost of supervision
    being off is one global read).  ``compile_key`` identifies the compiled
    program this dispatch runs (fused chunk length, predict bucket): its
    first successful completion ends the first-dispatch compile grace for
    that program only."""
    wd = _WATCHDOG
    if wd is None:
        return _NULL_CTX
    return wd.section(name, compile_key=compile_key, **info)


# ---- degraded-serving fallback accounting (always-on, like obs.recompile) ----

_FB_LOCK = threading.Lock()
_FALLBACKS: Dict[str, int] = {}

# What a degraded-serving handler re-raises instead of serving around: these
# come from a bug in the program (a removed API, a wrong call), not from an
# unhealthy device or mesh — a fallback that answers anyway would hide it.
PROGRAM_ERRORS = (AttributeError, TypeError, NameError)


def note_fallback(site: str, reason: str = "", **fields: Any) -> None:
    """Count one degraded-path activation at ``site``; mirrored to the
    active telemetry run (counter ``predict_fallbacks`` + a
    ``predict_fallback`` event) when one is configured.  A ``model=<name>``
    field (the serving tier's per-model attribution) additionally bumps a
    ``predict_fallbacks_model_<name>`` counter so the summary's serving
    block can surface fallbacks per resident model."""
    with _FB_LOCK:
        _FALLBACKS[site] = _FALLBACKS.get(site, 0) + 1
    from .obs import active as _telemetry_active
    tele = _telemetry_active()
    if tele is not None:
        tele.counter("predict_fallbacks").inc()
        model = fields.get("model")
        if model:
            tele.counter("predict_fallbacks_model_%s" % model).inc()
        tele.event("predict_fallback", site=site, reason=str(reason)[:300],
                   **fields)


def fallback_counts() -> Dict[str, int]:
    with _FB_LOCK:
        return dict(_FALLBACKS)


def reset_fallbacks() -> None:
    with _FB_LOCK:
        _FALLBACKS.clear()
