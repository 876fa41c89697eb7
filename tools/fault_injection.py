"""Fault-injection harness: prove every recovery path of the fault-tolerant
training runtime (lightgbm_tpu/checkpoint.py + resilience.py) recovers.

Scenarios (each prints PASS/FAIL and exits nonzero on failure):

  kill-write   Kill the trainer INSIDE an atomic snapshot write — after the
               temp file is written but before the rename (SIGKILL-equivalent
               os._exit in a child process).  Asserts the destination model/
               checkpoint files still validate (atomicity), then resumes the
               run and asserts the final model is bit-identical to an
               uninterrupted run.
  corrupt      Flip bytes in / truncate the NEWEST checkpoint.  Asserts
               load_latest_checkpoint falls back to the previous good one and
               the resumed run still completes.
  nan-grad     Train with gradients that go non-finite at a chosen iteration
               under each nan_policy: raise must raise a LightGBMError,
               skip_iter / clip must complete with a finite model.
  sigterm      Preempt a trainer with SIGTERM mid-run (the dominant TPU-fleet
               fault).  The installed handler sets a flag; the loop polls it
               at the next CHUNK boundary, writes an emergency checkpoint,
               and exits with resilience.EXIT_PREEMPTED (75) so a supervisor
               knows "resumable".  Asserts the distinct exit code, the
               checkpoint, and a bit-exact resume vs an uninterrupted run.
  hang         Stall the fused-chunk dispatch forever (a dead-peer collective
               stand-in).  The armed watchdog must dump a diagnostic
               artifact (section, device set, recompile/timer state) and
               abort with resilience.EXIT_STALLED (79) within 2x
               watchdog_timeout_s instead of hanging until the scheduler
               reaps the job.
  enospc       Periodic checkpoint/snapshot writes hit injected filesystem
               faults: transient EIO is retried (bounded jittered backoff in
               utils/file_io.py) and the checkpoint lands; persistent ENOSPC
               skips THAT checkpoint and training completes anyway (periodic
               durability is best-effort, never fatal to a healthy run).
  level-preempt  The round-12 level-batched dispatch (tree_grow_mode=level +
               trees_per_chunk, fused Pallas path in interpret mode via
               LIGHTGBM_TPU_PALLAS_INTERPRET=1) under the SIGTERM drill:
               emergency checkpoint at the chunk boundary, exit 75, resume
               bit-exact — the checkpoint/preemption invariants hold under
               the new dispatch shape.
  swap-under-load  The round-13 serving republish drill: two resident
               models under concurrent request threads, one hot-swapped
               mid-traffic.  Zero dropped requests (every response bit-exact
               vs the generation that served it), zero steady-state
               recompiles after warmup, old predictor entries fully dropped.
  scrape-under-preempt  The round-14 live-plane drill: the SIGTERM
               scenario with the HTTP exporter (obs/exporter.py) up.
               /healthz reads "ok" mid-train and flips to "draining" the
               moment the preemption flag lands (before the chunk-boundary
               poll), /metrics stays well-formed Prometheus text, the
               process exits 75, and the final summary artifact is
               consistent with the last live /summary.json scrape.
  drift-swap   The quality-plane provenance drill (obs/quality.py): a
               resident model hot-swapped mid-traffic for a replacement
               trained on a SHIFTED distribution, with the drift monitor
               live.  Per-generation PSI attributes each request to the
               generation that actually served it (old-generation requests
               in flight across the flip score against the OLD baseline),
               the swapped-in generation flags exactly the shifted feature
               above the alert threshold, the generation gauge flips with
               the swap, zero drops, zero steady-state recompiles, and the
               quality block survives died-run recovery from raw events.
  online-preempt  The round-17 train-while-serve drill: SIGTERM the
               online trainer in the middle of a retrain cycle while
               paced traffic runs against the live generation.  The
               cycle's persisted window + emergency checkpoint survive,
               the process exits EXIT_PREEMPTED (75) with zero dropped
               requests and every response bit-exact vs the generation
               that served it, and the rerun resumes the SAME cycle and
               publishes a next generation byte-identical (model hash)
               to an uninterrupted run's.
  ingest-preempt  The round-21 streaming-loader drill: SIGTERM lands in
               the middle of pass 2 of a ``data_chunk_rows`` ingest.  The
               loader polls the preemption flag at the next chunk
               boundary and the process exits EXIT_PREEMPTED (75) with NO
               partial binary store on disk (``save_binary`` is a single
               atomic rename after the last chunk); ingest holds no
               checkpoint state, so recovery is the rerun — which
               re-ingests from the raw file and trains a byte-identical
               model (hash-pinned vs an uninterrupted run).
  stall-capture  The round-16 flight recorder under the hang drill: the
               watchdog stall, with a telemetry run and flight_recorder
               armed, emits a kind="alert" event, triggers EXACTLY ONE
               jax.profiler capture artifact (written BEFORE the abort so
               a supervisor reading exit 79 finds the evidence), and the
               exit code stays EXIT_STALLED.
  all          Run every scenario.

``--matrix`` runs every scenario, prints a pass/fail table, and writes a
JSON report (``--report``, default <workdir>/fault_matrix.json) — the
one-command preemption drill PERF.md's multi-host protocol builds on.

Small CPU shapes; run with JAX_PLATFORMS=cpu anywhere.  The byte-level
helpers (corrupt_file / truncate_file) are imported by
tests/test_checkpoint.py so the pytest suite and this CLI exercise the same
fault model.
"""
import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- byte-level fault helpers (shared with tests/test_checkpoint.py) ----

def corrupt_file(path: str, offset: int = None, nbytes: int = 4) -> None:
    """Flip ``nbytes`` bytes in place (default: middle of the file)."""
    size = os.path.getsize(path)
    if offset is None:
        offset = size // 2
    with open(path, "r+b") as fh:
        fh.seek(offset)
        chunk = fh.read(nbytes)
        fh.seek(offset)
        fh.write(bytes(b ^ 0xFF for b in chunk))


def truncate_file(path: str, frac: float = 0.5) -> None:
    """Cut the file to ``frac`` of its size (a partial non-atomic write)."""
    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(max(1, int(size * frac)))


# ---- training driver used by every scenario ----

_TRAIN_SRC = r"""
import os, sys
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")

def build(n_iter, snapshot_freq, nan_policy="raise"):
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.metric.metric import create_metrics
    from lightgbm_tpu.objective import create_objective
    rng = np.random.RandomState(0)
    X = rng.uniform(-2, 2, size=(400, 5))
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
         + 0.1 * rng.normal(size=400)).astype(np.float32)
    cfg = Config(objective="regression", num_leaves=15, min_data_in_leaf=5,
                 bagging_fraction=0.8, bagging_freq=3, verbosity=-1,
                 num_iterations=n_iter, snapshot_freq=snapshot_freq,
                 metric_freq=4, nan_policy=nan_policy,
                 hist_precision=os.environ.get("HIST_PRECISION", "exact"))
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                   min_data_in_leaf=cfg.min_data_in_leaf)
    booster = create_boosting(cfg.boosting, cfg,
                              ds, create_objective(cfg.objective, cfg))
    booster.add_train_metrics(create_metrics(cfg.metric, cfg))
    return booster
"""

_KILL_CHILD_SRC = _TRAIN_SRC + r"""
# die like a preempted worker: os._exit inside the atomic write of the
# snapshot at iteration KILL_AT_WRITE_N, after the temp bytes are on disk
# but before the rename
from lightgbm_tpu.utils import file_io
nth = [0]
kill_n = int(os.environ["KILL_AT_WRITE_N"])

def _kill(stage, path):
    if stage != "written":
        return
    nth[0] += 1
    if nth[0] == kill_n:
        os._exit(9)

file_io.set_fault_hook(_kill)
booster = build(int(os.environ["TOTAL_ITERS"]), int(os.environ["SNAP_FREQ"]))
booster.train(snapshot_out=os.environ["MODEL_OUT"])
booster.save_model(os.environ["MODEL_OUT"])
print("TRAINED-TO-END")  # only reached when the kill did not fire
"""


def _run_child(src: str, env: dict) -> subprocess.CompletedProcess:
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "-c", src], env=full_env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=900)


def _uninterrupted_model(workdir: str, total: int, sf: int) -> str:
    out = os.path.join(workdir, "ref_model.txt")
    p = _run_child(_KILL_CHILD_SRC, {
        "MODEL_OUT": out, "TOTAL_ITERS": str(total), "SNAP_FREQ": str(sf),
        "KILL_AT_WRITE_N": "0"})
    assert "TRAINED-TO-END" in p.stdout, p.stdout + p.stderr
    with open(out) as fh:
        return fh.read()


def scenario_kill_write(workdir: str) -> None:
    """Kill mid-snapshot-write; assert atomicity + bit-exact resume."""
    total, sf = 20, 7
    ref = _uninterrupted_model(workdir, total, sf)
    out = os.path.join(workdir, "model.txt")
    # 2 snapshot boundaries before total (7, 14); each boundary performs two
    # atomic writes (model snapshot, checkpoint) -> the 3rd write is the
    # iteration-14 model snapshot, the 4th the iteration-14 checkpoint
    p = _run_child(_KILL_CHILD_SRC, {
        "MODEL_OUT": out, "TOTAL_ITERS": str(total), "SNAP_FREQ": str(sf),
        "KILL_AT_WRITE_N": "4"})
    assert p.returncode == 9, "child should have been killed: %s" % p.stderr
    assert "TRAINED-TO-END" not in p.stdout
    # atomicity: everything on disk validates; the interrupted checkpoint
    # write left no trace at the destination
    from lightgbm_tpu.checkpoint import list_checkpoints, load_checkpoint
    ckpts = list_checkpoints(out)
    assert [it for it, _ in ckpts] == [7], ckpts
    load_checkpoint(ckpts[0][1])  # CRC validates
    # resume from the iteration-7 checkpoint and finish
    sys.path.insert(0, REPO)
    ns = {}
    exec(compile(_TRAIN_SRC, "<train>", "exec"), ns)
    booster = ns["build"](total, sf)
    resumed = booster.resume_from_checkpoint(out)
    assert resumed == 7, resumed
    booster.train()
    assert booster.save_model_to_string() == ref, \
        "resumed model diverged from the uninterrupted run"
    print("PASS kill-write: mid-write kill left only valid files; resume "
          "from iter %d is bit-exact" % resumed)


def scenario_corrupt(workdir: str) -> None:
    """Corrupt / truncate the newest checkpoint; assert fallback."""
    out = os.path.join(workdir, "model_c.txt")
    p = _run_child(_KILL_CHILD_SRC, {
        "MODEL_OUT": out, "TOTAL_ITERS": "20", "SNAP_FREQ": "7",
        "KILL_AT_WRITE_N": "0"})
    assert "TRAINED-TO-END" in p.stdout, p.stdout + p.stderr
    from lightgbm_tpu.checkpoint import (CheckpointError, list_checkpoints,
                                         load_checkpoint,
                                         load_latest_checkpoint)
    ckpts = list_checkpoints(out)
    assert len(ckpts) == 2, ckpts  # iterations 14 and 7
    corrupt_file(ckpts[0][1])
    try:
        load_checkpoint(ckpts[0][1])
        raise AssertionError("corrupt checkpoint validated")
    except CheckpointError:
        pass
    meta, _, _, path = load_latest_checkpoint(out)
    assert path == ckpts[1][1] and meta["iteration"] == 7, (path, meta)
    truncate_file(ckpts[1][1], 0.3)
    assert load_latest_checkpoint(out) is None
    print("PASS corrupt: bit-flipped latest fell back to the previous good "
          "checkpoint; truncated survivors are rejected, not mis-loaded")


_NAN_CHILD_SRC = _TRAIN_SRC + r"""
# inject a non-finite gradient batch at iteration NAN_AT via the objective
booster = build(12, -1, nan_policy=os.environ["NAN_POLICY"])
nan_at = int(os.environ["NAN_AT"])
obj = booster.objective
orig = obj.get_gradients
state = {"it": 0}

def poisoned(score):
    g, h = orig(score)
    import jax.numpy as jnp
    if state["it"] == nan_at:
        g = g.at[:7].set(jnp.nan)
    state["it"] += 1
    return g, h

obj.get_gradients = poisoned
booster._fuse_failed = True  # host objective hook: keep per-iteration path
try:
    booster.train()
except Exception as exc:
    print("RAISED %s" % type(exc).__name__)
    sys.exit(0)
import numpy as np
score = np.asarray(booster.train_score)
print("COMPLETED trees=%d finite=%s" % (booster.num_trees,
                                        bool(np.isfinite(score).all())))
"""


def scenario_nan_grad(workdir: str) -> None:
    """NaN gradients at iteration 5 under each nan_policy."""
    for policy, want in [("raise", "RAISED LightGBMError"),
                         ("skip_iter", "COMPLETED trees=12 finite=True"),
                         ("clip", "COMPLETED trees=12 finite=True")]:
        p = _run_child(_NAN_CHILD_SRC, {"NAN_POLICY": policy, "NAN_AT": "5"})
        assert want in p.stdout, (policy, p.stdout, p.stderr[-2000:])
        print("PASS nan-grad[%s]: %s" % (policy, want))


# ---- sigterm: preemption -> emergency checkpoint -> distinct exit code ----

_SIGTERM_CHILD_SRC = _TRAIN_SRC + r"""
# preempted like a real TPU worker: SIGTERM lands after the Nth chunk (the
# handler only sets a flag; the loop polls it at the next chunk boundary)
import signal
from lightgbm_tpu import resilience

resilience.install_preemption_handler()
booster = build(int(os.environ["TOTAL_ITERS"]), int(os.environ["SNAP_FREQ"]))
orig_chunk = booster.train_chunk
state = {"n": 0}
sig_after = int(os.environ["SIG_AFTER_CHUNKS"])

def chunk(k):
    r = orig_chunk(k)
    state["n"] += 1
    if state["n"] == sig_after:
        signal.raise_signal(signal.SIGTERM)
    return r

booster.train_chunk = chunk
try:
    booster.train(snapshot_out=os.environ["MODEL_OUT"])
except resilience.TrainingPreempted as exc:
    print("PREEMPTED iter=%d ckpt=%s" % (exc.iteration, exc.checkpoint_path))
    sys.exit(resilience.EXIT_PREEMPTED)
booster.save_model(os.environ["MODEL_OUT"])
print("TRAINED-TO-END")
"""


def scenario_sigterm(workdir: str) -> None:
    """SIGTERM mid-train -> emergency checkpoint -> bit-exact resume."""
    from lightgbm_tpu.checkpoint import list_checkpoints
    from lightgbm_tpu.resilience import EXIT_PREEMPTED
    total, sf = 20, 7
    ref = _uninterrupted_model(workdir, total, sf)
    out = os.path.join(workdir, "model_sig.txt")
    p = _run_child(_SIGTERM_CHILD_SRC, {
        "MODEL_OUT": out, "TOTAL_ITERS": str(total), "SNAP_FREQ": str(sf),
        "SIG_AFTER_CHUNKS": "2"})
    assert p.returncode == EXIT_PREEMPTED, \
        "expected exit %d (resumable), got %r: %s" % (
            EXIT_PREEMPTED, p.returncode, p.stdout + p.stderr[-2000:])
    assert "PREEMPTED" in p.stdout and "TRAINED-TO-END" not in p.stdout
    ckpts = list_checkpoints(out)
    assert ckpts, "no emergency checkpoint on disk"
    sys.path.insert(0, REPO)
    ns = {}
    exec(compile(_TRAIN_SRC, "<train>", "exec"), ns)
    booster = ns["build"](total, sf)
    resumed = booster.resume_from_checkpoint(out)
    assert 0 < resumed < total, resumed
    booster.train()
    assert booster.save_model_to_string() == ref, \
        "SIGTERM-preempted resume diverged from the uninterrupted run"
    print("PASS sigterm: exit code %d + emergency checkpoint at iter %d; "
          "resume is bit-exact" % (EXIT_PREEMPTED, resumed))


def scenario_quant_preempt(workdir: str) -> None:
    """SIGTERM mid-run under quantized-gradient training (round 22):
    exit 75 + emergency checkpoint, and the resumed model is
    byte-identical to the uninterrupted quantized run — the stochastic
    rounding is a stateless hash of (iteration, global row), so replayed
    chunk iterations re-quantize identically, like the bagging mask."""
    from lightgbm_tpu.checkpoint import list_checkpoints
    from lightgbm_tpu.resilience import EXIT_PREEMPTED
    total, sf = 20, 7
    qenv = {"HIST_PRECISION": "quantized"}
    ref_out = os.path.join(workdir, "ref_model_q.txt")
    p = _run_child(_KILL_CHILD_SRC, dict(qenv, **{
        "MODEL_OUT": ref_out, "TOTAL_ITERS": str(total),
        "SNAP_FREQ": str(sf), "KILL_AT_WRITE_N": "0"}))
    assert "TRAINED-TO-END" in p.stdout, p.stdout + p.stderr
    with open(ref_out) as fh:
        ref = fh.read()
    out = os.path.join(workdir, "model_qsig.txt")
    p = _run_child(_SIGTERM_CHILD_SRC, dict(qenv, **{
        "MODEL_OUT": out, "TOTAL_ITERS": str(total), "SNAP_FREQ": str(sf),
        "SIG_AFTER_CHUNKS": "2"}))
    assert p.returncode == EXIT_PREEMPTED, \
        "expected exit %d (resumable), got %r: %s" % (
            EXIT_PREEMPTED, p.returncode, p.stdout + p.stderr[-2000:])
    assert "PREEMPTED" in p.stdout and "TRAINED-TO-END" not in p.stdout
    assert list_checkpoints(out), "no emergency checkpoint on disk"
    sys.path.insert(0, REPO)
    ns = {}
    prev = os.environ.get("HIST_PRECISION")
    os.environ["HIST_PRECISION"] = "quantized"
    try:
        exec(compile(_TRAIN_SRC, "<train>", "exec"), ns)
        booster = ns["build"](total, sf)
        resumed = booster.resume_from_checkpoint(out)
        assert 0 < resumed < total, resumed
        booster.train()
    finally:
        if prev is None:
            os.environ.pop("HIST_PRECISION", None)
        else:
            os.environ["HIST_PRECISION"] = prev
    assert booster.save_model_to_string() == ref, \
        "quantized preempted resume diverged from the uninterrupted run"
    print("PASS quant-preempt: exit %d + checkpoint at iter %d; quantized "
          "resume is byte-identical" % (EXIT_PREEMPTED, resumed))


# ---- scrape-under-preempt: live exporter through the SIGTERM drill ----

_SCRAPE_CHILD_SRC = _TRAIN_SRC + r"""
# the round-14 live-plane drill: a telemetry run with the HTTP exporter
# up, scraped at three defined points — mid-train (healthy), right after
# the SIGTERM flag is raised but before the chunk-boundary poll consumes
# it (/healthz must already say draining), and right before the preempted
# exit (/summary.json must match what finalize writes to disk).
import json as _json
import signal
import urllib.request
from lightgbm_tpu import obs, resilience
from lightgbm_tpu.obs.exporter import start_exporter

resilience.install_preemption_handler()
tele = obs.configure(out=os.environ["TELEMETRY_OUT"], freq=1,
                     entry="scrape-drill")
exp = start_exporter(tele, port=0)  # ephemeral; the child self-scrapes
base = "http://127.0.0.1:%d" % exp.port

def get(path):
    return urllib.request.urlopen(base + path, timeout=10).read().decode()

booster = build(int(os.environ["TOTAL_ITERS"]), int(os.environ["SNAP_FREQ"]))
orig_chunk = booster.train_chunk
state = {"n": 0}
scrapes = {}

def chunk(k):
    r = orig_chunk(k)
    state["n"] += 1
    if state["n"] == 1:
        scrapes["healthz_mid"] = get("/healthz")
        scrapes["metrics_mid"] = get("/metrics")
    if state["n"] == 2:
        signal.raise_signal(signal.SIGTERM)
        # flag set, not yet polled: the NEXT boundary drains — the live
        # plane must already report it
        scrapes["healthz_draining"] = get("/healthz")
    return r

booster.train_chunk = chunk
try:
    booster.train(snapshot_out=os.environ["MODEL_OUT"])
except resilience.TrainingPreempted as exc:
    scrapes["summary_final"] = get("/summary.json")
    with open(os.environ["SCRAPES_OUT"], "w") as fh:
        _json.dump(scrapes, fh)
    from lightgbm_tpu.obs.report import finalize_run
    finalize_run(tele)
    obs.disable()
    print("PREEMPTED iter=%d" % exc.iteration)
    sys.exit(resilience.EXIT_PREEMPTED)
print("TRAINED-TO-END")
"""


def scenario_scrape_under_preempt(workdir: str) -> None:
    """SIGTERM drill with a live exporter: /healthz flips ok -> draining
    when the flag lands, /metrics stays well-formed Prometheus text, exit
    code is 75, and the final on-disk summary is consistent with the last
    live scrape."""
    from lightgbm_tpu.resilience import EXIT_PREEMPTED
    out = os.path.join(workdir, "model_scrape.txt")
    t_out = os.path.join(workdir, "scrape_drill.jsonl")
    scrapes_out = os.path.join(workdir, "scrapes.json")
    p = _run_child(_SCRAPE_CHILD_SRC, {
        "MODEL_OUT": out, "TOTAL_ITERS": "20", "SNAP_FREQ": "7",
        "TELEMETRY_OUT": t_out, "SCRAPES_OUT": scrapes_out})
    assert p.returncode == EXIT_PREEMPTED, \
        "expected exit %d (resumable), got %r: %s" % (
            EXIT_PREEMPTED, p.returncode, p.stdout + p.stderr[-2000:])
    with open(scrapes_out) as fh:
        scrapes = json.load(fh)
    healthy = json.loads(scrapes["healthz_mid"])
    assert healthy["status"] == "ok", healthy
    draining = json.loads(scrapes["healthz_draining"])
    assert draining["status"] == "draining", draining
    assert draining["preemption_requested"] is True, draining
    metrics = scrapes["metrics_mid"]
    assert "# TYPE lgbm_tpu_" in metrics, metrics[:200]
    assert "lgbm_tpu_run_recompiles" in metrics, metrics[:200]
    assert "lgbm_tpu_chunk_dispatch_s_count" in metrics, metrics[:400]
    # the last live scrape and the finalized artifact describe the SAME
    # run state: no chunks trained between them, preemption counted
    live = json.loads(scrapes["summary_final"])
    with open(t_out + ".summary.json") as fh:
        final = json.load(fh)
    live_chunks = live["histograms"]["chunk_dispatch_s"]["count"]
    final_chunks = final["histograms"]["chunk_dispatch_s"]["count"]
    assert live_chunks == final_chunks, (live_chunks, final_chunks)
    assert final["resilience"]["preemptions"] == 1, final["resilience"]
    assert live["resilience"]["preemptions"] == 1, live["resilience"]
    print("PASS scrape-under-preempt: /healthz ok -> draining at the "
          "SIGTERM flag, well-formed /metrics mid-train, exit %d, final "
          "summary consistent with the last scrape (%d chunks)"
          % (EXIT_PREEMPTED, final_chunks))


# ---- hang: stalled dispatch -> watchdog abort + diagnostic artifact ----

_HANG_CHILD_SRC = _TRAIN_SRC + r"""
# a dead-peer collective stand-in: the cached fused-chunk program is
# replaced with a sleeper AFTER one healthy chunk ran under the armed
# watchdog (completing a section = the compiled program is proven cached,
# so the hung dispatch is held to the PLAIN timeout, not the
# first-dispatch compile grace), so the next dispatch blocks forever
# inside the watch section
import time
from lightgbm_tpu import resilience

booster = build(12, -1)
resilience.start_watchdog(float(os.environ["WD_TIMEOUT"]),
                          artifact=os.environ["STALL_ARTIFACT"])
booster.train_chunk(4)  # healthy: compiles + caches + completes a section
for key in list(booster._fused_cache):
    booster._fused_cache[key] = lambda *a, **k: time.sleep(3600)
print("WATCHDOG-ARMED %f" % time.time(), flush=True)
booster.train()  # hangs; the watchdog aborts with EXIT_STALLED
print("UNREACHABLE")
"""


def scenario_hang(workdir: str) -> None:
    """Stalled dispatch -> watchdog abort within 2x timeout + artifact."""
    from lightgbm_tpu.resilience import EXIT_STALLED
    art = os.path.join(workdir, "stall.json")
    timeout_s = 2.0
    p = _run_child(_HANG_CHILD_SRC, {"WD_TIMEOUT": str(timeout_s),
                                     "STALL_ARTIFACT": art})
    assert p.returncode == EXIT_STALLED, \
        "expected exit %d (stalled), got %r: %s" % (
            EXIT_STALLED, p.returncode, p.stdout + p.stderr[-2000:])
    assert "UNREACHABLE" not in p.stdout
    armed = float(p.stdout.split("WATCHDOG-ARMED", 1)[1].split()[0])
    with open(art) as fh:
        diag = json.load(fh)
    assert diag["section"] == "fused_train_chunk", diag
    assert diag["stall_s"] >= timeout_s, diag
    detect = diag["ts"] - armed
    assert detect < 2 * timeout_s, \
        "watchdog took %.1f s to abort (bar: < %.1f s)" % (detect,
                                                           2 * timeout_s)
    assert "devices" in diag and "recompiles" in diag, diag
    print("PASS hang: watchdog aborted the stalled dispatch in %.1f s "
          "(< 2x timeout %.1f s) with diagnostics at %s"
          % (detect, timeout_s, art))


# ---- enospc: disk-full checkpoints skipped, transient EIO retried ----

_ENOSPC_CHILD_SRC = _TRAIN_SRC + r"""
# filesystem faults scoped to the PERIODIC durability writes (checkpoint +
# model snapshot): "enospc" injects persistent disk-full, "eio-once" one
# transient error per path (must be absorbed by the retry policy)
import errno
from lightgbm_tpu.utils import file_io

mode = os.environ["IO_FAULT"]
seen = set()

def fault(stage, path):
    if stage != "written":
        return
    if ".ckpt_iter_" not in path and ".snapshot_iter_" not in path:
        return
    if mode == "enospc":
        raise OSError(errno.ENOSPC, "No space left on device (injected)")
    if path not in seen:
        seen.add(path)
        raise OSError(errno.EIO, "Input/output error (injected)")

file_io.set_fault_hook(fault)
booster = build(int(os.environ["TOTAL_ITERS"]), int(os.environ["SNAP_FREQ"]))
booster.train(snapshot_out=os.environ["MODEL_OUT"])
file_io.set_fault_hook(None)
booster.save_model(os.environ["MODEL_OUT"])
from lightgbm_tpu.checkpoint import list_checkpoints
print("COMPLETED trees=%d ckpts=%d retries=%d"
      % (booster.num_trees, len(list_checkpoints(os.environ["MODEL_OUT"])),
         file_io.io_retry_count()))
"""


def scenario_enospc(workdir: str) -> None:
    """Checkpoint writes hit disk-full / flaky-mount faults; training
    continues (skip vs retry per the errno classification)."""
    total, sf = 20, 7
    # persistent ENOSPC: every periodic checkpoint/snapshot is skipped with
    # a warning; the run itself completes and the final model lands
    out = os.path.join(workdir, "model_ns.txt")
    p = _run_child(_ENOSPC_CHILD_SRC, {
        "MODEL_OUT": out, "TOTAL_ITERS": str(total), "SNAP_FREQ": str(sf),
        "IO_FAULT": "enospc"})
    assert "COMPLETED trees=%d ckpts=0" % total in p.stdout, \
        p.stdout + p.stderr[-2000:]
    assert os.path.exists(out), "final model missing"
    print("PASS enospc[skip]: disk-full checkpoints skipped, training "
          "completed, final model written")
    # transient EIO: the bounded jittered retry absorbs one failure per
    # path — all checkpoints land and the retry counter shows the work
    out2 = os.path.join(workdir, "model_eio.txt")
    p = _run_child(_ENOSPC_CHILD_SRC, {
        "MODEL_OUT": out2, "TOTAL_ITERS": str(total), "SNAP_FREQ": str(sf),
        "IO_FAULT": "eio-once"})
    assert "COMPLETED trees=%d ckpts=2" % total in p.stdout, \
        p.stdout + p.stderr[-2000:]
    assert "retries=0" not in p.stdout.split("COMPLETED", 1)[1]
    print("PASS enospc[retry]: transient EIO absorbed by retry; all "
          "checkpoints landed")


# ---- level-preempt: the round-12 level-batched dispatch under the same
# preemption drill (SIGTERM -> emergency checkpoint -> bit-exact resume) ----

_LEVEL_TRAIN_SRC = r"""
import os, sys, signal
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# engage the fused Pallas path (interpret mode) off-TPU so
# tree_grow_mode=level actually dispatches level-batched launches
os.environ["LIGHTGBM_TPU_PALLAS_INTERPRET"] = "1"

def build(n_iter, snapshot_freq):
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.metric.metric import create_metrics
    from lightgbm_tpu.objective import create_objective
    rng = np.random.RandomState(0)
    X = rng.uniform(-2, 2, size=(400, 5))
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
         + 0.1 * rng.normal(size=400)).astype(np.float32)
    cfg = Config(objective="regression", num_leaves=8, max_depth=3,
                 min_data_in_leaf=5, verbosity=-1, num_iterations=n_iter,
                 snapshot_freq=snapshot_freq, metric_freq=4,
                 tree_grow_mode="level", trees_per_chunk=2)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                   min_data_in_leaf=cfg.min_data_in_leaf)
    booster = create_boosting(cfg.boosting, cfg,
                              ds, create_objective(cfg.objective, cfg))
    booster.add_train_metrics(create_metrics(cfg.metric, cfg))
    assert booster.learner.effective_grow_mode() == "level", \
        "level mode must engage under LIGHTGBM_TPU_PALLAS_INTERPRET"
    return booster
"""

_LEVEL_CHILD_SRC = _LEVEL_TRAIN_SRC + r"""
from lightgbm_tpu import resilience
resilience.install_preemption_handler()
booster = build(int(os.environ["TOTAL_ITERS"]), int(os.environ["SNAP_FREQ"]))
sig_after = int(os.environ["SIG_AFTER_CHUNKS"])
if sig_after:
    orig_chunk = booster.train_chunk
    state = {"n": 0}

    def chunk(k):
        r = orig_chunk(k)
        state["n"] += 1
        if state["n"] == sig_after:
            signal.raise_signal(signal.SIGTERM)
        return r

    booster.train_chunk = chunk
try:
    booster.train(snapshot_out=os.environ["MODEL_OUT"])
except resilience.TrainingPreempted as exc:
    print("PREEMPTED iter=%d" % exc.iteration)
    sys.exit(resilience.EXIT_PREEMPTED)
booster.save_model(os.environ["MODEL_OUT"])
print("TRAINED-TO-END")
"""


def scenario_level_preempt(workdir: str) -> None:
    """tree_grow_mode=level (+ trees_per_chunk) under the preemption drill:
    the level-batched dispatch must checkpoint at a chunk boundary and
    resume bit-exact, proving the round-12 dispatch shape holds the same
    checkpoint/preemption invariants as the leaf-wise path."""
    from lightgbm_tpu.resilience import EXIT_PREEMPTED
    total, sf = 8, 3
    ref_out = os.path.join(workdir, "level_ref.txt")
    p = _run_child(_LEVEL_CHILD_SRC, {
        "MODEL_OUT": ref_out, "TOTAL_ITERS": str(total),
        "SNAP_FREQ": str(sf), "SIG_AFTER_CHUNKS": "0"})
    assert "TRAINED-TO-END" in p.stdout, p.stdout + p.stderr[-2000:]
    with open(ref_out) as fh:
        ref = fh.read()
    out = os.path.join(workdir, "level_model.txt")
    p = _run_child(_LEVEL_CHILD_SRC, {
        "MODEL_OUT": out, "TOTAL_ITERS": str(total), "SNAP_FREQ": str(sf),
        "SIG_AFTER_CHUNKS": "1"})
    assert p.returncode == EXIT_PREEMPTED, \
        "expected exit %d, got %r: %s" % (EXIT_PREEMPTED, p.returncode,
                                          p.stdout + p.stderr[-2000:])
    assert "PREEMPTED" in p.stdout
    sys.path.insert(0, REPO)
    os.environ["LIGHTGBM_TPU_PALLAS_INTERPRET"] = "1"
    try:
        ns = {}
        exec(compile(_LEVEL_TRAIN_SRC, "<level-train>", "exec"), ns)
        booster = ns["build"](total, sf)
        resumed = booster.resume_from_checkpoint(out)
        assert 0 < resumed < total, resumed
        booster.train()
        got = booster.save_model_to_string()
    finally:
        os.environ.pop("LIGHTGBM_TPU_PALLAS_INTERPRET", None)
    assert got == ref, \
        "level-mode preempted resume diverged from the uninterrupted run"
    print("PASS level-preempt: level-batched dispatch preempts at the chunk "
          "boundary and resumes bit-exact (resumed at iter %d)" % resumed)


# ---- ingest-preempt: SIGTERM mid-pass-2 of the streaming loader ----

_INGEST_CHILD_SRC = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import hashlib
import signal
import numpy as np
from lightgbm_tpu import resilience
from lightgbm_tpu.config import Config
from lightgbm_tpu.io import parser as parser_mod
from lightgbm_tpu.io.loader import DatasetLoader

resilience.install_preemption_handler()
sig_after = int(os.environ["SIG_AFTER_CHUNKS"])
orig_stream = parser_mod.stream_file

def stream(*a, **kw):
    # SIGTERM lands after the Nth pass-2 chunk leaves the parser (possibly
    # from the prefetch producer thread -- raise_signal still routes the
    # Python-level handler to the main thread, whose flag the bin loop
    # polls at the next chunk boundary)
    n = 0
    for chunk in orig_stream(*a, **kw):
        yield chunk
        n += 1
        if sig_after and n == sig_after:
            signal.raise_signal(signal.SIGTERM)

parser_mod.stream_file = stream
cfg = Config(objective="regression", num_leaves=15, min_data_in_leaf=5,
             num_iterations=10, verbosity=-1, max_bin=63,
             data_chunk_rows=int(os.environ["CHUNK_ROWS"]),
             save_binary=True)
loader = DatasetLoader(cfg)
try:
    ds = loader.load_from_file(os.environ["DATA_PATH"])
except resilience.TrainingPreempted:
    print("PREEMPTED-IN-INGEST")
    sys.exit(resilience.EXIT_PREEMPTED)
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.metric.metric import create_metrics
from lightgbm_tpu.objective import create_objective
booster = create_boosting(cfg.boosting, cfg, ds,
                          create_objective(cfg.objective, cfg))
booster.add_train_metrics(create_metrics(cfg.metric, cfg))
booster.train()
sha = hashlib.sha256(booster.save_model_to_string().encode()).hexdigest()
print("MODEL-SHA %s" % sha)
print("INGESTED-AND-TRAINED")
"""


def scenario_ingest_preempt(workdir: str) -> None:
    """SIGTERM mid-pass-2 of streaming ingest: exit EXIT_PREEMPTED with no
    partial binary store on disk; the rerun re-ingests from the raw file and
    trains bit-exact (ingest holds no checkpoint state -- recovery IS the
    rerun, which is why the store write must be all-or-nothing)."""
    import numpy as np
    from lightgbm_tpu.resilience import EXIT_PREEMPTED
    rng = np.random.RandomState(11)
    n = 3000
    x = rng.normal(size=(n, 8)).round(4)
    y = (x[:, 0] - x[:, 1] + 0.1 * rng.normal(size=n)).round(4)
    data = os.path.join(workdir, "ingest_train.csv")
    np.savetxt(data, np.column_stack([y, x]), fmt="%.4f", delimiter=",")
    env = {"DATA_PATH": data, "CHUNK_ROWS": "500"}

    def model_sha(p):
        return [ln for ln in p.stdout.splitlines()
                if ln.startswith("MODEL-SHA")][0]

    # reference: uninterrupted streaming ingest + train
    p = _run_child(_INGEST_CHILD_SRC, dict(env, SIG_AFTER_CHUNKS="0"))
    assert "INGESTED-AND-TRAINED" in p.stdout, p.stdout + p.stderr[-2000:]
    ref = model_sha(p)
    assert os.path.exists(data + ".bin"), "save_binary did not land"
    os.remove(data + ".bin")

    # preempt after 2 of 6 pass-2 chunks
    p = _run_child(_INGEST_CHILD_SRC, dict(env, SIG_AFTER_CHUNKS="2"))
    assert p.returncode == EXIT_PREEMPTED, \
        "expected exit %d (resumable), got %r: %s" % (
            EXIT_PREEMPTED, p.returncode, p.stdout + p.stderr[-2000:])
    assert "PREEMPTED-IN-INGEST" in p.stdout
    assert "INGESTED-AND-TRAINED" not in p.stdout
    partial = [f for f in os.listdir(workdir) if ".bin" in f]
    assert not partial, "partial binary store on disk: %r" % partial

    # rerun re-ingests from the raw file; model is bit-exact vs the reference
    p = _run_child(_INGEST_CHILD_SRC, dict(env, SIG_AFTER_CHUNKS="0"))
    assert "INGESTED-AND-TRAINED" in p.stdout, p.stdout + p.stderr[-2000:]
    assert model_sha(p) == ref, \
        "post-preempt re-ingest trained a different model"
    assert os.path.exists(data + ".bin")
    print("PASS ingest-preempt: exit code %d mid-pass-2, no partial store; "
          "re-ingest trains bit-exact" % EXIT_PREEMPTED)


# ---- swap-under-load: hot-swap a resident model mid-traffic (round 13) ----

def scenario_swap_under_load(workdir: str) -> None:
    """The serving tier's republish drill: two resident models under
    concurrent request threads, one hot-swapped mid-traffic.  Asserts ZERO
    dropped requests (every accepted future resolves, each bit-exact vs the
    generation that served it), ZERO steady-state recompiles after warmup
    (the swap republish is a pure jit-cache hit — premise-checked by
    comparing stacked shapes), and the old model's predictor entries fully
    dropped once its in-flight batches drained."""
    import threading

    import numpy as np
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.core.predict_fused import FusedPredictor
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    from lightgbm_tpu.obs import recompile
    from lightgbm_tpu.serving import Server

    def train(seed):
        rng = np.random.RandomState(seed)
        X = rng.uniform(-2, 2, size=(800, 6)).astype(np.float32)
        y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
             + 0.1 * rng.normal(size=800)).astype(np.float64)
        cfg = Config(objective="regression", num_leaves=8,
                     min_data_in_leaf=5, verbosity=-1, num_iterations=10)
        ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                       min_data_in_leaf=cfg.min_data_in_leaf)
        b = create_boosting(cfg.boosting, cfg, ds,
                            create_objective(cfg.objective, cfg))
        for _ in range(10):
            b.train_one_iter()
        return b, X

    bA, XA = train(0)
    bB, XB = train(1)
    bB2, _ = train(2)
    fpA, fpB, fpB2 = (FusedPredictor(b.models) for b in (bA, bB, bB2))
    # premise for the zero-recompile assertion: the replacement stacks to
    # the SAME ensemble shapes, so the swap is a pure jit-cache hit
    assert [a.shape for a in fpB2.ens] == [a.shape for a in fpB.ens], \
        "replacement model stacked to different shapes; adjust training"
    sizes = (1, 17, 64, 200)
    refs = {"a": {n: fpA(XA[:n]) for n in sizes}}
    refs_b_old = {n: fpB(XB[:n]) for n in sizes}
    refs_b_new = {n: fpB2(XB[:n]) for n in sizes}

    srv = Server(max_batch_wait_us=500)
    srv.register("a", bA)
    srv.register("b", bB)
    # warm every bucket the traffic can coalesce into: request sizes reach
    # the 128/1024 rungs directly, and 4 threads x 2-outstanding x 200 rows
    # of backlog can merge into the 8192 rung
    for name, X in (("a", XA), ("b", XB)):
        for n in sizes:
            srv.predict(name, X[:n], raw_score=True)
        srv.predict(name, np.zeros((1500, X.shape[1]), np.float32),
                    raw_score=True)
    base = recompile.total()
    old_entry = srv.registry._resident["b"]

    results = []
    res_lock = threading.Lock()

    def traffic(tid):
        # closed-loop with a 2-deep pipeline per thread: enough concurrency
        # to overlap the swap, bounded backlog so the coalescer stays inside
        # the warmed rungs
        rng = np.random.RandomState(100 + tid)
        outstanding = []
        for i in range(60):
            name = "a" if (i + tid) % 2 == 0 else "b"
            n = int(sizes[rng.randint(len(sizes))])
            X = XA if name == "a" else XB
            fut = srv.submit(name, X[:n], raw_score=True)
            with res_lock:
                results.append((name, n, fut))
            outstanding.append(fut)
            if len(outstanding) >= 2:
                outstanding.pop(0).result()

    threads = [threading.Thread(target=traffic, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    # gate the swap on a traffic MILESTONE, not wall clock: with >= 20% of
    # the 240 requests submitted, >= 180 are still to come, so requests are
    # guaranteed on both sides of the republish on any machine speed
    deadline = time.time() + 120
    while True:
        with res_lock:
            submitted = len(results)
        if submitted >= 48:
            break
        assert time.time() < deadline, "traffic stalled before the swap"
        time.sleep(0.002)
    srv.swap("b", bB2, warm=(128, 1024, 8192))  # the mid-traffic republish
    for t in threads:
        t.join()
    srv.close()

    stats = srv.stats()
    assert stats["dropped"] == 0 and stats["failed"] == 0, stats
    assert stats["completed"] == stats["submitted"] == len(results) + \
        2 * (len(sizes) + 1), stats
    mismatches = served_old = served_new = 0
    for name, n, fut in results:
        got = fut.result(timeout=60)
        if name == "a":
            ok = np.array_equal(got, refs["a"][n])
        else:
            old = np.array_equal(got, refs_b_old[n])
            new = np.array_equal(got, refs_b_new[n])
            served_old += old
            served_new += new
            ok = old or new
        mismatches += not ok
    assert mismatches == 0, "%d responses matched neither generation" \
        % mismatches
    assert served_new > 0, "no request reached the swapped-in model"
    delta = recompile.total() - base
    assert delta == 0, "swap-under-load recompiled %d times after warmup" \
        % delta
    assert old_entry.retired and not old_entry._preds and \
        old_entry.inflight == 0, "old model not fully evicted after swap"
    assert srv.registry.stats()["swaps"] == 1
    print("PASS swap-under-load: %d requests (%d on the old generation, %d "
          "on the new) served bit-exact with 0 drops, 0 steady-state "
          "recompiles; old predictor entries dropped"
          % (len(results), served_old, served_new))


# ---- drift-swap: quality baseline + generation follow the hot-swap ----

def scenario_drift_swap(workdir: str) -> None:
    """Quality-plane provenance under a mid-traffic hot-swap: the
    replacement model trained on a SHIFTED feature-0 distribution, traffic
    stays on the OLD distribution.  Old-generation requests (including
    ones submitted before the flip but dispatched after) must score
    against the old baseline (PSI ~ 0 everywhere); the new generation
    must flag exactly feature 0 above the alert threshold; the generation
    gauge flips with the swap; 0 drops, 0 steady-state recompiles; and
    obs_report's died-run recovery rebuilds the quality block from the
    raw drift events alone."""
    import numpy as np
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lightgbm_tpu import obs
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    from lightgbm_tpu.obs import recompile
    from lightgbm_tpu.obs.exporter import render_prometheus
    from lightgbm_tpu.obs.quality import PSI_ALERT, PSI_WARN
    from lightgbm_tpu.serving import Server

    def train(seed, lo, hi):
        rng = np.random.RandomState(seed)
        X = rng.uniform(-2, 2, size=(800, 6)).astype(np.float32)
        X[:, 0] = rng.uniform(lo, hi, 800).astype(np.float32)
        y = (X[:, 1] * 2 + 0.1 * rng.normal(size=800)).astype(np.float64)
        cfg = Config(objective="regression", num_leaves=8,
                     min_data_in_leaf=5, verbosity=-1, num_iterations=10)
        ds = BinnedDataset.from_matrix(X, label=y, max_bin=63,
                                       min_data_in_leaf=cfg.min_data_in_leaf)
        b = create_boosting(cfg.boosting, cfg, ds,
                            create_objective(cfg.objective, cfg))
        for _ in range(10):
            b.train_one_iter()
        return b, X

    b_old, X = train(0, -2, 2)       # baseline distribution
    b_new, _ = train(2, 5, 9)        # replacement: feature 0 shifted
    jsonl = os.path.join(workdir, "drift_swap.jsonl")
    tele = obs.configure(out=jsonl, freq=1)
    srv = Server(max_batch_wait_us=0)
    try:
        srv.register("m", b_old)
        rng = np.random.RandomState(7)

        def req_rows():
            return X[rng.randint(0, len(X), 256)]

        # warm both request buckets, then pin the recompile baseline: the
        # timed window (traffic + swap) must compile NOTHING
        srv.predict("m", X[:1])
        srv.predict("m", req_rows())
        base_rc = recompile.total()

        # generation 1 gets a deterministic helping of matched traffic
        # (PSI noise scales ~ (groups-1)/rows; 3k rows keeps it far from
        # the warn bar), then a backlog straddles the flip — whichever
        # generation's entry a straddling request ACQUIRES at dispatch is
        # the one its drift attributes to
        for fut in [srv.submit("m", req_rows()) for _ in range(12)]:
            fut.result(timeout=120)
        pending = [srv.submit("m", req_rows()) for _ in range(6)]
        srv.swap("m", b_new, warm=(128, 1024))
        pending += [srv.submit("m", req_rows()) for _ in range(12)]
        for fut in pending:
            fut.result(timeout=120)
        stats = srv.stats()
        snap = tele.quality.snapshot()
        prom = render_prometheus(tele.registry.snapshot(), quality=snap)
    finally:
        srv.close()
        obs.disable()

    assert stats["dropped"] == 0 and stats["failed"] == 0, stats
    delta = recompile.total() - base_rc
    assert delta == 0, "drift-swap recompiled %d times after warmup" % delta
    gens = snap["generations"]["m"]
    assert set(gens) == {"1", "2"}, sorted(gens)
    g1, g2 = gens["1"], gens["2"]
    assert g1["rows"] > 0 and g2["rows"] > 0, (g1["rows"], g2["rows"])

    def psi_of(info, name):
        for f in info["features"]:
            if f["name"] == name:
                return f["psi"]
        raise AssertionError("feature %s missing from %r" % (name, info))

    # generation 1 served only its own training distribution: quiet
    for f in g1["features"]:
        assert f["psi"] < PSI_WARN, ("gen1 drifted", f)
    # generation 2: exactly the shifted feature alerts
    assert psi_of(g2, "Column_0") > PSI_ALERT, g2
    for f in g2["features"]:
        if f["name"] != "Column_0":
            assert f["psi"] < PSI_WARN, ("gen2 false positive", f)
    assert g2["level"] == "alert" and g1["level"] == "ok", (g1, g2)
    assert snap["models"]["m"]["generation"] == 2, snap["models"]["m"]
    assert 'lgbm_tpu_model_generation{model="m"} 2.0' in prom, prom
    assert 'lgbm_tpu_drift_psi{model="m",feature="Column_0"}' in prom

    # died-run recovery: the raw drift events alone rebuild the block
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from obs_report import summary_from_events
    from lightgbm_tpu.obs import iter_events
    rec = summary_from_events(iter_events(jsonl))
    q = rec.get("quality") or {}
    assert "m" in (q.get("models") or {}), sorted(q)
    assert q["models"]["m"]["generation"] == 2, q["models"]["m"]
    assert set(q.get("generations", {}).get("m", {})) == {"1", "2"}
    print("PASS drift-swap: gen1 quiet (psi_max %.3f), gen2 flags exactly "
          "the shifted feature (psi %.2f > %.2f), generation gauge flipped "
          "with the swap, 0 drops, 0 steady recompiles, died-run recovery "
          "intact" % (g1["psi_max"] or 0.0, psi_of(g2, "Column_0"),
                      PSI_ALERT))


# ---- stall-capture: the round-16 flight recorder under the hang drill ----

_STALL_CAPTURE_CHILD_SRC = _TRAIN_SRC + r"""
# the hang scenario with the forensics plane armed: a telemetry run with
# the flight recorder on.  The watchdog stall must emit an alert event,
# trigger EXACTLY ONE profiler capture (synchronously, BEFORE the abort,
# so the artifact exists when the supervisor reads exit 79), and still
# exit EXIT_STALLED.
import time
from lightgbm_tpu import obs, resilience

booster = build(12, -1)
tele = obs.configure(out=os.environ["TELE_OUT"], flight_recorder=True)
resilience.start_watchdog(float(os.environ["WD_TIMEOUT"]),
                          artifact=os.environ["STALL_ARTIFACT"])
booster.train_chunk(4)  # healthy: compiles + caches + completes a section
for key in list(booster._fused_cache):
    booster._fused_cache[key] = lambda *a, **k: time.sleep(3600)
print("ARMED", flush=True)
booster.train()  # hangs; watchdog -> alert + capture + EXIT_STALLED
print("UNREACHABLE")
"""


def scenario_stall_capture(workdir: str) -> None:
    """Watchdog fire with the flight recorder armed: capture artifact
    exists, alert event emitted, exit 79 unchanged."""
    import glob as _glob

    from lightgbm_tpu.obs import read_events
    from lightgbm_tpu.resilience import EXIT_STALLED
    tele_out = os.path.join(workdir, "stallcap.jsonl")
    art = os.path.join(workdir, "stallcap_stall.json")
    p = _run_child(_STALL_CAPTURE_CHILD_SRC, {
        "WD_TIMEOUT": "2.0", "STALL_ARTIFACT": art, "TELE_OUT": tele_out})
    assert p.returncode == EXIT_STALLED, \
        "expected exit %d (stalled), got %r: %s" % (
            EXIT_STALLED, p.returncode, p.stdout + p.stderr[-2000:])
    assert "UNREACHABLE" not in p.stdout
    assert os.path.exists(art), "stall diagnostics missing"
    # EXACTLY ONE capture artifact, in the run-scoped layout, with its
    # metadata stamp (the flight recorder is one-shot)
    caps = _glob.glob(os.path.join(tele_out + ".profiles", "capture_*"))
    assert len(caps) == 1, "expected 1 capture artifact, got %r" % caps
    assert os.path.exists(os.path.join(caps[0], "capture.json")), caps[0]
    # the torn-tail-tolerant event stream carries the whole incident:
    # stall -> alert -> capture
    kinds = {}
    alert = None
    for e in read_events(tele_out):
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
        if e["kind"] == "alert" and alert is None:
            alert = e
    for kind in ("watchdog_stall", "alert", "profile_capture"):
        assert kinds.get(kind), "no %r event in %s (%r)" % (kind, tele_out,
                                                            kinds)
    assert alert["rule"] == "watchdog_stall" \
        and alert["state"] == "firing", alert
    assert kinds["profile_capture"] == 1, kinds
    print("PASS stall-capture: watchdog stall emitted the alert event, "
          "fired exactly one flight-recorder capture (%s) and exited %d"
          % (os.path.basename(caps[0]), EXIT_STALLED))


# ---- online-preempt: SIGTERM the trainer mid-cycle under paced traffic
# (round 17): serving never tears, the rerun publishes the SAME next
# generation ----

_ONLINE_CHILD_SRC = r"""
import hashlib, os, signal, sys, threading, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from lightgbm_tpu import resilience, serve_and_train
from lightgbm_tpu.boosting import create_boosting
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.objective import create_objective

MODE = os.environ["ONLINE_MODE"]           # ref | kill | resume
PREFIX = os.environ["ONLINE_PREFIX"]

def base():
    rng = np.random.RandomState(0)
    X = rng.uniform(-2, 2, size=(400, 5))
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
         + 0.1 * rng.normal(size=400)).astype(np.float64)
    cfg = Config(objective="regression", num_leaves=15, min_data_in_leaf=5,
                 bagging_fraction=0.8, bagging_freq=1, verbosity=-1,
                 num_iterations=4, snapshot_freq=2, max_bin=63)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=63,
                                   min_data_in_leaf=5)
    b = create_boosting(cfg.boosting, cfg, ds,
                        create_objective(cfg.objective, cfg))
    b.train()  # bootstrap: 4 rounds
    return b, ds, X

def fresh(seed, n=160):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-2, 2, size=(n, 5))
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
         + 0.1 * rng.normal(size=n)).astype(np.float64)
    return X, y

def model_hash():
    with open(PREFIX) as fh:
        return hashlib.sha256(fh.read().encode()).hexdigest()[:16]

resilience.install_preemption_handler()
booster, ds, Xbase = base()
ctrl = serve_and_train(
    booster, train_set=ds, name="m",
    params={"objective": "regression", "verbosity": -1,
            "snapshot_freq": 2, "online_rounds": 4,
            "online_min_rows": 0, "online_interval_s": 0,
            "online_drift_trigger": False, "online_poll_s": 0.05,
            "max_batch_wait_us": 200},
    checkpoint_prefix=PREFIX, publish_out=PREFIX)
pool = Xbase[:64].astype(np.float32)
sizes = (1, 17, 64)

def refs():
    return {n: ctrl.predict(pool[:n], raw_score=True) for n in sizes}

def run_traffic(stop, out):
    # paced closed-loop traffic; responses are VALIDATED after the join
    # (a response served by a just-published generation must not race
    # the reference capture)
    rng = np.random.RandomState(7)
    while not stop.is_set():
        n = int(sizes[rng.randint(len(sizes))])
        out.append((n, ctrl.predict(pool[:n], raw_score=True)))
        time.sleep(0.002)

if MODE == "resume":
    # start() already loaded the published generation + the pending
    # window; the trainer thread finishes the preempted cycle
    deadline = time.time() + 120
    while ctrl.cycles < 1 and time.time() < deadline:
        if ctrl.preempted is not None:
            raise SystemExit("re-preempted on resume")
        time.sleep(0.05)
    assert ctrl.cycles >= 1, "resume never published"
    st = ctrl.stats()
    ctrl.close()
    assert st["serving"]["dropped"] == 0, st["serving"]
    print("RESUMED-HASH %s" % model_hash())
    sys.exit(0)

ref_list = [refs()]
W1 = fresh(11)
ctrl.ingest(*W1)
assert ctrl.run_cycle("drill"), "cycle 1 did not run"
ref_list.append(refs())
print("GEN2-HASH %s" % model_hash())

if MODE == "kill":
    orig_chunk = booster.train_chunk
    state = {"n": 0}
    def chunk(k):
        r = orig_chunk(k)
        state["n"] += 1
        if state["n"] == 1:
            signal.raise_signal(signal.SIGTERM)
        return r
    booster.train_chunk = chunk

stop = threading.Event()
results = []
threads = [threading.Thread(target=run_traffic, args=(stop, results))
           for _ in range(3)]
for t in threads:
    t.start()
W2 = fresh(12)
ctrl.ingest(*W2)
code = 0
try:
    ctrl.run_cycle("drill")
    ref_list.append(refs())
    print("GEN3-HASH %s" % model_hash())
except resilience.TrainingPreempted as exc:
    print("PREEMPTED iter=%d" % exc.iteration)
    code = resilience.EXIT_PREEMPTED
finally:
    stop.set()
    for t in threads:
        t.join()
st = ctrl.stats()
ctrl.close()
assert st["serving"]["dropped"] == 0, st["serving"]
bad = sum(1 for n, got in results
          if not any(np.array_equal(got, r[n]) for r in ref_list))
assert results and bad == 0, \
    "%d/%d responses matched no generation" % (bad, len(results))
print("TRAFFIC-OK n=%d dropped=%d" % (len(results),
                                      st["serving"]["dropped"]))
sys.exit(code)
"""


def scenario_online_preempt(workdir: str) -> None:
    """The round-17 train-while-serve preemption drill: SIGTERM lands in
    the middle of an online retrain cycle while paced traffic runs.  The
    trainer exits through the emergency-checkpoint path (exit 75), every
    response before/during/after stays bit-exact vs the generation that
    served it with zero drops, and the rerun resumes the persisted
    window + checkpoint and publishes the SAME next generation
    (model-hash equality vs an uninterrupted run)."""
    import glob as _glob

    from lightgbm_tpu.resilience import EXIT_PREEMPTED

    def marker(stdout, tag):
        for line in stdout.splitlines():
            if line.startswith(tag):
                return line.split()[1]
        raise AssertionError("no %r marker in:\n%s" % (tag, stdout))

    # uninterrupted reference: two explicit cycles, hashes per generation
    ref_prefix = os.path.join(workdir, "online_ref.txt")
    p = _run_child(_ONLINE_CHILD_SRC, {"ONLINE_MODE": "ref",
                                       "ONLINE_PREFIX": ref_prefix})
    assert p.returncode == 0, p.stdout + p.stderr[-2000:]
    assert "TRAFFIC-OK" in p.stdout, p.stdout
    ref_g2 = marker(p.stdout, "GEN2-HASH")
    ref_g3 = marker(p.stdout, "GEN3-HASH")

    # the kill run: SIGTERM after the first chunk of cycle 2
    prefix = os.path.join(workdir, "online_kill.txt")
    p = _run_child(_ONLINE_CHILD_SRC, {"ONLINE_MODE": "kill",
                                       "ONLINE_PREFIX": prefix})
    assert p.returncode == EXIT_PREEMPTED, \
        "expected exit %d (resumable), got %r: %s" % (
            EXIT_PREEMPTED, p.returncode, p.stdout + p.stderr[-2000:])
    assert "PREEMPTED" in p.stdout and "TRAFFIC-OK" in p.stdout, p.stdout
    assert marker(p.stdout, "GEN2-HASH") == ref_g2, \
        "generation 2 diverged before the preemption"
    # the cycle's durability files survived for the resume
    assert os.path.exists(prefix + ".online_window.npz"), \
        "persisted window missing"
    assert _glob.glob(prefix + ".ckpt_iter_*"), \
        "emergency checkpoint missing"

    # the rerun: resumes the window + checkpoint, publishes the SAME
    # next generation the uninterrupted run would have
    p = _run_child(_ONLINE_CHILD_SRC, {"ONLINE_MODE": "resume",
                                       "ONLINE_PREFIX": prefix})
    assert p.returncode == 0, p.stdout + p.stderr[-2000:]
    got = marker(p.stdout, "RESUMED-HASH")
    assert got == ref_g3, \
        "resumed generation %s != uninterrupted %s" % (got, ref_g3)
    assert not os.path.exists(prefix + ".online_window.npz"), \
        "window file not consumed by the resumed cycle"
    print("PASS online-preempt: SIGTERM mid-cycle under paced traffic -> "
          "exit %d with 0 drops and every response bit-exact per "
          "generation; rerun resumed the persisted window and published "
          "the same next generation (%s)" % (EXIT_PREEMPTED, ref_g3))


# ---- round 18: doctored kernel-plan cache -> analytic fallback, bit-exact ----

_PLAN_CHILD_SRC = r"""
import os, sys
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# engage the fused Pallas path in interpret mode so the plan's bucket
# ladder actually drives the split dispatch (CPU-only box)
os.environ["LIGHTGBM_TPU_PALLAS_INTERPRET"] = "1"

from lightgbm_tpu.utils.log import Log
warns = {"plan": 0}
orig_warning = Log.warning
def counting_warning(msg, *a):
    if "plan cache" in str(msg):
        warns["plan"] += 1
    orig_warning(msg, *a)
Log.warning = staticmethod(counting_warning)

import lightgbm_tpu as lgb
from lightgbm_tpu.plan import cache as plan_cache
from lightgbm_tpu.plan import state as plan_state

n = 4096
rng = np.random.RandomState(7)
X = rng.normal(size=(n, 8))
y = X[:, 0] * 1.5 + np.sin(X[:, 1]) + rng.normal(scale=0.1, size=n)
# the cache is engaged through the DEFAULT discovery location
# (JAX_COMPILATION_CACHE_DIR/plan_cache.json, set by the parent) — the
# params stay byte-identical across runs, so the saved model files can
# be compared whole
params = dict(objective="regression", num_leaves=8, num_iterations=2,
              min_data_in_leaf=2, max_bin=16, verbosity=-1)
booster = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=2)
booster.save_model(os.environ["MODEL_OUT"])
gbdt = booster._booster
print("BUCKET_PLAN=%r" % (gbdt.learner.bucket_plan,))
print("PROVENANCE=%s" % (gbdt.learner.plan.provenance
                         if gbdt.learner.plan is not None else None))
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # a second engagement of the same bad cache must count again but
    # NEVER warn again (the ONE-warning contract is process-wide)
    plan_state.configure(None)
print("FALLBACKS=%d WARNINGS=%d" % (plan_cache.fallback_count(),
                                    warns["plan"]))
print("TRAINED-TO-END")
"""


def scenario_plan_cache(workdir: str) -> None:
    """Doctored plan cache -> analytic fallback -> bit-exact completion.

    Three runs of the same fused-interpret training: (A) no cache — the
    analytic reference; (B) a VALID tuned cache whose ladder differs from
    analytic — must engage (bucket_plan installed, provenance tuned) and
    produce a byte-identical model (plans change dispatch only, never
    numerics); (C) a CORRUPT cache — must fall back to analytic with the
    counter bumped, warn exactly ONCE across two engagements, and again
    complete byte-identical."""
    from lightgbm_tpu.plan import cache as plan_cache
    from lightgbm_tpu.plan import planner

    def run(tag, cache_dir):
        out = os.path.join(workdir, "plan_model_%s.txt" % tag)
        env = {"MODEL_OUT": out}
        if cache_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        p = _run_child(_PLAN_CHILD_SRC, env)
        assert "TRAINED-TO-END" in p.stdout, p.stdout + p.stderr
        return out, p.stdout

    # (A) analytic reference
    out_a, log_a = run("analytic", None)
    assert "PROVENANCE=analytic" in log_a and "FALLBACKS=0" in log_a, log_a

    # (B) valid tuned cache: the one-size large-pipeline ladder — a real,
    # bit-exact-by-construction alternative to the analytic small+mid plan
    # max_bin=16 -> the learner's store is nibble-packed: the shape class
    # must carry packed=True or the tuned entry misses
    sc = planner.shape_class(4096, 8, 32, packed=True, device_kind="cpu")
    tuned_sched = ((False, 4096, None),)
    tuned = planner.analytic_plan(sc)._replace(
        bucket_plan=tuned_sched, level_ladder=tuned_sched,
        provenance="tuned")
    cache = plan_cache.PlanCache(device_kind="cpu")
    cache.put(sc, tuned)
    tuned_dir = os.path.join(workdir, "cache_tuned")
    os.makedirs(tuned_dir, exist_ok=True)
    cache.save(os.path.join(tuned_dir, "plan_cache.json"))
    out_b, log_b = run("tuned", tuned_dir)
    assert "PROVENANCE=tuned" in log_b, log_b
    assert "BUCKET_PLAN=((False, 4096, None),)" in log_b, log_b
    assert "FALLBACKS=0" in log_b and "WARNINGS=0" in log_b, log_b
    with open(out_a, "rb") as fa, open(out_b, "rb") as fb:
        assert fa.read() == fb.read(), \
            "tuned plan changed the model (must be bit-exact)"

    # (C) corrupt cache: fallback counted on BOTH engagements, ONE warning
    corrupt_dir = os.path.join(workdir, "cache_corrupt")
    os.makedirs(corrupt_dir, exist_ok=True)
    with open(os.path.join(corrupt_dir, "plan_cache.json"), "wb") as fh:
        fh.write(b'{"version": 1, "entries": not json at all')
    out_c, log_c = run("corrupt", corrupt_dir)
    assert "PROVENANCE=analytic" in log_c, log_c
    assert "BUCKET_PLAN=None" in log_c, log_c
    assert "FALLBACKS=2 WARNINGS=1" in log_c, log_c
    with open(out_a, "rb") as fa, open(out_c, "rb") as fc:
        assert fa.read() == fc.read(), \
            "corrupt-cache fallback changed the model (must be bit-exact)"
    print("PASS plan-cache: tuned cache engaged bit-exact; corrupt cache "
          "fell back to analytic plans (counted twice, warned once) and "
          "the run completed bit-exact")


# ---- contrib-under-swap: explanations traffic across a hot-swap (r19) ----

def scenario_contrib_swap(workdir: str) -> None:
    """Round 19's serving drill: MIXED score + pred_contrib traffic across
    a mid-traffic hot-swap.  The replacement is a leaf-value-perturbed
    republish of the same ensemble (the online refit shape: identical
    tree structure, different outputs — so score AND contrib programs are
    pure jit-cache hits).  Asserts ZERO dropped requests, every response
    — scores and [N, F+1] phi matrices alike — BIT-exact vs the
    generation that served it, and ZERO steady-state recompiles after
    warmup."""
    import threading

    import numpy as np
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    from lightgbm_tpu.obs import recompile
    from lightgbm_tpu.serving import Server

    rng = np.random.RandomState(5)
    X = rng.uniform(-2, 2, size=(800, 6)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
         + 0.1 * rng.normal(size=800)).astype(np.float64)
    cfg = Config(objective="regression", num_leaves=8,
                 min_data_in_leaf=5, verbosity=-1, num_iterations=10)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                   min_data_in_leaf=cfg.min_data_in_leaf)
    bA = create_boosting(cfg.boosting, cfg, ds,
                         create_objective(cfg.objective, cfg))
    for _ in range(10):
        bA.train_one_iter()
    # the republish: the SAME structure with perturbed leaf values (the
    # online refit shape) — contrib schedules stack to identical shapes,
    # so the swap is a pure jit-cache hit for score AND contrib programs
    bB = GBDT(cfg)
    bB.load_model_from_string(bA.save_model_to_string())
    for t in bB.models:
        t.leaf_value = t.leaf_value * 1.1
    ncol = bA.max_feature_idx + 2
    sizes = (1, 17, 64)
    # references through the SAME fused programs serving dispatches (the
    # host small-batch / host TreeSHAP paths agree only to rounding)
    from lightgbm_tpu.core.predict_fused import FusedPredictor
    fpA, fpB = FusedPredictor(bA.models), FusedPredictor(bB.models)
    refs = {
        ("a", "score"): {n: fpA(X[:n]) for n in sizes},
        ("b", "score"): {n: fpB(X[:n]) for n in sizes},
        ("a", "contrib"): {n: fpA.predict_contrib(X[:n], ncol)
                           for n in sizes},
        ("b", "contrib"): {n: fpB.predict_contrib(X[:n], ncol)
                           for n in sizes},
    }
    srv = Server(max_batch_wait_us=500)
    srv.register("m", bA)
    # warm every rung the mixed traffic can coalesce into, scores AND
    # contrib (4 threads x 2-outstanding x 64 rows stays under 1024)
    entry = srv.registry._resident["m"]
    entry.warm((128, 1024), contrib=True)
    for n in sizes:
        srv.predict("m", X[:n])
        srv.predict("m", X[:n], pred_contrib=True)
    base = recompile.total()

    results = []
    res_lock = threading.Lock()

    def traffic(tid):
        rng_t = np.random.RandomState(100 + tid)
        outstanding = []
        for i in range(50):
            n = int(sizes[rng_t.randint(len(sizes))])
            contrib = (i + tid) % 2 == 0
            fut = srv.submit("m", X[:n], raw_score=True,
                             pred_contrib=contrib)
            with res_lock:
                results.append((n, "contrib" if contrib else "score", fut))
            outstanding.append(fut)
            if len(outstanding) >= 2:
                outstanding.pop(0).result()

    threads = [threading.Thread(target=traffic, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    deadline = time.time() + 180
    while True:
        with res_lock:
            submitted = len(results)
        if submitted >= 40:
            break
        assert time.time() < deadline, "traffic stalled before the swap"
        time.sleep(0.002)
    srv.swap("m", bB, warm=(128, 1024), warm_contrib=True)
    for t in threads:
        t.join()
    srv.close()

    stats = srv.stats()
    assert stats["dropped"] == 0 and stats["failed"] == 0, stats
    served_old = served_new = mismatches = 0
    for n, mode, fut in results:
        got = fut.result(timeout=60)
        old = np.array_equal(got, refs[("a", mode)][n])
        new = np.array_equal(got, refs[("b", mode)][n])
        served_old += old
        served_new += new
        mismatches += not (old or new)
    assert mismatches == 0, \
        "%d responses matched neither generation" % mismatches
    assert served_new > 0, "no request reached the swapped-in model"
    n_contrib = sum(1 for _, m, _ in results if m == "contrib")
    assert n_contrib > 0, "no contrib traffic generated"
    delta = recompile.total() - base
    assert delta == 0, ("contrib-under-swap recompiled %d times after "
                        "warmup" % delta)
    print("PASS contrib-swap: %d requests (%d contrib) served bit-exact "
          "across the hot-swap (%d old / %d new generation), 0 drops, "
          "0 steady-state recompiles" % (len(results), n_contrib,
                                         served_old, served_new))


def scenario_precision_swap(workdir: str) -> None:
    """Round 20's serving drill: MIXED exact + bf16 traffic across a
    mid-traffic hot-swap.  The replacement is a leaf-value-perturbed
    republish of the same ensemble (identical tree structure, different
    outputs — exact AND bf16 programs are pure jit-cache hits).  Asserts
    ZERO dropped requests, every exact response BIT-exact vs the
    generation that served it, every bf16 response bit-exact vs that
    generation's bf16 program AND within the declared
    ``bf16_max_score_delta`` budget of its exact scores, and ZERO
    steady-state recompiles after warmup."""
    import json as _json
    import threading

    import numpy as np
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.objective import create_objective
    from lightgbm_tpu.obs import recompile
    from lightgbm_tpu.serving import Server

    with open(os.path.join(REPO, "PERF_BUDGETS.json")) as fh:
        budget = float(_json.load(fh)["budgets"]["bf16_max_score_delta"])

    rng = np.random.RandomState(7)
    X = rng.uniform(-2, 2, size=(800, 6)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2)
         + 0.1 * rng.normal(size=800)).astype(np.float64)
    cfg = Config(objective="regression", num_leaves=8,
                 min_data_in_leaf=5, verbosity=-1, num_iterations=10)
    ds = BinnedDataset.from_matrix(X, label=y, max_bin=cfg.max_bin,
                                   min_data_in_leaf=cfg.min_data_in_leaf)
    bA = create_boosting(cfg.boosting, cfg, ds,
                         create_objective(cfg.objective, cfg))
    for _ in range(10):
        bA.train_one_iter()
    # the republish: SAME structure, perturbed leaf values (the online
    # refit shape) — both tiers' programs are pure jit-cache hits
    bB = GBDT(cfg)
    bB.load_model_from_string(bA.save_model_to_string())
    for t in bB.models:
        t.leaf_value = t.leaf_value * 1.1
    sizes = (1, 17, 64)
    # per-generation, per-tier references through the SAME fused programs
    # serving dispatches: exact responses must be bit-exact vs the exact
    # program, bf16 responses bit-exact vs the bf16 program (it is
    # deterministic — lossy, not noisy)
    from lightgbm_tpu.core.predict_fused import FusedPredictor
    fps = {("a", "exact"): FusedPredictor(bA.models),
           ("b", "exact"): FusedPredictor(bB.models),
           ("a", "bf16"): FusedPredictor(bA.models, precision="bf16"),
           ("b", "bf16"): FusedPredictor(bB.models, precision="bf16")}
    refs = {k: {n: np.asarray(fp(X[:n])) for n in sizes}
            for k, fp in fps.items()}
    # the error budget holds per generation BEFORE the drill: a swap must
    # not be the thing that discovers an over-budget tier
    for gen in ("a", "b"):
        for n in sizes:
            worst = float(np.max(np.abs(refs[(gen, "exact")][n]
                                        - refs[(gen, "bf16")][n])))
            assert worst <= budget, \
                "gen %s bf16 delta %g exceeds budget %g" % (gen, worst,
                                                            budget)
    srv = Server(max_batch_wait_us=500)
    srv.register("m", bA)
    # warm every rung the mixed traffic can coalesce into, on BOTH tiers
    # (4 threads x 2-outstanding x 64 rows stays under 1024)
    entry = srv.registry._resident["m"]
    entry.warm((128, 1024), precisions=("exact", "bf16"))
    for n in sizes:
        srv.submit("m", X[:n], raw_score=True).result()
        srv.submit("m", X[:n], raw_score=True, precision="bf16").result()
    base = recompile.total()

    results = []
    res_lock = threading.Lock()

    def traffic(tid):
        rng_t = np.random.RandomState(200 + tid)
        outstanding = []
        for i in range(50):
            n = int(sizes[rng_t.randint(len(sizes))])
            tier = "bf16" if (i + tid) % 2 == 0 else "exact"
            fut = srv.submit("m", X[:n], raw_score=True, precision=tier)
            with res_lock:
                results.append((n, tier, fut))
            outstanding.append(fut)
            if len(outstanding) >= 2:
                outstanding.pop(0).result()

    threads = [threading.Thread(target=traffic, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    deadline = time.time() + 180
    while True:
        with res_lock:
            submitted = len(results)
        if submitted >= 40:
            break
        assert time.time() < deadline, "traffic stalled before the swap"
        time.sleep(0.002)
    srv.swap("m", bB, warm=(128, 1024),
             warm_precisions=("exact", "bf16"))
    for t in threads:
        t.join()
    srv.close()

    stats = srv.stats()
    assert stats["dropped"] == 0 and stats["failed"] == 0, stats
    served_old = served_new = mismatches = 0
    for n, tier, fut in results:
        got = np.asarray(fut.result(timeout=60))
        old = np.array_equal(got, refs[("a", tier)][n])
        new = np.array_equal(got, refs[("b", tier)][n])
        served_old += old
        served_new += new
        mismatches += not (old or new)
    assert mismatches == 0, \
        "%d responses matched neither generation's tier program" % mismatches
    assert served_new > 0, "no request reached the swapped-in model"
    n_bf16 = sum(1 for _, tier, _ in results if tier == "bf16")
    assert n_bf16 > 0, "no bf16 traffic generated"
    delta = recompile.total() - base
    assert delta == 0, ("precision-under-swap recompiled %d times after "
                        "warmup" % delta)
    print("PASS precision-swap: %d requests (%d bf16, budget %g) served "
          "across the hot-swap (%d old / %d new generation), 0 drops, "
          "0 steady-state recompiles" % (len(results), n_bf16, budget,
                                         served_old, served_new))


SCENARIOS = {"kill-write": scenario_kill_write,
             "precision-swap": scenario_precision_swap,
             "contrib-swap": scenario_contrib_swap,
             "plan-cache": scenario_plan_cache,
             "online-preempt": scenario_online_preempt,
             "stall-capture": scenario_stall_capture,
             "swap-under-load": scenario_swap_under_load,
             "drift-swap": scenario_drift_swap,
             "level-preempt": scenario_level_preempt,
             "ingest-preempt": scenario_ingest_preempt,
             "scrape-under-preempt": scenario_scrape_under_preempt,
             "corrupt": scenario_corrupt,
             "nan-grad": scenario_nan_grad,
             "sigterm": scenario_sigterm,
             "quant-preempt": scenario_quant_preempt,
             "hang": scenario_hang,
             "enospc": scenario_enospc}


def run_matrix(workdir: str, report_path: str) -> int:
    """Run every scenario, print a pass/fail table, write the JSON report.
    Returns the number of failures (process exit code)."""
    report = {}
    for name, fn in SCENARIOS.items():
        t0 = time.time()
        try:
            fn(workdir)
            report[name] = {"status": "pass",
                            "seconds": round(time.time() - t0, 2)}
        except BaseException as exc:  # noqa: BLE001 - report, don't die
            report[name] = {"status": "fail",
                            "seconds": round(time.time() - t0, 2),
                            "detail": "%s: %s" % (type(exc).__name__, exc)}
    from lightgbm_tpu.utils.file_io import atomic_write
    atomic_write(report_path, json.dumps(report, indent=1))
    print("\nfault matrix (%s):" % report_path)
    for name, r in report.items():
        print("  %-12s %-4s %6.1fs  %s" % (name, r["status"].upper(),
                                           r["seconds"],
                                           r.get("detail", "")))
    failures = sum(1 for r in report.values() if r["status"] != "pass")
    print("MATRIX %s (%d/%d passed)"
          % ("PASSED" if failures == 0 else "FAILED",
             len(report) - failures, len(report)))
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fault-injection harness for the checkpoint/resume + "
                    "resilience runtime (kill mid-write, corrupt/truncate, "
                    "NaN gradients, SIGTERM preemption, stalled-dispatch "
                    "watchdog, disk-full checkpoint writes)")
    ap.add_argument("scenario", nargs="?", default="all",
                    choices=["all"] + sorted(SCENARIOS))
    ap.add_argument("--matrix", action="store_true",
                    help="run every scenario and emit a JSON pass/fail "
                         "report instead of stopping at the first failure")
    ap.add_argument("--report", default=None,
                    help="matrix report path (default: "
                         "<workdir>/fault_matrix.json)")
    ap.add_argument("--workdir", default=None,
                    help="scratch directory (default: a fresh tempdir)")
    args = ap.parse_args(argv)
    import tempfile
    if args.workdir:
        os.makedirs(args.workdir, exist_ok=True)
    workdir = args.workdir or tempfile.mkdtemp(prefix="lgbm_fault_")
    sys.path.insert(0, REPO)
    if args.matrix:
        report = args.report or os.path.join(workdir, "fault_matrix.json")
        return 1 if run_matrix(workdir, report) else 0
    names = list(SCENARIOS) if args.scenario == "all" else [args.scenario]
    for name in names:
        SCENARIOS[name](workdir)
    print("ALL FAULT SCENARIOS PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
