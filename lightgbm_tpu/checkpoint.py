"""Fault-tolerant training checkpoints: versioned ``TrainState`` snapshots.

On TPU pods preemption is routine; a run that cannot resume *bit-exactly*
loses hours of work.  The model string alone is not enough — GOSS / DART
RNG streams (the bag and the feature mask are stateless), DART drop history,
early-stopping bookkeeping, CEGB paid-cost state and the score cache all feed future
iterations, so an ``init_model``-style resume silently diverges from the
uninterrupted run.  A checkpoint captures ALL of it:

  line 0   ``LGBMTPU-CKPT v1``
  line 1   JSON header: trainer meta (iteration, RNG states, ES state, ...)
           + an array manifest (name/dtype/shape) + model byte length
  ...      raw C-order array bytes, concatenated in manifest order
           (train_score, one score per valid set, CEGB state when active)
  ...      the model string (same text format ``save_model`` writes)
  trailer  ``CRC32 xxxxxxxx nnnnnnnnnnnn`` over everything above

Checkpoints are written atomically (tmp + fsync + rename,
utils/file_io.atomic_write) on the ``snapshot_freq`` boundary, retained
last-``snapshot_keep``, and discovered newest-first with per-file CRC
validation — a corrupt or truncated latest checkpoint falls back to the
previous good one instead of failing the resume.

Scores ride the checkpoint as *binary* f32 arrays rather than being replayed
from the model text: DART's dropout shrinks/re-adds old trees, so the
incremental f32 score sum is order-dependent and a replay of final leaf
values would differ in the last ulps — binary restore is what makes
``train(100)`` == ``train(40) -> kill -> resume -> 100`` exact.
"""
from __future__ import annotations

import glob
import json
import os
import re
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .utils.file_io import append_crc_trailer, atomic_write, check_crc_trailer
from .utils.log import LightGBMError, Log

CKPT_MAGIC = b"LGBMTPU-CKPT v1"
CKPT_VERSION = 1


class CheckpointError(LightGBMError):
    """A checkpoint failed validation (truncated, corrupt, or wrong version)."""


def checkpoint_path(prefix: str, iteration: int) -> str:
    return "%s.ckpt_iter_%d" % (prefix, iteration)


_CKPT_RE = re.compile(r"\.ckpt_iter_(\d+)$")


def list_checkpoints(prefix: str) -> List[Tuple[int, str]]:
    """All checkpoint files for ``prefix``, newest (highest iteration) first."""
    out = []
    for path in glob.glob(glob.escape(prefix) + ".ckpt_iter_*"):
        m = _CKPT_RE.search(path)
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out, reverse=True)


# ---- dataset identity ----

def mapper_digest(bin_mappers, crc: int = 0) -> int:
    """Fold every bin mapper (bounds, categories, types) into a CRC32.

    Shared by :func:`dataset_fingerprint` (resume identity) and
    ``parallel.distdata.schema_digest`` (pod-wide mapper agreement) — the
    sharded loader's "every rank froze the same bins" pin is exactly the
    mapper part of the resume fingerprint, with the per-rank row count
    deliberately left out."""
    for m in bin_mappers:
        crc = zlib.crc32(np.asarray(
            [int(m.num_bin), int(m.bin_type), int(m.missing_type),
             int(m.default_bin)], dtype=np.int64).tobytes(), crc)
        if m.bin_2_categorical:
            crc = zlib.crc32(np.asarray(m.bin_2_categorical,
                                        dtype=np.int64).tobytes(), crc)
        else:
            crc = zlib.crc32(np.asarray(m.bin_upper_bound,
                                        dtype=np.float64).tobytes(), crc)
    return crc


def dataset_fingerprint(ds) -> Dict[str, Any]:
    """Cheap identity of a ``BinnedDataset``: row/feature counts plus a
    CRC32 digest of every feature's bin mapper (bounds, categories, types).

    A checkpoint resumed against a *different* dataset silently trains
    garbage — the restored score caches describe rows that no longer
    exist; the fingerprint turns that into a hard error.  Deterministic
    for a given input (binning is deterministic), so rebuilding the same
    dataset in the resume process matches byte-for-byte.

    Host-sharded stores (loader ``shard`` stamp) additionally fold the
    shard bounds: rank 0's stripe of a 2-host run holds different rows
    than the same file loaded whole, and a resume that silently crossed
    that line would restore score caches for the wrong rows.  Unsharded
    datasets keep the exact pre-round-21 digest."""
    crc = zlib.crc32(np.asarray(
        [ds.num_data, ds.num_total_features], dtype=np.int64).tobytes())
    crc = mapper_digest(ds.bin_mappers, crc)
    out = {"num_rows": int(ds.num_data),
           "num_features": int(ds.num_total_features)}
    shard = getattr(ds, "shard", None)
    if shard:
        crc = zlib.crc32(np.asarray(
            [int(shard["rank"]), int(shard["num_machines"]),
             int(shard["begin"]), int(shard["end"]),
             int(shard["num_total"])], dtype=np.int64).tobytes(), crc)
        out["shard"] = {k: int(shard[k]) for k in
                        ("rank", "num_machines", "begin", "end", "num_total")}
    out["bin_digest"] = "%08x" % (crc & 0xFFFFFFFF)
    return out


# ---- RNG state (np.random.RandomState <-> JSON) ----

def encode_rng_state(rng: np.random.RandomState) -> Dict[str, Any]:
    name, keys, pos, has_gauss, cached = rng.get_state()
    return {"name": name, "keys": np.asarray(keys, np.uint32).tolist(),
            "pos": int(pos), "has_gauss": int(has_gauss),
            "cached": float(cached)}


def decode_rng_state(d: Dict[str, Any]) -> Tuple:
    return (str(d["name"]), np.asarray(d["keys"], dtype=np.uint32),
            int(d["pos"]), int(d["has_gauss"]), float(d["cached"]))


# ---- serialization ----

def serialize_state(meta: Dict[str, Any], arrays: Dict[str, np.ndarray],
                    model_str: str) -> bytes:
    """One self-validating blob: magic, JSON header, raw arrays, model text,
    CRC32+length trailer."""
    model_bytes = model_str.encode("utf-8")
    manifest = []
    chunks = []
    for name in sorted(arrays):
        a = np.ascontiguousarray(arrays[name])
        manifest.append({"name": name, "dtype": a.dtype.str,
                         "shape": list(a.shape)})
        chunks.append(a.tobytes())
    header = json.dumps({"version": CKPT_VERSION, "meta": meta,
                         "arrays": manifest,
                         "model_bytes": len(model_bytes)},
                        separators=(",", ":"))
    blob = b"".join([CKPT_MAGIC, b"\n", header.encode("utf-8"), b"\n"]
                    + chunks + [model_bytes])
    return append_crc_trailer(blob)


def deserialize_state(blob: bytes
                      ) -> Tuple[Dict[str, Any], Dict[str, np.ndarray], str]:
    """Inverse of :func:`serialize_state`; raises :class:`CheckpointError`
    naming the failing section."""
    try:
        payload = check_crc_trailer(blob)
    except ValueError as exc:
        raise CheckpointError(str(exc))
    nl0 = payload.find(b"\n")
    if nl0 < 0 or payload[:nl0] != CKPT_MAGIC:
        raise CheckpointError(
            "not a checkpoint file (magic %r missing)" % CKPT_MAGIC.decode())
    nl1 = payload.find(b"\n", nl0 + 1)
    if nl1 < 0:
        raise CheckpointError("checkpoint header line missing")
    try:
        header = json.loads(payload[nl0 + 1:nl1].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as exc:
        raise CheckpointError("checkpoint header unparseable: %s" % exc)
    if int(header.get("version", -1)) != CKPT_VERSION:
        raise CheckpointError("unsupported checkpoint version %r (this "
                              "build reads v%d)" % (header.get("version"),
                                                    CKPT_VERSION))
    off = nl1 + 1
    arrays: Dict[str, np.ndarray] = {}
    for spec in header["arrays"]:
        dt = np.dtype(spec["dtype"])
        shape = tuple(int(s) for s in spec["shape"])
        nbytes = dt.itemsize * int(np.prod(shape, dtype=np.int64))
        if off + nbytes > len(payload):
            raise CheckpointError("checkpoint array %r truncated"
                                  % spec["name"])
        arrays[spec["name"]] = np.frombuffer(
            payload[off:off + nbytes], dtype=dt).reshape(shape)
        off += nbytes
    model_bytes = int(header["model_bytes"])
    if off + model_bytes != len(payload):
        raise CheckpointError(
            "checkpoint model section length mismatch: header says %d bytes, "
            "%d present" % (model_bytes, len(payload) - off))
    model_str = payload[off:].decode("utf-8")
    return header["meta"], arrays, model_str


# ---- save / load / discover ----

# wall-clock time of the newest successful checkpoint write in this
# process: /healthz (obs/exporter.py) surfaces its age so an operator can
# see how much work a preemption right now would lose
_LAST_WRITE_TS: Optional[float] = None


def last_checkpoint_time() -> Optional[float]:
    """Unix time of this process's newest successful checkpoint write
    (None before the first one)."""
    return _LAST_WRITE_TS


def save_checkpoint(booster, prefix: str, keep: Optional[int] = None) -> str:
    """Capture the booster's full train state and write it atomically to
    ``<prefix>.ckpt_iter_<iteration>``; prune to the newest ``keep`` files
    (``snapshot_keep`` param when None; <= 0 keeps everything)."""
    import time

    from .obs.spans import span
    global _LAST_WRITE_TS
    t0 = time.perf_counter()
    ts0 = time.time()
    with span("checkpoint.write"):
        meta, arrays, model_str = booster.capture_train_state()
        path = checkpoint_path(prefix, int(meta["iteration"]))
        blob = serialize_state(meta, arrays, model_str)
        atomic_write(path, blob)
    _LAST_WRITE_TS = time.time()
    Log.info("Wrote checkpoint %s", path)
    from .obs import active as _telemetry_active
    tele = _telemetry_active()
    if tele is not None:
        from .obs import spans
        dt = time.perf_counter() - t0
        tele.histogram("checkpoint_write_s").observe(dt)
        tele.event("checkpoint_write", iteration=int(meta["iteration"]),
                   dt_s=dt, bytes=len(blob))
        # a span too: the write shows on the run's trace lifeline between
        # the train_chunk slices it interleaves with
        spans.record_span(tele, "checkpoint_write", t0=ts0, dur_s=dt,
                          iteration=int(meta["iteration"]))
    if keep is None:
        keep = int(getattr(booster.config, "snapshot_keep", 0))
    prune_checkpoints(prefix, keep)
    return path


def skip_io_failure(what: str, exc: OSError) -> None:
    """Record a skipped best-effort durability write: periodic snapshots
    are an optimization, not correctness — disk-full must not kill a
    healthy training run.  The previous checkpoint stays the resume point."""
    Log.warning("%s failed (%s); training continues — periodic durability "
                "writes are best-effort and the previous checkpoint remains "
                "the resume point", what, exc)
    from .obs import active as _telemetry_active
    tele = _telemetry_active()
    if tele is not None:
        tele.counter("checkpoint_skipped").inc()
        tele.event("checkpoint_skipped", what=what, error=str(exc)[:300])


def save_checkpoint_best_effort(booster, prefix: str,
                                keep: Optional[int] = None) -> Optional[str]:
    """:func:`save_checkpoint` with the periodic-write policy: transient
    faults were already retried inside ``atomic_write``; what still raises
    is fatal for THIS write (``ENOSPC``, permissions) but not for the run —
    log + count + return ``None`` so the training loop continues."""
    try:
        return save_checkpoint(booster, prefix, keep=keep)
    except OSError as exc:
        skip_io_failure("checkpoint write %s" % prefix, exc)
        return None


def load_checkpoint(path: str
                    ) -> Tuple[Dict[str, Any], Dict[str, np.ndarray], str]:
    try:
        with open(path, "rb") as fh:
            blob = fh.read()
    except OSError as exc:
        raise CheckpointError("cannot read checkpoint %s: %s" % (path, exc))
    return deserialize_state(blob)


def load_latest_checkpoint(prefix: str):
    """Newest checkpoint for ``prefix`` that VALIDATES; a corrupt/truncated
    latest falls back to the previous good one.  Returns
    ``(meta, arrays, model_str, path)`` or ``None`` when no usable
    checkpoint exists."""
    for it, path in list_checkpoints(prefix):
        try:
            meta, arrays, model_str = load_checkpoint(path)
        except CheckpointError as exc:
            Log.warning("Checkpoint %s failed validation (%s); falling back "
                        "to the previous one", path, exc)
            continue
        return meta, arrays, model_str, path
    return None


def prune_checkpoints(prefix: str, keep: int) -> None:
    """Bounded retention: drop all but the newest ``keep`` checkpoints (and
    model ``.snapshot_iter_*`` files) for ``prefix``.  ``keep <= 0`` keeps
    everything."""
    if keep <= 0:
        return
    for old_it, old_path in list_checkpoints(prefix)[keep:]:
        _unlink_quiet(old_path)
    snaps = []
    for path in glob.glob(glob.escape(prefix) + ".snapshot_iter_*"):
        m = re.search(r"\.snapshot_iter_(\d+)$", path)
        if m:
            snaps.append((int(m.group(1)), path))
    for old_it, old_path in sorted(snaps, reverse=True)[keep:]:
        _unlink_quiet(old_path)


def cleanup_checkpoints(prefix: str) -> None:
    """Remove ALL checkpoints for ``prefix`` — called after a run COMPLETES
    (final model saved): leftover checkpoints would make a rerun of the same
    command silently resume the finished run instead of training fresh.
    Model ``.snapshot_iter_*`` files are kept (they are ordinary models)."""
    for _, path in list_checkpoints(prefix):
        _unlink_quiet(path)


def _unlink_quiet(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


def restore_state(booster, state) -> int:
    """Restore an already-loaded ``(meta, arrays, model_str, path)`` tuple
    (from :func:`load_latest_checkpoint`) into ``booster`` and log it.
    Split from :func:`restore_checkpoint` for callers that must discover
    the checkpoint BEFORE attaching valid sets (cli.py task=train)."""
    import time

    from .obs.spans import span
    meta, arrays, model_str, path = state
    t0 = time.perf_counter()
    with span("checkpoint.restore"):
        booster.restore_train_state(meta, arrays, model_str)
    Log.info("Resumed training from checkpoint %s (iteration %d)",
             path, booster.iter_)
    from .obs import active as _telemetry_active
    tele = _telemetry_active()
    if tele is not None:
        dt = time.perf_counter() - t0
        tele.histogram("checkpoint_restore_s").observe(dt)
        tele.event("checkpoint_restore", iteration=int(meta["iteration"]),
                   dt_s=dt, path=path)
    return int(meta["iteration"])


def restore_checkpoint(booster, prefix: str) -> int:
    """Discover + validate + restore the latest good checkpoint for
    ``prefix`` into ``booster``.  Returns the restored iteration (0 when no
    usable checkpoint was found and the booster is untouched)."""
    found = load_latest_checkpoint(prefix)
    if found is None:
        return 0
    return restore_state(booster, found)
