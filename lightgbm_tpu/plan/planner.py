"""The unified kernel planner (round 18, ROADMAP item 4).

Four subsystems independently reinvented VMEM budgeting and pipeline
shape: ``partition.fused_bucket_plan`` (bucket variant / CHUNK / totals-k),
the round-12 ``level_plan`` ladder, the histogram layout chooser (factored
vs classic, grid-over-groups G, the 4 MiB accumulator gate) and
``predict_fused.tree_block`` (G sizing over the shape-bucket ladder).
Every constant in them was hand-tuned for v5e at one shape.  This module
folds all four into ONE typed :class:`Plan` produced from a
:class:`ShapeClass` — (rows, features, bins/packing, classes,
device_kind) — by either:

- the **analytic** planner (:func:`analytic_plan`): reproduces today's
  hand-tuned constants byte-for-byte.  Plans affect dispatch shape only,
  never numerics — every kernel variant is pinned bit-exact against the
  others (tests/test_partition_buckets.py, tests/test_predict_fused.py) —
  so swapping plans is performance-safe by construction; or
- a **tuned** entry from the persisted plan cache (``plan/cache.py``),
  written by the autotuner (``plan/autotune.py``) which microbenchmarks
  candidate tilings once per (shape-class, device_kind) and ranks them on
  the compile-accounting steady-median machinery (obs/compile.py).

Callers go through ``plan.state.resolve`` (the one entry point), which
adds pin/tuned-cache resolution and telemetry provenance stamping.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

from . import device_specs

# bump when Plan fields / semantics change: cache entries from another
# version fall back to analytic (plan/cache.py)
PLAN_SCHEMA_VERSION = 1

PROVENANCES = ("analytic", "tuned", "pinned")


class ShapeClass(NamedTuple):
    """The planning key.  ``n_rows`` is kept EXACT for analytic planning
    (bucket bounds compare against it); :func:`plan_key` bucketizes it to
    a power of two for cache lookups so one tuned entry covers a class of
    nearby sizes."""
    n_rows: int
    num_features: int
    num_bins: int          # kernel histogram block (power of two)
    bpc: int               # bytes per bin code (1 = u8, 2 = u16)
    packed: bool           # 4-bit nibble packing
    num_class: int
    device_kind: str
    # round 22: quantized-gradient histograms run a 2-row integer operand —
    # half the factored accumulator per group, so the same VMEM gate admits
    # twice the groups / wider level windows.  A distinct planning axis:
    # exact and quantized builds must never share a tuned entry.
    quantized: bool = False


class Plan(NamedTuple):
    """One typed plan covering all four dispatch sites.

    ``bucket_plan`` / ``level_ladder`` are ``((small, chunk, bound), ...)``
    schedules in the exact ``partition.fused_bucket_plan`` format (bounds
    ascending, last ``None``); ``hist_factored``/``hist_groups`` describe
    the histogram layout for this (F, B); ``predict_block_vmem_bytes``
    sizes ``tree_block``'s G and ``predict_buckets`` is the serving row
    ladder.  ``provenance`` is stamped into telemetry so a run's summary
    records which plan it ran."""
    bucket_plan: Tuple            # fused split dispatch schedule (leaf-wise)
    level_ladder: Tuple           # level-mode per-level bucket-class set
    hist_factored: bool           # factored hi/lo vs classic one-hot layout
    hist_groups: int              # feature groups G of the factored path
    hist_accum_budget_bytes: int  # factored-accumulator VMEM gate
    predict_block_vmem_bytes: int # path-matrix budget per predict block
    predict_buckets: Tuple        # serving row-padding ladder
    provenance: str               # analytic | tuned | pinned


def shape_class(n_rows: int, num_features: int, num_bins: int, *,
                bpc: int = 1, packed: bool = False, num_class: int = 1,
                device_kind: Optional[str] = None,
                quantized: bool = False) -> ShapeClass:
    """Normalize raw shape facts into the planning key."""
    if device_kind is None:
        device_kind = device_specs.current_device_kind()
    return ShapeClass(int(n_rows), int(num_features), int(num_bins),
                      int(bpc), bool(packed), int(num_class),
                      str(device_kind).lower(), bool(quantized))


def _pow2_ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def plan_key(sc: ShapeClass) -> str:
    """Cache key of a shape class: rows bucketized to their power-of-two
    class (one tuned entry per size regime, not per exact n)."""
    key = "n%d|f%d|b%d|bpc%d|pk%d|k%d|%s" % (
        _pow2_ceil(max(sc.n_rows, 1)), sc.num_features, sc.num_bins,
        sc.bpc, int(sc.packed), sc.num_class, sc.device_kind or "unknown")
    if getattr(sc, "quantized", False):
        # suffix only on the new axis: every pre-round-22 cache entry keeps
        # its key (and keeps applying to exact builds only)
        key += "|q1"
    return key


def analytic_plan(sc: ShapeClass) -> Plan:
    """The byte-for-byte reproduction of today's hand-tuned constants —
    golden-pinned by tests/test_plan.py against the four original sites.
    With no plan cache present this IS the plan every caller gets, so the
    refactor is behavior-neutral by default (acceptance criterion)."""
    from ..core.histogram import _factored_geometry, _use_factored
    from ..core.partition import fused_bucket_plan, level_plan
    from ..core.predict_fused import PREDICT_BUCKETS
    quant = bool(getattr(sc, "quantized", False))
    _, groups = _factored_geometry(sc.num_features, sc.num_bins,
                                   quantized=quant)
    return Plan(
        bucket_plan=fused_bucket_plan(sc.n_rows),
        level_ladder=level_plan(sc.n_rows),
        hist_factored=_use_factored(sc.num_features, sc.num_bins,
                                    quantized=quant),
        hist_groups=int(groups),
        hist_accum_budget_bytes=device_specs.hist_accum_budget_bytes(
            sc.device_kind),
        predict_block_vmem_bytes=device_specs.predict_block_vmem_bytes(
            sc.device_kind),
        predict_buckets=tuple(PREDICT_BUCKETS),
        provenance="analytic",
    )


def validate_plan(plan: Plan, n_rows: Optional[int] = None) -> None:
    """Raise ``ValueError`` unless ``plan`` is a VALID dispatch shape —
    the gate between a (possibly stale or doctored) cache entry and the
    trace-static kernel dispatch.  Checks structure only: any valid plan
    is numerics-safe by the bit-exactness of the kernel variants."""
    from ..core.partition import CHUNK, SMALL_CHUNK, _ALIGN
    if plan.provenance not in PROVENANCES:
        raise ValueError("unknown plan provenance %r" % (plan.provenance,))
    for name, sched in (("bucket_plan", plan.bucket_plan),
                        ("level_ladder", plan.level_ladder)):
        if not sched:
            raise ValueError("%s is empty" % name)
        bounds = []
        for entry in sched:
            if len(entry) != 3:
                raise ValueError("%s entry %r is not (small, chunk, bound)"
                                 % (name, entry))
            small, chunk, bound = entry
            if chunk not in (SMALL_CHUNK, CHUNK):
                raise ValueError("%s chunk %r not in (%d, %d)"
                                 % (name, chunk, SMALL_CHUNK, CHUNK))
            if small and chunk != SMALL_CHUNK:
                raise ValueError("%s small-kernel bucket must use the "
                                 "single-chunk capacity %d"
                                 % (name, SMALL_CHUNK))
            bounds.append(bound)
        if bounds[-1] is not None:
            raise ValueError("%s last bucket must be unbounded" % name)
        if any(b is None for b in bounds[:-1]):
            raise ValueError("%s only the last bucket may be unbounded"
                             % name)
        finite = [int(b) for b in bounds[:-1]]
        if finite != sorted(finite) or len(set(finite)) != len(finite):
            raise ValueError("%s bounds must be strictly ascending" % name)
        if sched[0][0] and finite:
            # the small kernel processes [wb_al, wb_al + SMALL_CHUNK) with
            # a head offset up to _ALIGN - 1: its bound may not exceed the
            # single-chunk capacity minus that slack
            if finite[0] > SMALL_CHUNK - _ALIGN:
                raise ValueError(
                    "%s small bucket bound %d exceeds the single-chunk "
                    "window contract (%d)" % (name, finite[0],
                                              SMALL_CHUNK - _ALIGN))
        if any(s for (s, _, _) in sched[1:]):
            raise ValueError("%s only the first bucket may be small" % name)
    if int(plan.hist_groups) < 1:
        raise ValueError("hist_groups must be >= 1")
    if int(plan.hist_accum_budget_bytes) <= 0:
        raise ValueError("hist_accum_budget_bytes must be positive")
    if int(plan.predict_block_vmem_bytes) <= 0:
        raise ValueError("predict_block_vmem_bytes must be positive")
    pb = [int(b) for b in plan.predict_buckets]
    if not pb or pb != sorted(pb) or len(set(pb)) != len(pb) or pb[0] < 1:
        raise ValueError("predict_buckets must be ascending positive sizes")
    del n_rows  # schedules are valid for any row count by construction


def tree_block_for(plan: Plan, t: int, m: int, l: int) -> int:
    """Trees per predict scan block under ``plan``'s VMEM budget — the
    planner-facing form of ``predict_fused.tree_block``."""
    from ..core.predict_fused import tree_block
    return tree_block(t, m, l,
                      vmem_bytes=int(plan.predict_block_vmem_bytes))


# ---- (de)serialization: JSON-safe dicts for the persisted cache ----

def plan_to_dict(plan: Plan) -> dict:
    return {
        "bucket_plan": [[bool(s), int(c), (None if b is None else int(b))]
                        for (s, c, b) in plan.bucket_plan],
        "level_ladder": [[bool(s), int(c), (None if b is None else int(b))]
                         for (s, c, b) in plan.level_ladder],
        "hist_factored": bool(plan.hist_factored),
        "hist_groups": int(plan.hist_groups),
        "hist_accum_budget_bytes": int(plan.hist_accum_budget_bytes),
        "predict_block_vmem_bytes": int(plan.predict_block_vmem_bytes),
        "predict_buckets": [int(b) for b in plan.predict_buckets],
        "provenance": str(plan.provenance),
    }


def plan_from_dict(doc: dict) -> Plan:
    def sched(rows):
        return tuple((bool(s), int(c), (None if b is None else int(b)))
                     for (s, c, b) in rows)
    return Plan(
        bucket_plan=sched(doc["bucket_plan"]),
        level_ladder=sched(doc["level_ladder"]),
        hist_factored=bool(doc["hist_factored"]),
        hist_groups=int(doc["hist_groups"]),
        hist_accum_budget_bytes=int(doc["hist_accum_budget_bytes"]),
        predict_block_vmem_bytes=int(doc["predict_block_vmem_bytes"]),
        predict_buckets=tuple(int(b) for b in doc["predict_buckets"]),
        provenance=str(doc.get("provenance", "tuned")),
    )
