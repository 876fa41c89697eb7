"""Per-device-kind hardware tables — ONE source of truth (round 18).

Before this module, device constants were scattered and re-hardcoded:
the ~16 MiB v5e VMEM note lived in a ``core/histogram.py`` docstring, the
4 MiB factored-histogram accumulator gate was a literal in
``_use_factored`` and ``core/predict_fused.py`` carried its own
``BLOCK_VMEM_BYTES``.  The kernel planner (``plan/planner.py``) needs those
numbers per ``device_kind``, so they live here — adding a backend becomes
"add a spec row + run the tuner" (ROADMAP item 4), not "re-derive every
constant".  A chip's peak FLOP/s and bytes/s are the benchmark's, in
``benchmarks/peaks.json``: nothing in the program reads them.

Dependency-free by design: ``core/histogram.py`` and
``core/predict_fused.py`` import this at module load, so it must never
import jax, core, or obs.  ``lightgbm_tpu/plan/__init__.py`` is lazy
(PEP 562) for the same reason.

The CPU row carries the v5e VMEM budgets every constant in the tree was
hand-tuned for, so a host without a chip plans byte-for-byte what the chip
plans.  A TPU whose ``device_kind`` has no row is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple, Optional


class DeviceSpec(NamedTuple):
    """Hardware envelope of one accelerator kind."""
    kind: str                     # canonical name (substring-matched)
    vmem_bytes: int               # per-core VMEM


# the "~16 MiB v5e VMEM" every round-5..7 kernel constant was tuned inside
# (previously a core/histogram.py docstring note)
V5E_VMEM_BYTES = 16 << 20

# Substring-matched IN ORDER against the lowercased ``device_kind``
# ("v5 lite" before "v5e" so both spellings of the same chip hit one row).
SPECS = (
    DeviceSpec("v5 lite", V5E_VMEM_BYTES),
    DeviceSpec("v5e", V5E_VMEM_BYTES),
    DeviceSpec("v5p", 16 << 20),
    DeviceSpec("v4", 16 << 20),
    DeviceSpec("v3", 16 << 20),
    DeviceSpec("v6", 32 << 20),
)

# hosts whose platform is ``cpu`` (tests, rehearsals): v5e-shaped VMEM budgets
# keep the analytic planner byte-equal to what the chip plans
CPU_SPEC = DeviceSpec("cpu", V5E_VMEM_BYTES)

# path-matrix VMEM budget per predict scan block (f32 bytes) — the former
# ``predict_fused.BLOCK_VMEM_BYTES`` literal; device-independent until the
# tuner says otherwise
PREDICT_BLOCK_VMEM_BYTES = 1 << 20


def spec_for(device_kind: str) -> DeviceSpec:
    """The spec row of ``device_kind`` (substring match, first hit);
    ``"cpu"`` — what :func:`current_device_kind` answers on a host without
    a chip — gets :data:`CPU_SPEC`.  A kind with no row raises: budgets
    quoted for a device nobody looked up would be made-up numbers."""
    kind = str(device_kind).lower()
    if kind == "cpu":
        return CPU_SPEC
    for spec in SPECS:
        if spec.kind in kind:
            return spec
    raise ValueError(
        "unknown device_kind %r: add its row to plan/device_specs.SPECS"
        % (device_kind,))


def hist_accum_budget_bytes(device_kind: str) -> int:
    """VMEM budget of the factored-histogram accumulator — the round-6
    "4 MiB" gate in ``histogram._use_factored``, now derived as a quarter
    of the device VMEM (4 MiB at the 16 MiB v5e: the accumulator lives
    alongside the partition kernel's ~5 MiB of pipelined streaming
    scratch — NIN=3 input ring + double-banked placement tiles)."""
    return spec_for(device_kind).vmem_bytes // 4


def predict_block_vmem_bytes(device_kind: Optional[str] = None) -> int:
    """Path-matrix VMEM budget per predict scan block
    (``predict_fused.tree_block`` sizing)."""
    del device_kind  # device-independent until tuned
    return PREDICT_BLOCK_VMEM_BYTES


_current_kind_cache = None


def current_device_kind() -> str:
    """``device_kind`` of the attached accelerator, lowercased; ``"cpu"``
    where jax's platform is not ``tpu``.  On a tpu the kind must have a
    :data:`SPECS` row (raises otherwise).  jax is imported lazily (this
    module stays dependency-free at import).  Memoized: the device set is
    process-static and this is called from trace-time layout choices
    (``histogram._use_factored``)."""
    global _current_kind_cache
    if _current_kind_cache is None:
        import jax
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            _current_kind_cache = "cpu"
        else:
            kind = str(dev.device_kind).lower()
            spec_for(kind)          # a tpu without a row raises here
            _current_kind_cache = kind
    return _current_kind_cache
