"""Serving tier: continuous batching, multi-model residency, SLO telemetry.

PR 3 built the serving ENGINE (``core/predict_fused.py``: tree-blocked
contraction, binned fast path, the fixed shape-bucket ladder with a cached
``FusedPredictor`` so steady-state serving never recompiles); this package
is the SYSTEM around it — what turns individual requests from millions of
users into those cached bucket dispatches:

- :class:`~.scheduler.Server` — the request loop: a dispatcher thread
  coalesces single rows and micro-batches under ``max_batch_wait_us`` into
  the next bucket rung and completes one future per request (per-request
  ``num_iteration``/``pred_early_stop``, raw vs binned inputs, optional
  single-row bypass through ``model_codegen.compile_single_row``);
- :class:`~.registry.ModelRegistry` — many boosters resident per process
  under a ``serve_residency_budget_mb`` budget with LRU eviction, refcounted
  in-flight protection, transparent re-admission, and atomic
  :meth:`~.registry.ModelRegistry.swap` hot-swaps;
- SLO instrumentation — per-model latency/occupancy/queue-depth histograms
  and eviction/swap counters through the ``obs`` registry (zero telemetry
  calls when no run is active), rendered as the ``serving`` block of the
  telemetry summary.

Entry points: ``lightgbm_tpu.serve(...)`` (engine), ``Booster.serve()``,
CLI ``task=serve``; ``lightgbm_tpu.serve_and_train(...)`` / ``task=online``
wrap a Server in the round-17 train-while-serve loop
(``lightgbm_tpu/online``).
"""
from .registry import ModelRegistry, ResidentModel
from .scheduler import Server, ServingClosed, ServingQueueFull

__all__ = ["Server", "ModelRegistry", "ResidentModel", "ServingQueueFull",
           "ServingClosed"]
