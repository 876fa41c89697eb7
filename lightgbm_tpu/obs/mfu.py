"""Analytical MFU / device-utilization estimator.

The honest-denominator accounting bench.py carried inline since round 5,
promoted to a reusable estimator so ANY telemetry run reports MFU — not
just the flagship bench.  Work is counted from the trained trees
themselves (every row passes through one window per level, so
visits = sum(leaf_count * depth)); bytes/MACs follow the fused split
kernel's actual streaming scheme and the histogram layout the shape
selects (factored hi/lo vs classic).  The device peak comes from the
attached accelerator's ``device_kind``; on a host without one the
flop/byte totals are still reported and the utilization ratios are
``None`` rather than a made-up number.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

# Device hardware tables live in plan/device_specs.py (round 18: ONE
# source of truth per device_kind, shared with the kernel planner).
from ..plan.device_specs import spec_for as _spec_for


def device_peaks(device=None) -> Optional[Dict[str, float]]:
    """{"bw": bytes/s, "macs": MACs/s, "kind": str} for the attached
    accelerator; None on a host without one.  A tpu whose kind has no
    ``device_specs.SPECS`` row raises."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = str(device.device_kind).lower()
    spec = _spec_for(kind)
    return {"bw": spec.hbm_bw, "macs": spec.peak_macs, "kind": kind}


def training_cost_model(trees: List, n_rows: int, iters: int,
                        num_features: int, max_bin: int) -> Dict[str, float]:
    """(bytes_moved, macs) for ``iters`` training iterations that produced
    ``trees`` on an [n_rows, num_features] dataset at ``max_bin``.

    Row-visits per tree are EXACT from the trees; the fused split pass
    moves ~2.5 row-store widths of HBM per visit (chunk read + left
    in-place write or right scratch write+read+write); histogram MACs
    follow the kernel's actual layout choice for this (F, B) shape."""
    from ..core.partition import TS
    from ..core.histogram import (_factored_geometry, _hilo_factors,
                                  _pad_bins_pow2, _padded_features,
                                  _use_factored)
    W = 128
    B = _pad_bins_pow2(max_bin + 1)
    if _use_factored(num_features, B):
        nhi, nlo = _hilo_factors(B)
        p, G = _factored_geometry(num_features, B)
        hist_macs_per_row = G * (4 * p * nhi) * (p * nlo)
    else:
        hist_macs_per_row = 4 * _padded_features(num_features, B) * B
    visits = 0.0
    hist_rows = 0.0
    for t in trees:
        nl = t.num_leaves
        visits += float(np.sum(t.leaf_count[:nl] * t.leaf_depth[:nl]))
        lc, rc = t.left_child[:nl - 1], t.right_child[:nl - 1]
        cnt = t.internal_count[:nl - 1].astype(np.float64)
        for node in range(nl - 1):
            l = lc[node]
            r = rc[node]
            lcnt = (cnt[l] if l >= 0 else t.leaf_count[~l])
            rcnt = (cnt[r] if r >= 0 else t.leaf_count[~r])
            hist_rows += min(float(lcnt), float(rcnt))
    bytes_moved = visits * W * 2.5 + n_rows * iters * W  # + root hist streams
    macs = (visits * (2 * TS * W)
            + (hist_rows + n_rows * iters) * hist_macs_per_row)
    return {"bytes": float(bytes_moved), "macs": float(macs),
            "row_visits": float(visits)}


def training_utilization(trees: List, n_rows: int, iters: int,
                         num_features: int, max_bin: int,
                         wall_s: float) -> Dict:
    """Cost model + achieved/peak ratios for one timed training window.
    ``device_util``/``mfu`` are None on devices with no peak entry."""
    cost = training_cost_model(trees, n_rows, iters, num_features, max_bin)
    peaks = device_peaks()
    out = dict(cost)
    out["wall_s"] = float(wall_s)
    if peaks is not None and wall_s > 0:
        out["device_kind"] = peaks["kind"]
        out["device_util"] = cost["bytes"] / wall_s / peaks["bw"]
        out["mfu"] = cost["macs"] / wall_s / peaks["macs"]
    else:
        out["device_kind"] = None
        out["device_util"] = None
        out["mfu"] = None
    return out


def record_training_estimate(tele, gbdt, wall_s: float,
                             iters: Optional[int] = None) -> Optional[Dict]:
    """Compute the MFU estimate for a finished training run and record it
    into ``tele``'s gauges (``mfu``, ``device_util``, ``est_flops``,
    ``est_bytes``).  Best-effort: a model shape the cost model cannot
    price (no trees, no train data) records nothing and returns None."""
    try:
        models = list(gbdt.models)
        K = max(int(gbdt.num_tree_per_iteration), 1)
        n_iters = iters if iters is not None else len(models) // K
        if n_iters <= 0 or not models or gbdt.train_data is None:
            return None
        trees = models[-n_iters * K:]
        est = training_utilization(
            trees, int(gbdt.num_data), n_iters,
            int(gbdt.train_data.num_features),
            int(gbdt.config.max_bin), wall_s)
    except Exception:  # noqa: BLE001 - estimator must never fail a run
        return None
    tele.gauge("est_bytes").set(est["bytes"])
    tele.gauge("est_macs").set(est["macs"])
    if est["mfu"] is not None:
        tele.gauge("mfu").set(est["mfu"])
        tele.gauge("device_util").set(est["device_util"])
    tele.event("mfu_estimate", **{k: v for k, v in est.items()})
    return est
