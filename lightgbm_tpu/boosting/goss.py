"""GOSS: gradient-based one-side sampling (src/boosting/goss.hpp:25-185).

Keep the top_rate fraction by |grad*hess|, sample other_rate from the rest and
amplify their grad/hess by (1-top_rate)/other_rate.  Expressed as a row weight
mask (0 / 1 / multiplier) folded into grad/hess, matching the reference's
in-place gradient scaling (goss.hpp:117-121).

Round 12: the top-k selection runs ON DEVICE — ``jax.lax.top_k`` over the
|grad*hess| key replaces the host ``np.argsort`` round-trip (the full-n
top_k is XLA's stable descending sort: ties broken toward the lower index,
exactly ``np.argsort(-g, kind="stable")``, pinned by
tests/test_goss_device.py).  Only the "other" subsample's POSITIONS still
come from the host RandomState — same call with the same arguments as
before, so the bagging RNG stream (and with it checkpoint resume
bit-exactness) is unchanged.  The host path is retained as a fallback
(``LIGHTGBM_TPU_GOSS_HOST=1`` or any selection failure) and is bit-equal to
the device path.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from .gbdt import GBDT
from ..obs import active as _telemetry_active
from ..utils.log import Log


class GOSS(GBDT):
    fuse_iters = False
    shard_row_state = False
    def __init__(self, config, train_data=None, objective=None, mesh=None):
        super().__init__(config, train_data, objective, mesh=mesh)
        if config.top_rate + config.other_rate > 1.0:
            Log.fatal("top_rate + other_rate cannot be larger than 1.0 in GOSS")
        if config.top_rate <= 0.0 or config.other_rate <= 0.0:
            Log.fatal("top_rate and other_rate must be positive in GOSS")
        if config.bagging_freq > 0 and config.bagging_fraction != 1.0:
            Log.fatal("Cannot use bagging in GOSS")
        Log.info("Using GOSS")
        self._goss_multiplier = None
        self._goss_device = os.environ.get("LIGHTGBM_TPU_GOSS_HOST",
                                           "0") != "1"

    def _bagging(self, it: int) -> None:
        # GOSS resamples every iteration once warmed up (goss.hpp:133-136:
        # no subsampling for the first 1/learning_rate iterations)
        self.bag_mask = None
        self.bag_data_cnt = self.num_data
        self._goss_multiplier = None
        if it < int(1.0 / self.config.learning_rate):
            return
        self._needs_goss = True

    def _select_weights_device(self, key, top_k: int,
                               sampled: np.ndarray, multiply: float):
        """Device-side selection: full-n ``lax.top_k`` gives the stable
        descending order (== np.argsort(-key, kind="stable")); the top_k
        prefix keeps weight 1, the host-sampled positions of the remainder
        get the amplification weight.  No key/order round-trips the host."""
        n = key.shape[0]
        _, order = jax.lax.top_k(key, n)
        w = jnp.zeros((n,), jnp.float32)
        w = w.at[order[:top_k]].set(1.0)
        if len(sampled):
            other_idx = order[top_k:][jnp.asarray(sampled, jnp.int32)]
            w = w.at[other_idx].set(np.float32(multiply))
        return w

    def _select_weights_host(self, key: np.ndarray, top_k: int,
                             sampled: np.ndarray, multiply: float):
        """Host fallback (the pre-round-12 path), bit-equal to the device
        selection on the same key."""
        n = len(key)
        order = np.argsort(-key, kind="stable")
        w = np.zeros(n, dtype=np.float32)
        w[order[:top_k]] = 1.0
        if len(sampled):
            w[order[top_k:][sampled]] = multiply
        return jnp.asarray(w)

    def _adjust_gradients_for_bagging(self, grad, hess):
        if getattr(self, "_needs_goss", False):
            self._needs_goss = False
            key = jnp.abs(grad * hess).sum(axis=0)
            n = self.num_data
            top_k = max(1, int(n * self.config.top_rate))
            other_k = max(1, int(n * self.config.other_rate))
            rest_n = n - top_k
            # the "other" positions come from the SAME host RandomState call
            # as always — the bagging RNG stream checkpoints replay is
            # untouched by where the sort runs
            sampled = self._bag_rng.choice(
                rest_n, size=min(other_k, rest_n), replace=False)
            multiply = (n - top_k) / max(other_k, 1)
            if self._goss_device:
                try:
                    w = self._select_weights_device(key, top_k, sampled,
                                                    multiply)
                except Exception as exc:  # degraded-mode idiom (round 11):
                    # selection failure falls back to the bit-equal host
                    # path instead of killing the run
                    Log.warning("device GOSS selection failed (%s); falling "
                                "back to the host path", exc)
                    self._goss_device = False
            if not self._goss_device:
                w = self._select_weights_host(np.asarray(key), top_k,
                                              sampled, multiply)
            self.bag_data_cnt = top_k + len(sampled)
            self.bag_mask = None  # weights are folded into grad/hess below
            tele = _telemetry_active()
            if tele is not None:
                tele.gauge("goss_top_k").set(top_k)
                tele.gauge("goss_other_k").set(len(sampled))
                # JSONL growth bounded by the telemetry_freq cadence like
                # engine.train's iteration events; gauges always current
                if self.iter_ % tele.freq == 0:
                    tele.event("goss_select", iteration=int(self.iter_),
                               top_k=int(top_k),
                               other_k=int(len(sampled)),
                               multiplier=float(multiply))
            wj = w[None, :]
            return grad * wj, hess * wj
        return grad, hess
