"""Quantized-gradient training: low-bit integer grad/hess for histograms.

The GBDT literature's answer to histogram bandwidth (dense_bin.hpp's
ConstructHistogram being the hottest op everywhere) is quantized training:
per-iteration scales map gradients to a few integer levels, histogram
accumulation runs on the narrow integers, and split gains are computed from
dequantized sums.  On the TPU one-hot-contraction layout the win is
structural — small integers are EXACT in bf16, so the 4-row hi/lo split of
``histogram._hilo_split`` collapses to a 2-row operand: half the MXU rows,
half the accumulator VMEM, and the parallel learners' hist allreduce rides
a bf16 payload at half the bytes (the pod-path analog of the reference's
histogram Allreduce).

Determinism contract (same as the bagging mask, ``gbdt._bag_uniforms``):
the stochastic-rounding offset for a row is a STATELESS hash of
(iteration, global row index, seed).  No RNG state rides the checkpoint —
resuming at iteration k replays the identical rounding stream, and the
fused trees-per-chunk scan at any chunk boundary sees the same integers.
A distinct mixing tag keeps the quant stream decorrelated from the bagging
stream (rows bagged in must not share their rounding direction).

Level choice: grad quantizes to [-127, 127] (signed), hess to [0, 255]
(non-negative) — both exact in bf16 (integers to 256), and per-shard
window sums stay exact in the f32 accumulator up to 2^24 / 255 ≈ 65k rows
per bin; full-window sums are exact to 2^24.  Scales are per boosting
iteration, computed from the global max over the (sharded) gradient —
``jax.lax.pmax`` under an axis makes every shard quantize with the serial
stream's scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GRAD_LEVELS = 127    # signed: q_g in [-127, 127]
HESS_LEVELS = 255    # non-negative: q_h in [0, 255]
_QUANT_TAG = 0x7FB5D591  # domain separation vs the bagging hash stream


def quant_uniforms(row_ids: jax.Array, seed, it) -> jax.Array:
    """Stateless per-(iteration, row) uniform in [0, 1) for stochastic
    rounding — the avalanche family of ``gbdt._bag_uniforms`` with a
    domain-separation tag, truncated to 24 bits so the f32 value is
    STRICTLY below 1.0 (a 32-bit uniform can round to 1.0 in f32, and
    floor(0 + 1.0) would give bagged-out zero-gradient rows a phantom
    integer level)."""
    x = row_ids.astype(jnp.uint32)
    x = x ^ (jnp.uint32(seed) * jnp.uint32(2654435761))
    x = x ^ jnp.uint32(_QUANT_TAG)
    x = x + jnp.uint32(it) * jnp.uint32(0x9E3779B9)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(3266489917)
    x = x ^ (x >> 16)
    return (x >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def quantize_gradients(grad: jax.Array, hess: jax.Array, row_ids: jax.Array,
                       it, seed, axis_name: str = ""):
    """Stochastically round (grad, hess) to integer-valued f32.

    Returns (q_grad, q_hess, qscale[2]) — q_* are f32 arrays holding exact
    integers (grad in [-127, 127], hess in [0, 255]); ``qscale`` is
    (s_g, s_h) with real value = q * s.  Zero inputs (bagged-out or padded
    rows) map to exactly zero.  Under ``axis_name`` the scales are the
    pmax over shards, so a sharded build quantizes with the serial
    stream's scale (row_ids must then be GLOBAL ids)."""
    gmax = jnp.max(jnp.abs(grad))
    hmax = jnp.max(hess)
    if axis_name:
        with jax.named_scope("comm.sums"):
            gmax = jax.lax.pmax(gmax, axis_name)
            hmax = jax.lax.pmax(hmax, axis_name)
    tiny = jnp.float32(1e-30)
    s_g = jnp.maximum(gmax, tiny) / jnp.float32(GRAD_LEVELS)
    s_h = jnp.maximum(hmax, tiny) / jnp.float32(HESS_LEVELS)
    u_g = quant_uniforms(row_ids, seed, it)
    # one hash per row, two decorrelated offsets: the hessian stream
    # reuses the grad stream reflected — exact in f32 and independent
    # enough for unbiased rounding of a DIFFERENT value
    u_h = jnp.float32(1.0) - jnp.float32(2.0 ** -24) - u_g
    q_g = jnp.clip(jnp.floor(grad / s_g + u_g),
                   -GRAD_LEVELS, GRAD_LEVELS)
    q_h = jnp.clip(jnp.floor(hess / s_h + u_h), 0, HESS_LEVELS)
    # exact-zero inputs stay exact zero regardless of the offset (floor of
    # u alone is 0 for u < 1, and -s*u rounds to 0 or -1; pin it)
    q_g = jnp.where(grad == 0.0, 0.0, q_g).astype(jnp.float32)
    q_h = jnp.where(hess == 0.0, 0.0, q_h).astype(jnp.float32)
    qscale = jnp.stack([s_g, s_h]).astype(jnp.float32)
    return q_g, q_h, qscale
