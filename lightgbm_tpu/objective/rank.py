"""Learning-to-rank objectives: lambdarank NDCG and rank_xendcg.

Counterparts of src/objective/rank_objective.hpp:23-202 (LambdarankNDCG) and
src/objective/rank_xendcg_objective.hpp:25-110 (RankXENDCG).

TPU-first design: queries are bucketed by padded size (powers of two) at init;
each bucket is a [Q, S] gather of scores through a static index matrix, the
per-query pairwise lambda computation runs as one jitted [Q, S, S] tensor
kernel per bucket, and results scatter-add back into the [N] gradient vector —
no host round-trip per iteration (the reference's per-query OpenMP loops,
rank_objective.hpp:117-168, become batched device math).  Exact sigmoids are
used instead of the reference's lookup table (:185-200) — the table is a CPU
speed hack, not semantics.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import ObjectiveFunction
from ..metric.dcg import DCGCalculator
from ..utils.log import Log

# cap on per-bucket [Q, S, S] pair-tensor elements (memory guard)
_PAIR_BUDGET = 1 << 26


def _make_buckets(query_boundaries: np.ndarray, num_data: int
                  ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Group queries by padded size: [(idx [Q, S] with num_data padding,
    qids [Q]), ...] for S in powers of two."""
    lens = np.diff(query_boundaries)
    out = []
    sizes = {}
    for q, cnt in enumerate(lens):
        s = 8
        while s < cnt:
            s *= 2
        sizes.setdefault(s, []).append(q)
    for s, qids in sorted(sizes.items()):
        idx = np.full((len(qids), s), num_data, dtype=np.int32)
        for r, q in enumerate(qids):
            lo, hi = query_boundaries[q], query_boundaries[q + 1]
            idx[r, :hi - lo] = np.arange(lo, hi, dtype=np.int32)
        out.append((idx, np.asarray(qids, dtype=np.int32)))
    return out


@functools.partial(jax.jit, static_argnames=("sigmoid", "norm"))
def _lambdarank_bucket(scores: jax.Array, labels: jax.Array, mask: jax.Array,
                       inv_max_dcg: jax.Array, label_gain: jax.Array,
                       discounts: jax.Array, *, sigmoid: float, norm: bool):
    """Pairwise lambdas for one size bucket.

    scores/labels/mask: [Q, S] (pad rows masked); returns (lambda, hess) [Q, S]
    in the bucket's (unsorted) doc order.  Mirrors
    LambdarankNDCG::GetGradientsForOneQuery (rank_objective.hpp:117-168).
    """
    q, s_dim = scores.shape
    neg = jnp.where(mask, scores, -jnp.inf)
    order = jnp.argsort(-neg, axis=1, stable=True)
    s = jnp.take_along_axis(scores, order, axis=1)
    m = jnp.take_along_axis(mask, order, axis=1)
    lab = jnp.take_along_axis(labels, order, axis=1)
    gains = label_gain[jnp.clip(lab, 0, label_gain.shape[0] - 1)]
    disc = discounts[:s_dim][None, :]
    cnt = jnp.sum(mask, axis=1).astype(jnp.int32)
    best = s[:, 0]
    worst = jnp.take_along_axis(
        s, jnp.maximum(cnt - 1, 0)[:, None], axis=1)[:, 0]

    valid = ((lab[:, :, None] > lab[:, None, :])
             & m[:, :, None] & m[:, None, :])
    ds = jnp.where(valid, s[:, :, None] - s[:, None, :], 0.0)
    dndcg = (jnp.abs(gains[:, :, None] - gains[:, None, :])
             * jnp.abs(disc[:, :, None] - disc[:, None, :])
             * inv_max_dcg[:, None, None])
    if norm:
        same = (best == worst)[:, None, None]
        dndcg = jnp.where(same, dndcg, dndcg / (0.01 + jnp.abs(ds)))
    p = 1.0 / (1.0 + jnp.exp(sigmoid * ds))
    p_lambda = jnp.where(valid, -sigmoid * dndcg * p, 0.0)
    p_hess = jnp.where(valid, sigmoid * sigmoid * dndcg * p * (1.0 - p), 0.0)
    lam = jnp.sum(p_lambda, axis=2) - jnp.sum(p_lambda, axis=1)
    hes = jnp.sum(p_hess, axis=2) + jnp.sum(p_hess, axis=1)
    if norm:
        sum_lambdas = -2.0 * jnp.sum(p_lambda, axis=(1, 2))
        nf = jnp.where(sum_lambdas > 0,
                       jnp.log2(1.0 + sum_lambdas)
                       / jnp.maximum(sum_lambdas, 1e-300), 1.0)
        lam = lam * nf[:, None]
        hes = hes * nf[:, None]
    # unsort back to the bucket's doc positions
    inv = jnp.argsort(order, axis=1)
    return (jnp.take_along_axis(lam, inv, axis=1),
            jnp.take_along_axis(hes, inv, axis=1))


class LambdarankNDCG(ObjectiveFunction):
    name = "lambdarank"
    need_accurate_prediction = False

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0.0:
            Log.fatal("Sigmoid param %f should be greater than zero", self.sigmoid)
        self.norm = bool(config.lambdamart_norm)
        self.optimize_pos_at = int(config.max_position)
        DCGCalculator.init(list(config.label_gain) or None)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("Lambdarank tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries)
        DCGCalculator.check_label(self.label_np)
        inverse_max_dcgs = np.zeros(len(self.query_boundaries) - 1)
        for q in range(len(inverse_max_dcgs)):
            lo, hi = self.query_boundaries[q], self.query_boundaries[q + 1]
            maxdcg = DCGCalculator.cal_max_dcg_at_k(self.optimize_pos_at,
                                                    self.label_np[lo:hi])
            inverse_max_dcgs[q] = 1.0 / maxdcg if maxdcg > 0 else 0.0
        # device bucket structures
        self._buckets = []
        label_pad = np.concatenate([self.label_np.astype(np.int32), [0]])
        max_s = 8
        for idx, qids in _make_buckets(self.query_boundaries, num_data):
            s = idx.shape[1]
            max_s = max(max_s, s)
            chunk = max(_PAIR_BUDGET // (s * s), 1)
            for lo in range(0, idx.shape[0], chunk):
                part_idx = idx[lo:lo + chunk]
                self._buckets.append({
                    "idx": jnp.asarray(part_idx),
                    "labels": jnp.asarray(label_pad[part_idx]),
                    "mask": jnp.asarray(part_idx < num_data),
                    "inv_max_dcg": jnp.asarray(
                        inverse_max_dcgs[qids[lo:lo + chunk]].astype(
                            np.float32)),
                })
        self._label_gain = jnp.asarray(
            np.asarray(DCGCalculator.label_gain_, dtype=np.float32))
        disc = np.asarray(DCGCalculator.discount_, dtype=np.float32)
        if max_s > disc.shape[0]:   # queries beyond kMaxPosition positions
            disc = np.concatenate(
                [disc, np.full(max_s - disc.shape[0], disc[-1], np.float32)])
        self._discounts = jnp.asarray(disc[:max_s])

    def shard_rows(self, place) -> bool:
        return False    # a query's gradients read its other rows

    def get_gradients(self, score):
        score = jnp.asarray(score, dtype=jnp.float32).reshape(-1)
        score_pad = jnp.concatenate([score, jnp.zeros((1,), jnp.float32)])
        lam = jnp.zeros((self.num_data,), jnp.float32)
        hes = jnp.zeros((self.num_data,), jnp.float32)
        for b in self._buckets:
            sc = score_pad[b["idx"]]
            bl, bh = _lambdarank_bucket(sc, b["labels"], b["mask"],
                                        b["inv_max_dcg"], self._label_gain,
                                        self._discounts,
                                        sigmoid=self.sigmoid, norm=self.norm)
            lam = lam.at[b["idx"].reshape(-1)].add(bl.reshape(-1),
                                                   mode="drop")
            hes = hes.at[b["idx"].reshape(-1)].add(bh.reshape(-1),
                                                   mode="drop")
        if self.weights is not None:
            lam = lam * self.weights
            hes = hes * self.weights
        return lam, hes

    def to_string(self):
        return self.name


@jax.jit
def _xendcg_bucket(scores: jax.Array, labels: jax.Array, mask: jax.Array,
                   gammas: jax.Array):
    """Listwise XE-NDCG lambdas for one bucket ([Q, S] rows; pads masked).
    Mirrors RankXENDCG::GetGradientsForOneQuery
    (rank_xendcg_objective.hpp:43-110)."""
    neg_inf = jnp.float32(-1e30)
    sm = jnp.where(mask, scores, neg_inf)
    e = jnp.exp(sm - jnp.max(sm, axis=1, keepdims=True))
    rho = e / jnp.sum(e, axis=1, keepdims=True)
    phi = jnp.where(mask, jnp.power(2.0, labels.astype(jnp.float32)) - gammas,
                    0.0)
    sum_labels = jnp.sum(phi, axis=1, keepdims=True)
    ok = jnp.abs(sum_labels) > 1e-15
    l1 = jnp.where(mask, -phi / jnp.where(ok, sum_labels, 1.0) + rho, 0.0)
    inv = jnp.where(mask, 1.0 / jnp.maximum(1.0 - rho, 1e-15), 0.0)
    li = l1 * inv
    l2 = jnp.sum(li, axis=1, keepdims=True) - li
    rl = rho * l2 * inv
    l3 = jnp.sum(rl, axis=1, keepdims=True) - rl
    lam = jnp.where(mask & ok, l1 + rho * l2 + rho * l3, 0.0)
    hes = jnp.where(mask & ok, rho * (1.0 - rho), 0.0)
    cnt = jnp.sum(mask, axis=1, keepdims=True)
    single = cnt <= 1
    return jnp.where(single, 0.0, lam), jnp.where(single, 0.0, hes)


class RankXENDCG(ObjectiveFunction):
    """Listwise cross-entropy NDCG surrogate (rank_xendcg_objective.hpp:25-110):
    phi(l, gamma) = 2^l - gamma with per-doc uniform gammas, batched on device."""
    name = "rank_xendcg"
    need_accurate_prediction = False
    deterministic_gradients = False  # fresh gammas every call

    def __init__(self, config):
        super().__init__(config)
        self._seed = int(getattr(config, "objective_seed", 5))
        self._call = 0

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("RankXENDCG tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries)
        label_pad = np.concatenate([self.label_np.astype(np.float32), [0.0]])
        self._buckets = []
        for idx, _ in _make_buckets(self.query_boundaries, num_data):
            self._buckets.append({
                "idx": jnp.asarray(idx),
                "labels": jnp.asarray(label_pad[idx]),
                "mask": jnp.asarray(idx < num_data),
            })

    def shard_rows(self, place) -> bool:
        return False    # a query's gradients read its other rows

    def get_gradients(self, score):
        score = jnp.asarray(score, dtype=jnp.float32).reshape(-1)
        score_pad = jnp.concatenate([score, jnp.zeros((1,), jnp.float32)])
        lam = jnp.zeros((self.num_data,), jnp.float32)
        hes = jnp.zeros((self.num_data,), jnp.float32)
        self._call += 1
        key = jax.random.PRNGKey(self._seed + self._call)
        for i, b in enumerate(self._buckets):
            sc = score_pad[b["idx"]]
            gammas = jax.random.uniform(jax.random.fold_in(key, i),
                                        b["idx"].shape, dtype=jnp.float32)
            bl, bh = _xendcg_bucket(sc, b["labels"], b["mask"], gammas)
            lam = lam.at[b["idx"].reshape(-1)].add(bl.reshape(-1), mode="drop")
            hes = hes.at[b["idx"].reshape(-1)].add(bh.reshape(-1), mode="drop")
        return lam, hes
