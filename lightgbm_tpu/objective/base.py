"""Objective function interface.

Counterpart of the reference ``ObjectiveFunction`` (include/LightGBM/
objective_function.h): gradients/hessians from scores, boost-from-score,
raw-score -> output conversion, and optional per-leaf output renewal.

Elementwise objectives compute gradients on device (jitted jnp); the listwise
ranking objectives run per-query on host NumPy (their pairwise loops are not a
device-friendly hot spot at reference scale).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.metadata import Metadata


class ObjectiveFunction:
    name: str = "custom"
    num_model_per_iteration: int = 1
    is_constant_hessian: bool = False
    need_accurate_prediction: bool = True
    is_renew_tree_output: bool = False
    # False for objectives that draw fresh randomness per GetGradients call
    # (they must not be traced once and replayed by fused training)
    deterministic_gradients: bool = True

    def __init__(self, config) -> None:
        self.config = config
        self.num_data = 0
        self.label: Optional[jnp.ndarray] = None
        self.weights: Optional[jnp.ndarray] = None
        self.label_np: Optional[np.ndarray] = None
        self.weights_np: Optional[np.ndarray] = None

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label_np = np.asarray(metadata.label, dtype=np.float32)
        self.label = jnp.asarray(self.label_np)
        if metadata.weights is not None:
            self.weights_np = np.asarray(metadata.weights, dtype=np.float32)
            self.weights = jnp.asarray(self.weights_np)
        else:
            self.weights_np = None
            self.weights = None
        self.metadata = metadata

    def get_gradients(self, score) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """score: [num_model_per_iteration, N] (or [N]) raw scores -> (grad, hess)
        of the same shape."""
        raise NotImplementedError

    # ---- row-sharded training (parallel learners that shard rows) ----

    def shard_rows(self, place) -> bool:
        """Hand every per-row device constant (label, weights, what ``init``
        derived from them: any array whose last axis is the rows) to
        ``place``, which pads the rows to the learner's count and puts them
        where the learner keeps its rows; ``get_gradients`` then takes scores
        of that length and stays shard-local.  False from an objective whose
        gradients read other rows (ranking): its state stays where it was."""
        for name, value in list(vars(self).items()):
            if (isinstance(value, jax.Array) and value.ndim
                    and value.shape[-1] == self.num_data):
                setattr(self, name, place(value))
        return True

    # ---- carried-row-store training (boosting/gbdt.py fused path) ----
    # Objectives whose gradients are a pointwise function of (score, one f32
    # per-row auxiliary value) can train with the per-row state carried INSIDE
    # the tree builder's permuted row store, eliminating every per-row
    # gather/scatter between iterations.  ``carry_aux`` returns that [N] f32
    # auxiliary vector (or None when unsupported — e.g. ranking objectives
    # whose gradients need query-grouped neighbours, or when sample weights
    # would need a second column).

    def carry_aux(self):
        return None

    def pointwise_gradients(self, score, aux):
        """grad/hess of a single row given its score and carried aux value;
        must be vectorized over [N] arrays and ORDER-AGNOSTIC."""
        raise NotImplementedError

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    def convert_output(self, scores: np.ndarray) -> np.ndarray:
        """Raw score -> prediction output (identity by default)."""
        return scores

    def renew_tree_output(self, leaf_rows_residual, leaf_rows_weight) -> float:
        """New output for one leaf given its rows' residuals (+weights)."""
        raise NotImplementedError

    def _apply_weights(self, grad, hess):
        if self.weights is not None:
            return grad * self.weights, hess * self.weights
        return grad, hess

    def to_string(self) -> str:
        return self.name
