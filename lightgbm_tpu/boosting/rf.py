"""Random forest mode (src/boosting/rf.hpp:25-218): mandatory bagging, no
shrinkage, gradients always computed at the constant init score, and the model
output is the average over trees (average_output)."""
from __future__ import annotations

import numpy as np

from .gbdt import GBDT
from ..core.tree import Tree
from ..obs import sampling as _sampling
from ..utils.log import Log

K_EPSILON = 1e-15


class RF(GBDT):
    fuse_iters = False
    shard_row_state = False
    average_output = True

    def __init__(self, config, train_data=None, objective=None, mesh=None):
        super().__init__(config, train_data, objective, mesh=mesh)
        self.shrinkage_rate = 1.0
        self._init_scores = [0.0] * self.num_tree_per_iteration
        if objective is None:
            Log.fatal("RF mode do not support custom objective function, "
                      "please use built-in objectives.")
        self._rf_grad = None

    def _boost_from_average(self, class_id, update_scorer):
        # RF computes init scores but never adds them to the score updater
        return super()._boost_from_average(class_id, update_scorer=False)

    _init_scores_ready = False
    _rf_guarded = False
    _rf_skip = False

    def _extra_train_state(self):
        """The constant init scores gradients are computed against: after a
        resume the model is non-empty, so _boost_from_average would return
        0.0 and a recompute would silently shift every later tree."""
        return {"init_scores": [float(s) for s in self._init_scores],
                "init_scores_ready": bool(self._init_scores_ready)}

    def _restore_extra_train_state(self, extra):
        if "init_scores" in extra:
            self._init_scores = [float(s) for s in extra["init_scores"]]
            self._init_scores_ready = bool(extra.get("init_scores_ready"))
            self._rf_grad = None
            self._rf_guarded = False

    def _get_gradients(self):
        # gradients w.r.t. constant init score, computed once (rf.hpp:83-101)
        if self._rf_grad is None:
            import jax.numpy as jnp
            if not self._init_scores_ready:
                for k in range(self.num_tree_per_iteration):
                    self._init_scores[k] = self._boost_from_average(k, False)
                self._init_scores_ready = True
            init = jnp.asarray(np.asarray(self._init_scores, dtype=np.float32))
            scores = jnp.broadcast_to(init[:, None],
                                      (self.num_tree_per_iteration,
                                       self.num_data))
            if self.num_tree_per_iteration == 1:
                g, h = self.objective.get_gradients(scores[0])
                self._rf_grad = (g[None, :], h[None, :])
            else:
                self._rf_grad = self.objective.get_gradients(scores)
        return self._rf_grad

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        assert gradients is None and hessians is None, \
            "RF does not accept custom gradients"
        self.shrinkage_rate = 1.0
        # scores hold the average of trees so far: un-average, add, re-average
        it = self.iter_ + self.num_init_iteration
        grad, hess = self._get_gradients()
        # RF gradients are constant across iterations: guard the pair ONCE
        # when first computed (a per-iteration isfinite fetch would block
        # the device queue 2x per iteration for an answer that cannot
        # change) and cache the sanitized result + the skip verdict
        if not self._rf_guarded:
            grad, hess, self._rf_skip = self._guard_gradients(
                grad, hess, force_check=True)
            self._rf_grad = (grad, hess)
            self._rf_guarded = True
        if self._rf_skip:
            return self._skip_iteration(self._init_scores)
        self._bagging(self.iter_)

        should_continue = False
        feature_mask = self._feature_mask()
        self._last_iter_arrays = []
        for k in range(self.num_tree_per_iteration):
            new_tree = Tree(1)
            arrays = None
            if self.class_need_train[k]:
                gk = self.learner.pad_rows(grad[k])
                hk = self.learner.pad_rows(hess[k])
                if self.bag_mask is not None:
                    gk = gk * self.bag_mask
                    hk = hk * self.bag_mask
                arrays = self.learner.train(gk, hk, self.bag_data_cnt,
                                            feature_mask)
                if int(arrays.num_leaves) > 1:
                    new_tree = self.learner.host_tree(arrays)
            if new_tree.num_leaves > 1:
                should_continue = True
                arrays = self._renew_tree_output(new_tree, arrays, k)
                if abs(self._init_scores[k]) > K_EPSILON:
                    new_tree.add_bias(self._init_scores[k])
                    arrays = arrays._replace(
                        leaf_value=arrays.leaf_value + self._init_scores[k])
                # running average of tree outputs (rf.hpp MultiplyScore dance)
                self.train_score = (
                    self.train_score.at[k].multiply(float(it))
                    .at[k].add(self._gather_tree_output(arrays))
                    .at[k].multiply(1.0 / (it + 1)))
                for vs in self.valid_sets:
                    vs["score"] = vs["score"].at[k].multiply(float(it))
                    self._add_tree_score_valid(len(self.models), new_tree, k, vs)
                    vs["score"] = vs["score"].at[k].multiply(1.0 / (it + 1))
                self._last_iter_arrays.append(arrays)
            else:
                self._last_iter_arrays.append(None)
            self.models.append(new_tree)

        if not should_continue:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            return True
        self.iter_ += 1
        _sampling.record_trees("per_iteration", self.num_tree_per_iteration,
                               self.bag_data_cnt)
        return False
