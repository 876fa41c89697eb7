"""DART: dropouts meet multiple additive regression trees
(src/boosting/dart.hpp:23-211)."""
from __future__ import annotations

import numpy as np

from .gbdt import GBDT
from ..obs import active as _telemetry_active
from ..utils.log import Log


class DART(GBDT):
    fuse_iters = False
    shard_row_state = False
    lazy_trees = False  # dropout shrinks/re-adds host trees every iteration
    # dropout rescales OLD trees' leaf values in place and appends to the
    # tree-weight history — effects the pre-chunk score/model refs cannot
    # undo, so score corruption stops at detection (gbdt._guard_chunk_scores)
    _prechunk_rollback_safe = False

    def __init__(self, config, train_data=None, objective=None, mesh=None):
        self._drop_rng = np.random.RandomState(int(config.drop_seed))
        self.tree_weight = []
        self.sum_weight = 0.0
        self.drop_index = []
        self._score_is_dropped = False
        super().__init__(config, train_data, objective, mesh=mesh)

    def sub_model_name(self) -> str:
        return "tree"

    def _extra_train_state(self):
        """Dropout state a bit-exact resume needs: the drop RNG stream and
        the per-tree weight history driving non-uniform drop probabilities
        (dart.hpp:76-86).  Without these a resumed run drops different
        trees and silently diverges."""
        from ..checkpoint import encode_rng_state
        return {"drop_rng": encode_rng_state(self._drop_rng),
                "tree_weight": [float(w) for w in self.tree_weight],
                "sum_weight": float(self.sum_weight)}

    def _restore_extra_train_state(self, extra):
        from ..checkpoint import decode_rng_state
        self._drop_rng.set_state(decode_rng_state(extra["drop_rng"]))
        self.tree_weight = [float(w) for w in extra.get("tree_weight", [])]
        self.sum_weight = float(extra.get("sum_weight", 0.0))
        self.drop_index = []
        self._score_is_dropped = False

    def _get_gradients(self):
        # drop trees once per iteration before computing gradients (dart.hpp:76-86)
        if not self._score_is_dropped:
            self._dropping_trees()
            self._score_is_dropped = True
        return super()._get_gradients()

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        self._score_is_dropped = False
        ret = super().train_one_iter(gradients, hessians)
        if ret:
            return ret
        self._normalize()
        if not self.config.uniform_drop:
            self.tree_weight.append(self.shrinkage_rate)
            self.sum_weight += self.shrinkage_rate
        return False

    def _dropping_trees(self) -> None:
        self.drop_index = []
        cfg = self.config
        if self._drop_rng.uniform() >= cfg.skip_drop:
            drop_rate = cfg.drop_rate
            if not cfg.uniform_drop:
                if self.sum_weight > 0:
                    inv_avg = len(self.tree_weight) / self.sum_weight
                    if cfg.max_drop > 0:
                        drop_rate = min(drop_rate,
                                        cfg.max_drop * inv_avg / self.sum_weight)
                    for i in range(self.iter_):
                        if (self._drop_rng.uniform()
                                < drop_rate * self.tree_weight[i] * inv_avg):
                            self.drop_index.append(self.num_init_iteration + i)
                            if len(self.drop_index) >= cfg.max_drop > 0:
                                break
            else:
                if cfg.max_drop > 0 and self.iter_ > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / self.iter_)
                for i in range(self.iter_):
                    if self._drop_rng.uniform() < drop_rate:
                        self.drop_index.append(self.num_init_iteration + i)
                        if len(self.drop_index) >= cfg.max_drop > 0:
                            break
        # remove dropped trees from the training score (dart.hpp:129-137):
        # negate the tree, then add it to the score
        for i in self.drop_index:
            for c in range(self.num_tree_per_iteration):
                tree = self.models[i * self.num_tree_per_iteration + c]
                tree.shrink(-1.0)
                self._add_tree_score_train(tree, c)
        kdrop = len(self.drop_index)
        tele = _telemetry_active()
        if tele is not None:
            tele.histogram("dart_dropped_trees").observe(kdrop)
            # JSONL growth bounded by the telemetry_freq cadence like
            # engine.train's iteration events; the histogram sees every drop
            if self.iter_ % tele.freq == 0:
                tele.event("dart_drop", iteration=int(self.iter_),
                           dropped=int(kdrop))
        if not self.config.xgboost_dart_mode:
            self.shrinkage_rate = self.config.learning_rate / (1.0 + kdrop)
        else:
            self.shrinkage_rate = (self.config.learning_rate if kdrop == 0 else
                                   self.config.learning_rate
                                   / (self.config.learning_rate + kdrop))

    def _normalize(self) -> None:
        """Re-add dropped trees normalized to k/(k+1) weight (dart.hpp:139-183)."""
        k = float(len(self.drop_index))
        cfg = self.config
        for i in self.drop_index:
            for c in range(self.num_tree_per_iteration):
                idx = i * self.num_tree_per_iteration + c
                tree = self.models[idx]
                if not cfg.xgboost_dart_mode:
                    # tree currently at -w; scale leaf values to w*k/(k+1)
                    tree.shrink(1.0 / (k + 1.0))     # -> -w/(k+1)
                    for vs in self.valid_sets:
                        self._add_tree_score_valid(idx, tree, c, vs)
                    tree.shrink(-k)                  # -> w*k/(k+1)
                    self._add_tree_score_train(tree, c)
                else:
                    tree.shrink(self.shrinkage_rate)
                    for vs in self.valid_sets:
                        self._add_tree_score_valid(idx, tree, c, vs)
                    tree.shrink(-k / cfg.learning_rate)
                    self._add_tree_score_train(tree, c)
            if not cfg.uniform_drop:
                j = i - self.num_init_iteration
                if not cfg.xgboost_dart_mode:
                    self.sum_weight -= self.tree_weight[j] / (k + 1.0)
                    self.tree_weight[j] *= k / (k + 1.0)
                else:
                    self.sum_weight -= self.tree_weight[j] / (k + cfg.learning_rate)
                    self.tree_weight[j] *= k / (k + cfg.learning_rate)
