"""Subsampling accounting: what a booster samples a tree, and which path
built each tree.

Same contract as the launch gauge (:mod:`.launches`) and the bundling counts
(:mod:`.efb`): always on, one call per booster bound to its data and one per
chunk or iteration finished, read without a telemetry run::

    counts() -> {"sampling.features": 28, "sampling.features_used": 22,
                 "sampling.bag_fraction": 0.8, "sampling.bag_freq": 5,
                 "sampling.fused_trees": 56, "sampling.per_iteration_trees": 0,
                 "sampling.bag_rows": 8400312}

``sampling.features`` are the data set's used features and
``sampling.features_used`` those a tree may split on (``feature_fraction``:
``max(1, round(F * fraction))``, all of them when it is 1);
``sampling.bag_fraction`` / ``sampling.bag_freq`` the plain bagging in force
(1.0 / 0 when there is none).  ``sampling.fused_trees`` and
``sampling.per_iteration_trees`` count the trees each path has finished since
:func:`reset`: a fused ``train_chunk`` program, or ``train_one_iter`` (one
tree a host round), so a run that was meant to stay fused can say that it
did, which ``GBDT._fuse_failed`` cannot.  ``sampling.bag_rows`` is the
realised bag of the newest finished tree: the fused scan emits one count a
tree (4 bytes), kept as the device array it is and fetched when somebody
asks, so recording it never waits for the chunk.
"""
from __future__ import annotations

import threading
from typing import Dict

_lock = threading.Lock()
_config: Dict[str, float] = {}
_trees = {"fused": 0, "per_iteration": 0}
_bag_rows = None      # an int, or the fused scan's [k] counts still on the device


def record_config(features: int, features_used: int, bag_fraction: float,
                  bag_freq: int) -> None:
    """Note what the booster just bound to its data samples a tree."""
    global _bag_rows
    with _lock:
        _config.clear()
        _config.update({"sampling.features": int(features),
                        "sampling.features_used": int(features_used),
                        "sampling.bag_fraction": float(bag_fraction),
                        "sampling.bag_freq": int(bag_freq)})
        _bag_rows = None


def record_trees(path: str, trees: int, bag_rows) -> None:
    """Note ``trees`` trees finished by ``path`` ("fused" /
    "per_iteration"); ``bag_rows``: the newest one's bag, an int or an array
    of counts whose last entry it is."""
    global _bag_rows
    with _lock:
        _trees[path] += int(trees)
        _bag_rows = bag_rows


def counts() -> Dict[str, float]:
    """The last bound booster's sampling and the trees by path since
    :func:`reset`; empty before the first booster."""
    with _lock:
        if not _config:
            return {}
        out = dict(_config)
        out.update({"sampling.%s_trees" % p: n for p, n in _trees.items()})
        newest = _bag_rows
    if newest is not None:
        import numpy as np
        out["sampling.bag_rows"] = int(np.asarray(newest).reshape(-1)[-1])
    return out


def reset() -> None:
    """Zero the trees by path (the configuration and the newest bag stay)."""
    with _lock:
        for path in _trees:
            _trees[path] = 0
