"""Categorical-feature accounting: what the split search of the last learner
built sorts and scans a leaf, and how many categorical splits the finished
trees hold.

Same contract as the bundling counts (:mod:`.efb`) and the sampling counts
(:mod:`.sampling`): always on, one call per learner built and one per tree
turned into a host :class:`~lightgbm_tpu.core.tree.Tree`, read without a
telemetry run::

    counts() -> {"cat.features": 6, "cat.bins": 589, "cat.scan_steps": 32,
                 "cat.splits": 1391, "cat.onehot_splits": 0}

``cat.features`` are the used features binned as categories and ``cat.bins``
the sum of their bins: what one leaf's search sorts.  ``cat.scan_steps`` are
the sorted positions a direction that the many-vs-many search walks
(``core/split.py::cat_scan_steps``: ``min(feature bins,
max_cat_threshold)``, the lanes of one window and no loop's trips); 0 for a
learner with no categorical feature, whose programs trace none of the search.  ``cat.splits`` are the categorical splits
of the trees finished since :func:`reset` and ``cat.onehot_splits`` those of
them on a feature searched one category against the rest (``num_bin <=
max_cat_to_onehot``); the rest are many-vs-many.
"""
from __future__ import annotations

import threading
from typing import Dict

_lock = threading.Lock()
_learner: Dict[str, int] = {}
_splits = {"cat.splits": 0, "cat.onehot_splits": 0}
_max_onehot_bins = 0


def record_learner(features: int, bins: int, scan_steps: int,
                   max_cat_to_onehot: int) -> None:
    """Note the categorical search of the learner just built."""
    global _max_onehot_bins
    with _lock:
        _learner.clear()
        _learner.update({"cat.features": int(features),
                         "cat.bins": int(bins),
                         "cat.scan_steps": int(scan_steps)})
        _max_onehot_bins = int(max_cat_to_onehot)


def record_split(num_bin: int) -> None:
    """Note one categorical split of a finished tree, on a feature of
    ``num_bin`` bins."""
    with _lock:
        _splits["cat.splits"] += 1
        _splits["cat.onehot_splits"] += int(num_bin <= _max_onehot_bins)


def counts() -> Dict[str, int]:
    """The last learner's search and the splits since :func:`reset`; empty
    before the first learner."""
    with _lock:
        return dict(_learner, **_splits) if _learner else {}


def reset() -> None:
    """Zero the splits (the learner's counts stay)."""
    with _lock:
        for name in _splits:
            _splits[name] = 0
