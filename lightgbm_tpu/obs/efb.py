"""Feature-bundling accounting: what EFB made of the last table ingested.

Same contract as the launch gauge (:mod:`.launches`): always on, one call per
data set built (``BinnedDataset.from_matrix`` / ``from_csr``), read without a
telemetry run::

    counts() -> {"efb.features": 671, "efb.groups": 8, "efb.conflict_rows": 0}

``efb.features`` are the used (non-trivial) features, ``efb.groups`` the
device columns they were bundled into, and ``efb.conflict_rows`` the rows of
the whole table in which one feature of a group overwrote another's code (the
greedy grouping admits a few conflicts of its SAMPLE; this is what they came
to on every row).

A learner built on a bundled table adds ``efb.search_lanes``: the candidate
lanes its split search evaluates for ONE leaf.  ``groups x group bins`` when it
searches the group histogram's own lanes (2,304 on the benchmark's one-hot
table), ``features x feature bins`` when it unbundles first (179,200 there: a
table with a categorical feature, or CEGB), so the count says which path a run
took without a trace.  A new data set drops it: no learner has been built on
that one yet.
"""
from __future__ import annotations

import threading
from typing import Dict

_lock = threading.Lock()
_counts: Dict[str, int] = {}


def record(dataset) -> None:
    """Note the bundling of ``dataset`` (a ``BinnedDataset`` just built)."""
    with _lock:
        _counts.clear()
        _counts.update({
            "efb.features": len(dataset.used_feature_idx),
            "efb.groups": len(dataset.feature_groups),
            "efb.conflict_rows": int(dataset.conflict_rows)})


def record_search_lanes(lanes: int) -> None:
    """Note the lanes a learner just built searches a leaf (bundled tables)."""
    with _lock:
        _counts["efb.search_lanes"] = int(lanes)


def counts() -> Dict[str, int]:
    """The last recorded data set's counts; empty before the first."""
    with _lock:
        return dict(_counts)
