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
"""
from __future__ import annotations

import threading
from typing import Dict

_lock = threading.Lock()
_counts: Dict[str, int] = {}


def record(dataset) -> None:
    """Note the bundling of ``dataset`` (a ``BinnedDataset`` just built)."""
    with _lock:
        _counts.update({
            "efb.features": len(dataset.used_feature_idx),
            "efb.groups": len(dataset.feature_groups),
            "efb.conflict_rows": int(dataset.conflict_rows)})


def counts() -> Dict[str, int]:
    """The last recorded data set's counts; empty before the first."""
    with _lock:
        return dict(_counts)
