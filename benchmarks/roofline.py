"""Operations and bytes the split kernel's algorithm needs, and the least time
the chip could take for them.  Kept with the benchmark so that no PR that
claims a gain can change the yardstick."""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

# The kernel splits each f32 gradient value into a bf16 hi and a bf16 lo part
# (core/histogram.py::_hilo_split) and contracts both against the one-hot in
# one MXU operand of 4 rows (grad_hi, hess_hi, grad_lo, hess_lo): two bf16
# multiply-adds per value where an f32 MXU would need one.
BF16_PASSES = 2


def peaks(device_kind):
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in peaks.json; add a row "
                       "with its source" % device_kind)
    return table[device_kind]


def tree_rows(trees):
    """(window rows, smaller-child rows, right-child rows) of every split of
    ``trees``: a split's window is its node's ``internal_count`` (the
    program's own count, which it estimates from hessians as LightGBM does),
    a child's rows its ``internal_count`` when it split again, else its
    ``leaf_count``.  The kernel places left rows in the window and streams
    right rows to a scratch, then copies that block back behind the left one,
    so a right row is read and written twice."""
    window_rows = small_rows = right_rows = 0
    for t in trees:
        for node in range(int(t.num_leaves) - 1):
            window_rows += int(t.internal_count[node])
            kids = []
            for c in (int(t.left_child[node]), int(t.right_child[node])):
                kids.append(int(t.internal_count[c]) if c >= 0
                            else int(t.leaf_count[~c]))
            small_rows += min(kids)
            right_rows += kids[1]
    return window_rows, small_rows, right_rows


def split_work(trees, *, features, bins, code_bytes=1):
    """(bytes, operations, window rows, smaller-child rows) of partitioning
    every window of ``trees`` and histogramming the smaller child of each
    split.

    Bytes: each window row is read once and written once, its bin codes plus
    the f32 gradient pair: 2 * (F * code_bytes + 8).
    Operations: the one-hot histogram of the smaller child,
    rows * F * bins * 2 values * 2 (multiply, add) * BF16_PASSES."""
    window_rows, small_rows, _ = tree_rows(trees)
    nbytes = window_rows * 2 * (features * code_bytes + 8)
    ops = small_rows * features * bins * 2 * 2 * BF16_PASSES
    return nbytes, ops, window_rows, small_rows


def least_seconds(nbytes, ops, peak):
    """(seconds, which bound sets them)."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
