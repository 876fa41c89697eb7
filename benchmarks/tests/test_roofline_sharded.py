"""``readers/roofline_sharded.py`` on the recorded trace: the table's work
divided by the chips traced before it is held against one chip's kernel."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

import trace_reduce as tr
from readers import roofline, roofline_sharded

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny.xplane.pb.gz")
ARGS = {"prefixes": ["%partition_hist_pallas"]}


def tree():
    """One split of 1000 rows into 300 and 700."""
    return SimpleNamespace(
        num_leaves=2, internal_count=np.array([1000.0]),
        left_child=np.array([-1]), right_child=np.array([-2]),
        leaf_count=np.array([300.0, 700.0]))


def ctx(chips):
    trace = dict(tr.reduce(FIXTURE, tr.UNIT_ANNOTATION), chips=chips)
    return {"trace": trace, "job": SimpleNamespace(traced_trees=[tree()] * 2),
            "cfg": {"features": 67, "params": {"max_bin": 255}},
            "device_kind": "TPU v5 lite"}


def test_one_chip_is_the_plain_reader():
    assert roofline_sharded.read(ARGS, ctx(1)) == pytest.approx(
        roofline.read(ARGS, ctx(1)), rel=1e-12)


def test_four_chips_share_the_tables_work():
    assert roofline_sharded.read(ARGS, ctx(4)) == pytest.approx(
        roofline.read(ARGS, ctx(1)) / 4, rel=1e-12)


def test_nothing_to_read():
    assert roofline_sharded.read(ARGS, dict(ctx(4), trace=None)) is None
    assert roofline_sharded.read({"prefixes": ["%no_such_kernel"]},
                                 ctx(4)) is None
