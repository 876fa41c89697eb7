"""A traced run traces the same trees however long ``--seconds`` is and
however fast the program: ``trace_first_tree`` of the traffic file, not the
clock, ends its untraced stretch.  Rehearsals on the CPU (the profiler off,
the control flow the chip's), one per kind that cuts a window, and the rule
that the index is the warm-up plus whole units."""
import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH, HERE

import gbdt_job


def rehearse(cell, seconds, trace, tmp_path, rows=4096):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--cells-dir", os.path.join(HERE, "cells"), "--seed", "2147483659",
         "--seconds", str(seconds), "--trace", str(trace), "--rehearse-rows",
         str(rows)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    window, = [ln for ln in done.stdout.splitlines()
               if ln.startswith("window ")]
    return window, json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,traced,units", [
    ("tiny_trace", "traced trees 6-7", 2 + 1),            # chunks of 2
    ("higgs1m_train_api", "traced trees 32-47", 16 + 16)])  # iterations
def test_two_window_lengths_trace_the_same_trees(cell, traced, units,
                                                 tmp_path):
    short, last_short = rehearse(cell, 0.01, 1, tmp_path)
    long_, last_long = rehearse(cell, 4, 1, tmp_path)
    assert traced in short and traced in long_
    # the untraced stretch is as long in both: the clock does not end it
    assert last_short["attempted"] == last_long["attempted"] == units


def test_an_untraced_window_goes_by_the_clock(tmp_path):
    short, _ = rehearse("tiny_trace", 0.01, 0, tmp_path)
    long_, _ = rehearse("tiny_trace", 3, 0, tmp_path)
    assert "traced trees" not in short + long_
    chunks = [int(re.search(r": (\d+) chunks", w).group(1))
              for w in (short, long_)]
    assert chunks[0] == 1 and chunks[1] > 3


@pytest.mark.parametrize("wl,warmup,unit", [
    ({"trace_first_tree": 60}, 8, 8), ({"trace_first_tree": 8}, 8, 8),
    ({"trace_first_tree": 46}, 4, 4), ({"trace_first_tree": 0}, 4, 4)])
def test_an_index_off_the_units_is_an_error(wl, warmup, unit):
    with pytest.raises(ValueError, match="whole number"):
        gbdt_job.trace_first_tree(wl, warmup, unit)


def test_a_kind_refuses_such_a_traffic_file_before_any_data(monkeypatch):
    from kinds import train_api, train_chunks
    monkeypatch.setattr(gbdt_job, "make_data", lambda *a, **k: pytest.fail(
        "data was made under a traffic file that cannot be traced"))
    with pytest.raises(ValueError):
        train_chunks.Job({}, {"trees_per_chunk": 8, "auc_trees": 16,
                              "trace_units": 1, "trace_first_tree": 60}, 1)
    with pytest.raises(ValueError):
        train_api.Job({}, {"warmup_iters": 4, "auc_trees": 16,
                           "trace_units": 4, "trace_first_tree": 46}, 1)
    assert gbdt_job.trace_first_tree({}, 8, 8) is None
    assert gbdt_job.trace_first_tree({"trace_first_tree": 64}, 8, 8) == 64
    assert gbdt_job.trace_first_tree({"trace_first_tree": 48}, 4, 4) == 48
