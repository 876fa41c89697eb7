"""``run.py --rehearse-rows`` end to end on the CPU, once per job kind: the
control flow of a run, every check, and the shape of the last line."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, HERE

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,trace", [("higgs_train", 1),
                                        ("higgs1m_train_api", 0)])
def test_rehearsal_ends_in_the_contracts_line(cell, trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--cells-dir", os.path.join(HERE, "cells"), "--seed", "2147483659",
         "--seconds", "2", "--trace", str(trace), "--rehearse-rows", "20000"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == KEYS
    # a CPU number never appears under a metric's name
    assert last["correct"] is False and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] >= 1 and last["failed"] == 0
    checks = [ln for ln in lines if ln.startswith(("ok ", "NOT"))]
    assert len(checks) == 5 and all(ln.startswith("ok ") for ln in checks)


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "higgs_train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "{" not in done.stdout
