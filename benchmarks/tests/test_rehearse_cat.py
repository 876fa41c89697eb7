"""``run.py --rehearse-rows`` of ``expo_cat_train`` end to end on the CPU: the
categorical kind's control flow, its seven checks (both trees of
``plain_first_splits`` among them) and the shape of the last line."""
import json
import os
import subprocess
import sys

from conftest import BENCH

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CHECKS = ["no_degraded_path", "no_recompile_in_window", "training_loss_falls",
          "categorical_ingest", "plain_first_splits", "plain_leaf_values",
          "plain_walk"]


def rehearse(tmp_path, rows=4096):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "expo_cat_train", "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse-rows", str(rows)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout.strip().splitlines()


def test_rehearsal_ends_in_the_contracts_line(tmp_path):
    lines = rehearse(tmp_path)
    last = json.loads(lines[-1])
    assert set(last) == KEYS
    # a CPU number never appears under a metric's name
    assert last["correct"] is False and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] >= 1 and last["failed"] == 0
    checks = [ln for ln in lines if ln.startswith(("ok ", "NOT"))]
    assert [ln.split()[1].rstrip(":") for ln in checks] == CHECKS
    assert all(ln.startswith("ok ") for ln in checks), checks
    said = next(ln for ln in checks if "plain_first_splits" in ln)
    assert "tree 0 (" in said and "tree 4 (" in said
    assert "traced trees 64-71" in "\n".join(lines)
    would = next(ln for ln in lines if ln.startswith("rehearsal on cpu"))
    for name in ("cat_scan_steps_per_leaf.cat", "cat_splits_share.cat",
                 "unit_wall_ms_per_tree.cat"):
        assert name in would
