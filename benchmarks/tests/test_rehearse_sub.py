"""``run.py --rehearse-rows`` of ``higgs_prod_train`` end to end on the CPU:
the subsampled kind's control flow, its eight checks (both trees of
``plain_first_splits`` among them) and the shape of the last line."""
import json
import os
import subprocess
import sys

from conftest import BENCH

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CHECKS = ["no_degraded_path", "no_recompile_in_window", "plain_walk",
          "training_loss_falls", "fused_every_tree", "sampled_as_configured",
          "mask_honoured", "plain_first_splits"]


def test_rehearsal_ends_in_the_contracts_line(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "higgs_prod_train", "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse-rows", "24576"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == KEYS
    # a CPU number never appears under a metric's name
    assert last["correct"] is False and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] >= 1 and last["failed"] == 0
    checks = [ln for ln in lines if ln.startswith(("ok ", "NOT"))]
    assert [ln.split()[1].rstrip(":") for ln in checks] == CHECKS
    assert all(ln.startswith("ok ") for ln in checks), checks
    said = checks[-1]
    assert "tree 0 on" in said and "tree 8 on" in said
    would = next(ln for ln in lines if ln.startswith("rehearsal on cpu"))
    for name in ("bag_rows_share.sub", "features_used_per_tree.sub",
                 "per_iteration_trees.sub"):
        assert name in would
