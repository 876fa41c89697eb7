"""``run.py --rehearse-rows`` of the four-chip cell on 4 virtual CPU devices:
the control flow of a run, every check of kind ``train_api_sharded``, and the
shape of the last line."""
import json
import os
import subprocess
import sys

from conftest import BENCH

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_four_chip_cell_rehearses_to_the_contracts_line(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "criteo_dp4_train", "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--rehearse-rows", "40000"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=1200)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == KEYS
    assert last["correct"] is False and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 4
    assert last["attempted"] >= 1 and last["failed"] == 0
    checks = [ln for ln in lines if ln.startswith(("ok ", "NOT"))]
    assert len(checks) == 7 and all(ln.startswith("ok ") for ln in checks)
    assert [ln.split()[1].rstrip(":") for ln in checks][-3:] == [
        "sharded_4", "plain_first_splits", "no_row_collective"]
    would = next(ln for ln in lines if ln.startswith("rehearsal on cpu"))
    for name in ("collectives_per_tree.dp", "comm_bytes_per_tree.dp",
                 "row_collectives.dp", "shard_upload_s.dp",
                 "launches_per_tree"):
        assert name in would
