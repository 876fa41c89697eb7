"""``readers/trace_scope_sharded.py`` on the texts of a data-parallel
iteration's programs (4 virtual CPU devices, in a process of its own) against
a made-up trace, and ``readers/program_span_window.py`` on the span API."""
import json
import os
import subprocess
import sys
import time
import types

from conftest import BENCH, ROOT

PROBE = r"""
import json, re, sys
sys.path[:0] = [%r, %r]
import numpy as np
import lightgbm_tpu as lgb
from readers import trace_scope_sharded as reader
rng = np.random.RandomState(0)
X = rng.normal(size=(2001, 9)).astype(np.float32)
y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
gbdt = lgb.train(dict(objective="binary", tree_learner="data", num_leaves=7,
                      min_data_in_leaf=5, verbosity=-1),
                 lgb.Dataset(X, label=y), num_boost_round=2)._booster
texts = gbdt.iteration_program_texts()
names = sorted({n for t in texts
                for n in re.findall(r"^\s*(?:ROOT )?(%%\S+) = ", t, re.M)})
own = {n: 1000 for n in names}          # every instruction ran 1 us
ctx = {"trace": {"own": own}, "job": type("J", (), {
    "traced_trees": [0, 0], "gbdt": gbdt})()}
TREE = ["tree.store", "tree.root", "tree.pick_leaf", "tree.split",
        "tree.find_split", "tree.state_update", "tree.finish"]
COMM = ["comm.hist_reduce", "comm.best_split", "comm.sums"]
COLL = ["%%all-reduce", "%%reduce-scatter", "%%all-gather", "%%reduce_scatter",
        "%%all_gather"]
out = {"ops": len(names), "excluded": sum(n.startswith(tuple(COLL + ["%%while"]))
                                          for n in names)}
for s in TREE + ["unscoped"]:
    out[s] = reader.read({"scope": s, "among": TREE,
                          "exclude_prefixes": COLL + ["%%while"]}, ctx)
for s in COMM:
    out[s] = reader.read({"scope": s, "among": COMM, "only_prefixes": COLL},
                         ctx)
print(json.dumps(out))
"""


def test_scopes_split_the_iterations_programs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, "-c", PROBE % (BENCH, ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    tree = [out[k] for k in out if k.startswith("tree.")]
    # 1 us an op over 2 traced trees: every op that is not excluded counts
    # under exactly one of the tree scopes or as unscoped
    assert abs(sum(tree) + out["unscoped"]
               - (out["ops"] - out["excluded"]) * 1e-3 / 2) < 1e-9
    assert out["tree.find_split"] > 0 and out["tree.root"] > 0
    assert out["unscoped"] > 0          # the per-row programs, at the least
    # the collectives carry their phase: the reduce of the root's and the
    # loop's histogram, the gather of their best split, the root's sums
    # (the made-up trace also "ran" the instructions inside fusions, which
    # is where the reduce's further names come from)
    assert out["comm.hist_reduce"] >= 2 * 1e-3 / 2
    assert out["comm.best_split"] == 2 * 1e-3 / 2      # one under vmap
    assert out["comm.sums"] == 1 * 1e-3 / 2


def test_a_collective_the_compiler_made_takes_its_reductions_scope():
    """The chip's compiler runs the loop's reduce-scatter as an all-reduce
    with no path of the program on it; its reduction still names the scope."""
    from readers import trace_scope_sharded as reader
    text = """
%region_56.57.clone (reduce_scatter.31: f32[], reduce_scatter.32: f32[]) -> f32[] {
  %reduce_scatter.31 = f32[] parameter(0), metadata={op_name="jit(b)/while/body/tree.find_split/comm.hist_reduce/reduce_scatter"}
  %reduce_scatter.32 = f32[] parameter(1), metadata={op_name="jit(b)/while/body/tree.find_split/comm.hist_reduce/reduce_scatter"}
  ROOT %add.1602 = f32[] add(%reduce_scatter.31, %reduce_scatter.32), metadata={op_name="tree.find_split/comm.hist_reduce/add"}
}

%add.18 (x: u32[], y: u32[]) -> u32[] {
  %x = u32[] parameter(0)
  %y = u32[] parameter(1)
  ROOT %add.19 = u32[] add(%x, %y)
}

ENTRY %main (p: f32[68,2,256]) -> f32[68,2,256] {
  %p = f32[68,2,256] parameter(0)
  %all-reduce.18 = u32[80] all-reduce(%q), channel_id=3, to_apply=%add.18
  ROOT %all-reduce.23 = f32[68,2,256] all-reduce(%p), channel_id=4, to_apply=%region_56.57.clone
}
"""
    comm = ["comm.hist_reduce", "comm.best_split", "comm.sums"]
    assert reader.applied_scopes(text, comm) == {
        "%all-reduce.23": "comm.hist_reduce"}
    # among the builder's phases the same reduction says tree.find_split
    assert reader.applied_scopes(text, ["tree.find_split", "tree.root"]) == {
        "%all-reduce.23": "tree.find_split"}


def test_span_inside_the_window_per_tree():
    from lightgbm_tpu.obs import spans
    from readers import program_span_window as reader
    with spans.span("test.dispatch"):
        pass                                        # before the window
    t_start = time.perf_counter()
    for _ in range(3):
        with spans.span("test.dispatch"):
            time.sleep(0.01)
    t_end = time.perf_counter()
    with spans.span("test.dispatch"):
        pass                                        # after it
    job = types.SimpleNamespace(t_start=t_start, t_end=t_end, window_trees=3)
    got = reader.read({"span": "test.dispatch"}, {"job": job})
    assert 10.0 <= got < 1e3 * (t_end - t_start) / 3
    assert reader.read({"span": "test.none"}, {"job": job}) is None
