"""The readers that read what the program says of itself — its spans, its
buckets, its named scopes — on hand-made ``ctx`` dicts, and one rehearsal
that lists the metrics a CPU run can have.  (A rehearsal traces nothing, so
the trace-sourced metrics are checked here on made-up traces and on the chip.)
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from conftest import BENCH, ROOT

from readers import bucket_launches, program_span, trace_scope

B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def spec(metric):
    return json.load(open(os.path.join(BENCH, "layer_metrics",
                                       metric + ".json")))


def tree(counts, leaves):
    """A finished tree of ``leaves`` leaves whose internal nodes hold
    ``counts`` rows (the arrays are as long as the 255-leaf budget)."""
    internal = np.zeros(254)
    internal[:len(counts)] = counts
    return types.SimpleNamespace(num_leaves=leaves, internal_count=internal)


class Learner:
    bucket_plan = None
    bins = np.zeros(((1 << 20) + 4096, 1), np.uint8)


def ctx_of(trees, own=None, text=None):
    gbdt = types.SimpleNamespace(learner=Learner(),
                                 chunk_program_text=lambda k: text)
    job = types.SimpleNamespace(traced_trees=trees, gbdt=gbdt, k=8,
                                t_start=100.0)
    trace = None if own is None else {"own": own, "busy_ns": sum(own.values())}
    return {"job": job, "trace": trace,
            "cfg": {"params": {"num_leaves": 255}}}


# ---- bucket_launches -------------------------------------------------------

def test_bucket_launches_sum_to_the_trees_launches_dead_ones_included():
    full = tree([1 << 20, 500_000, 16385] + [16384] * 100 + [993] * 50
                + [992] * 101, 255)
    short = tree([1 << 20, 300], 3)                 # stopped after 2 splits
    ctx = ctx_of([full, short], own={
        "%partition_hist_pallas_c4096.3": 8e6,
        "%partition_hist_pallas_c1024.2": 3e6,
        "%partition_hist_pallas_small.1": 1e6})
    got = {b: bucket_launches.read({"bucket": b}, ctx)
           for b in ("small", "c1024", "c4096")}
    assert got == {"small": (101 + 1 + 252) / 2, "c1024": 150 / 2,
                   "c4096": (3 + 1) / 2}
    assert sum(got.values()) == 254
    table = bucket_launches.table(ctx)
    assert table["small"]["dead"] == 252 / 2
    assert table["c4096"]["rows"] == ((1 << 20) * 2 + 500_000 + 16385) / 2


def test_bucket_launches_nothing_to_read():
    assert bucket_launches.read({"bucket": "small"}, ctx_of([])) is None
    ctx = ctx_of([tree([5000], 2)])
    del ctx["job"].gbdt.learner                    # a program without a plan
    assert bucket_launches.read({"bucket": "small"}, ctx) is None


def test_a_bucket_the_plan_lacks_served_nothing():
    ctx = ctx_of([tree([5000, 100], 3)])
    ctx["job"].gbdt.learner.bins = np.zeros((8192, 1), np.uint8)
    ctx["cfg"]["params"]["num_leaves"] = 3
    assert bucket_launches.read({"bucket": "c4096"}, ctx) == 0.0
    assert bucket_launches.read({"bucket": "c1024"}, ctx) == 1.0
    assert bucket_launches.read({"bucket": "small"}, ctx) == 1.0


# ---- trace_scope -----------------------------------------------------------

TEXT = '''
ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.59 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/while/body/tree.finish/add"}
  %copy.7 = f32[8]{0} copy(%fusion.59)
  %dynamic-update-slice.961 = f32[8]{0} dynamic-update-slice(%p, %p), metadata={op_name="jit(f)/while/body/tree.store/dynamic_update_slice"}
  %partition_hist_pallas_c4096.14 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/tree.split/pallas_call"}
  %while.2 = f32[8]{0} while(%p), condition=%c, body=%b, metadata={op_name="jit(f)/while/body/tree.split/while"}
  ROOT %add.3 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/while/body/add"}
}
'''
OWN = {"%fusion.59": 6e6, "%copy.7": 2e6, "%dynamic-update-slice.961": 16e6,
       "%partition_hist_pallas_c4096.14": 400e6, "%while.2": 10e6,
       "%add.3": 1e6, "%reduce.1": 3e6}


def test_trace_scope_joins_the_trace_with_the_programs_text():
    ctx = ctx_of([tree([9], 2)] * 2, own=OWN, text=TEXT)
    args = spec("glue_finish_ms_per_tree.train")["args"]
    assert args["scope"] == "tree.finish"
    assert trace_scope.read(args, ctx) == pytest.approx((6 + 2) / 2)
    assert trace_scope.read(spec("glue_store_ms_per_tree.train")["args"],
                            ctx) == pytest.approx(16 / 2)
    # the kernel and the loop are under tree.split in the text, and left out
    assert trace_scope.read(spec("glue_split_ms_per_tree.train")["args"],
                            ctx) == 0.0
    # what no scope claims, and an op the text does not have
    assert trace_scope.read(spec("glue_unscoped_ms_per_tree.train")["args"],
                            ctx) == pytest.approx((1 + 3) / 2)


def test_the_glue_metrics_add_up_to_busy_less_the_kernels():
    ctx = ctx_of([tree([9], 2)], own=OWN, text=TEXT)
    from readers import trace_ops
    glue = [m["name"] for m in B["per_layer"]
            if m["name"].startswith("glue_")
            and "higgs_train" in m["workloads"]]
    assert len(glue) == 10
    total = sum((trace_scope if spec(m)["reader"] == "trace_scope"
                 else trace_ops).read(spec(m)["args"], ctx) for m in glue)
    outside = trace_ops.read(spec("xla_glue_ms_per_tree.train")["args"], ctx)
    assert total == pytest.approx(outside) == pytest.approx(38.0)


def test_trace_scope_nothing_to_read():
    args = spec("glue_root_ms_per_tree.train")["args"]
    assert trace_scope.read(args, ctx_of([tree([9], 2)], text=TEXT)) is None
    assert trace_scope.read(args, ctx_of([], own=OWN, text=TEXT)) is None
    assert trace_scope.read(args, ctx_of([tree([9], 2)], own=OWN)) is None
    ctx = ctx_of([tree([9], 2)], own=OWN, text=TEXT)
    del ctx["job"].gbdt.chunk_program_text     # a program from before PR 27
    assert trace_scope.read(args, ctx) is None
    del ctx["job"].k                           # a kind that runs no chunk
    ctx.pop("_scope_of_ops")
    assert trace_scope.read(args, ctx) is None


def test_the_scopes_asked_for_are_those_with_a_metric():
    assert trace_scope.all_scopes() == sorted(
        ["gbdt.gradients", "gbdt.sample", "tree.store", "tree.root",
         "tree.pick_leaf", "tree.split", "tree.find_split", "tree.unpack",
         "tree.state_update", "tree.finish", "unscoped"])


# ---- program_span ----------------------------------------------------------

def test_program_span_counts_what_ended_before_the_window():
    from lightgbm_tpu.obs import spans
    spans.reset()
    spans._keep(1, 0, "ingest.upload", 10.0, 12.5)
    spans._keep(2, 0, "ingest.upload", 99.0, 100.5)     # ended in the window
    spans._keep(3, 0, "gbdt.construct", 9.0, 13.0)
    ctx = ctx_of([])
    args = spec("ingest_upload_s.train")["args"]
    assert args == {"span": "ingest.upload", "until": "t_start"}
    assert program_span.read(args, ctx) == pytest.approx(2.5)
    assert program_span.read({"span": "ingest.upload"}, ctx) \
        == pytest.approx(4.0)
    assert program_span.read({"span": "no.such.span", "until": "t_start"},
                             ctx) == 0.0
    spans.reset()


def test_program_span_nothing_to_read(monkeypatch):
    monkeypatch.setattr(program_span, "_records", lambda name: None)
    assert program_span.read({"span": "x"}, ctx_of([])) is None


# ---- a rehearsal lists what a CPU run can have -----------------------------

def test_rehearsal_would_report_the_span_metrics(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "higgs_train", "--seed", "2147483659", "--seconds", "1", "--trace",
         "1", "--rehearse-rows", "4096"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = next(ln for ln in done.stdout.splitlines()
                if ln.startswith("rehearsal on cpu: would report"))
    spans_metrics = [m["name"] for m in B["per_layer"]
                     if m["source"] == "program_span"
                     and "higgs_train" in m["workloads"]]
    assert len(spans_metrics) == 9
    for name in spans_metrics:
        assert repr(name) in line, name
