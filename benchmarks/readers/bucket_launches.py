"""Split-kernel launches per traced tree served by one size bucket of the
builder's dispatch plan: ``args["bucket"]`` is ``small``, ``c1024`` or
``c4096`` (``core/partition.py::bucket_name``).

A finished tree says which bucket served each split: ``partition.bucket_of``
puts a node's ``internal_count`` — its window's rows — through the bounds the
builder's ``searchsorted`` uses.  ``internal_count`` is the program's own
count, which it estimates from hessians as LightGBM does (the roofline
reader takes its window rows from it too): on a 400,000-row check against
rows routed through the tree, 7% off for the median node, 1.2% in the sum,
and 4 of 254 windows beside a bound counted in the bucket next to theirs.
A tree that stopped short of ``num_leaves``
still launched the smallest bucket on an empty window for every split it did
not make; those dead launches count under the smallest bucket and are printed
apart.  Also printed, per bucket: window rows a tree and, from the trace, ns
per window row and us per launch."""
import numpy as np

import trace_reduce


def table(ctx):
    """{bucket name: {"launches", "dead", "rows"}} per traced tree, or None
    when the program cannot say (no traced trees, no ``bucket_of``)."""
    if "_bucket_table" in ctx:
        return ctx["_bucket_table"]
    ctx["_bucket_table"] = None
    job = ctx["job"]
    trees = job.traced_trees
    try:
        from lightgbm_tpu.core import partition
        learner = job.gbdt.learner
        plan = learner.bucket_plan or partition.fused_bucket_plan(
            int(learner.bins.shape[0]))
        names = [partition.bucket_name(s, c) for s, c, _ in plan]
        bucket_of = partition.bucket_of
    except (ImportError, AttributeError):
        return None
    if not trees:
        return None
    out = {n: {"launches": 0.0, "dead": 0.0, "rows": 0.0} for n in names}
    for t in trees:
        made = int(t.num_leaves) - 1
        rows = np.asarray(t.internal_count[:made], np.int64)
        for i, r in zip(bucket_of(rows, plan), rows):
            out[names[i]]["launches"] += 1
            out[names[i]]["rows"] += int(r)
        out[names[0]]["dead"] += int(ctx["cfg"]["params"]["num_leaves"]) \
            - 1 - made
    for n in names:
        for k in out[n]:
            out[n][k] /= len(trees)
        out[n]["launches"] += out[n]["dead"]
        line = ("bucket %s, per traced tree: %.3f launches (%.3f of them "
                "dead), %.1f window rows"
                % (n, out[n]["launches"], out[n]["dead"], out[n]["rows"]))
        trace = ctx["trace"]
        if trace is not None:
            ns = trace_reduce.own_of(
                trace["own"], ["%partition_hist_pallas_" + n]) / len(trees)
            line += ("; %.3f ms of kernel, %.3f ns per window row, %.2f us "
                     "per launch"
                     % (ns / 1e6, ns / max(out[n]["rows"], 1.0),
                        ns / 1e3 / max(out[n]["launches"], 1.0)))
        print(line, flush=True)
    ctx["_bucket_table"] = out
    return out


def read(args, ctx):
    found = table(ctx)
    if found is None:
        return None
    # a bucket the plan of this table does not have served no split
    return found.get(args["bucket"], {"launches": 0.0})["launches"]
