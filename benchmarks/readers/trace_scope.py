"""Own device time, in ms per traced tree, of the fused chunk program's
instructions under one named scope: ``args["scope"]`` (``tree.root`` ...), or
``"unscoped"`` for what no scope claims.

The trace names a device event by its compiled instruction
(``%fusion.59``); ``lightgbm_tpu.obs.scopes.op_scopes`` reads the scope of
every instruction off the compiled program's text
(``GBDT.chunk_program_text``), and the two are joined by that name.  Ops whose
name starts with any of ``args["exclude_prefixes"]`` are left out — the Pallas
kernels and the ``%while`` / ``%cond`` instructions have metrics of their own
— so nothing is counted twice.  The scopes asked for are those of the metric
files that use this reader.  An event of another program inside the traced
span (the chunk boundary's ``isfinite``) counts under its name's scope in
the chunk program if there is one, else as unscoped: microseconds."""
import glob
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def all_scopes():
    found = set()
    for path in glob.glob(os.path.join(HERE, "..", "layer_metrics", "*.json")):
        with open(path) as fh:
            spec = json.load(fh)
        if spec["reader"] == "trace_scope":
            found.add(spec["args"]["scope"])
    return sorted(found)


def scope_of_ops(ctx):
    """{"%instruction": scope} of the job's fused chunk program, or None when
    the program cannot give its text or the scopes."""
    if "_scope_of_ops" in ctx:
        return ctx["_scope_of_ops"]
    ctx["_scope_of_ops"] = None
    job = ctx["job"]
    try:
        from lightgbm_tpu.obs import scopes
        t0 = time.perf_counter()
        text = job.gbdt.chunk_program_text(job.k)
    except (ImportError, AttributeError):
        return None
    if text is None:
        return None
    ctx["_scope_of_ops"] = scopes.op_scopes(text, all_scopes())
    print("scope map of the fused chunk program: %d instructions, %d "
          "characters of compiled text, %.3f s to get and read"
          % (len(ctx["_scope_of_ops"]), len(text), time.perf_counter() - t0),
          flush=True)
    return ctx["_scope_of_ops"]


def read(args, ctx):
    trace, trees = ctx["trace"], len(ctx["job"].traced_trees)
    if trace is None or not trees:
        return None
    scope_of = scope_of_ops(ctx)
    if scope_of is None:
        return None
    skip = tuple(args.get("exclude_prefixes", ()))
    mine = {op: ns for op, ns in trace["own"].items()
            if not op.startswith(skip)
            and scope_of.get(op, "unscoped") == args["scope"]}
    top = sorted(mine.items(), key=lambda kv: -kv[1])[:6]
    print("scope %s: %d ops, %.3f ms a tree; most: %s"
          % (args["scope"], len(mine), sum(mine.values()) / 1e6 / trees,
             ", ".join("%s %.3f" % (op, ns / 1e6 / trees) for op, ns in top)),
          flush=True)
    return sum(mine.values()) / 1e6 / trees
