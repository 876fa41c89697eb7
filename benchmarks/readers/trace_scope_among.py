"""One level below ``readers/trace_scope.py``: own device time, in ms per
traced tree, of the instructions whose innermost scope AMONG ``args["among"]``
(``find.hist_cache`` ... ``find.bests``; ``chunk.score_out``) is
``args["scope"]``, counted only where ``trace_scope``'s own map puts the
instruction under one of the parents ``args["within"]`` (``tree.find_split``
and ``tree.unpack``; ``unscoped`` for the chunk's epilogue).
``"scope": "rest"`` is the parents' instructions that no child claims, so the
parts and the rest add up to the parents' own metrics (``glue_find_split_``
plus ``glue_unpack_`` on a bundled table) as long as ``exclude_prefixes`` is
theirs.  The parents' map is never touched: the accepted metrics read what
they read.

Two maps of the same compiled text(s), joined with the trace by instruction
name: the parents' is ``trace_scope.scope_of_ops`` (the fused chunk program)
or, on a job with no chunk program, ``trace_scope_sharded.scope_of_ops`` over
``GBDT.iteration_program_texts()`` among the scopes its own metric files
name; the children's is ``obs.scopes.op_scopes(text, among)`` (with the
sharded reader's handling of ``vmap(...)`` and ``to_apply=``), then
``obs.scopes.bare_op_scopes``: the running sums lower through a cached
function and carry no path of the program (``obs.scopes.BARE_OPS``).

A program that opens none of ``among`` (every commit before PR 39) has
nothing to read: None, and the line leaves the metric out."""
import glob
import json
import os
import time

from readers import trace_scope, trace_scope_sharded

HERE = os.path.dirname(os.path.abspath(__file__))
REST = "rest"


def sharded_parents_among(within):
    """The ``among`` list of the ``trace_scope_sharded`` metric files that
    name the parents ``within``: the map those metrics read."""
    wanted = {w for w in within if w != "unscoped"}
    for path in sorted(glob.glob(os.path.join(HERE, "..", "layer_metrics",
                                              "*.json"))):
        with open(path) as fh:
            spec = json.load(fh)
        among = spec["args"].get("among", ())
        if spec["reader"] == "trace_scope_sharded" and wanted & set(among):
            return list(among)
    return None


def program_texts(ctx):
    """The compiled texts the trace's instructions come from: the fused
    chunk program's, else the iteration's programs' (the build last)."""
    if "_among_texts" not in ctx:
        gbdt, texts = ctx["job"].gbdt, None
        try:
            text = gbdt.chunk_program_text(ctx["job"].k)
            texts = None if text is None else [text]
        except AttributeError:
            pass
        if texts is None:
            try:
                texts = gbdt.iteration_program_texts()
            except AttributeError:
                pass
        ctx["_among_texts"] = texts or None
    return ctx["_among_texts"]


def parents_of_ops(ctx, within):
    found = trace_scope.scope_of_ops(ctx)
    if found is None:
        among = sharded_parents_among(within)
        if among is not None:
            found = trace_scope_sharded.scope_of_ops(ctx, among)
    return found


def children_of_ops(ctx, among):
    """{"%instruction": scope among ``among``}, or None when the program
    opens none of them."""
    cache = ctx.setdefault("_among_children", {})
    key = tuple(among)
    if key in cache:
        return cache[key]
    cache[key] = None
    try:
        from lightgbm_tpu.obs import scopes
        bare = {name: scope for name, scope in scopes.BARE_OPS.items()
                if scope in among}
    except (ImportError, AttributeError):
        return None
    texts = program_texts(ctx)
    if texts is None:
        return None
    t0 = time.perf_counter()
    found = {}
    for text in texts:                  # the build comes last and wins
        text = trace_scope_sharded._TRANSFORMED.sub(r"\1", text)
        of_text = scopes.op_scopes(text, among)
        for name, scope in trace_scope_sharded.applied_scopes(
                text, among).items():
            if of_text.get(name, scopes.UNSCOPED) == scopes.UNSCOPED:
                of_text[name] = scope
        if any(s != scopes.UNSCOPED for s in of_text.values()):
            # a program that opens one of them: its running sums are known
            of_text.update(scopes.bare_op_scopes(text, bare))
        found.update(of_text)
    print("scope map among %s: %d instructions, %d of them claimed, %.3f s "
          "to read" % (", ".join(among), len(found),
                       sum(s != scopes.UNSCOPED for s in found.values()),
                       time.perf_counter() - t0), flush=True)
    if all(s == scopes.UNSCOPED for s in found.values()):
        return None
    cache[key] = found
    return found


def read(args, ctx):
    trace, trees = ctx["trace"], len(ctx["job"].traced_trees)
    if trace is None or not trees:
        return None
    children = children_of_ops(ctx, args["among"])
    if children is None:
        return None
    parents = parents_of_ops(ctx, args["within"])
    if parents is None:
        return None
    skip = tuple(args.get("exclude_prefixes", ()))
    want = "unscoped" if args["scope"] == REST else args["scope"]
    mine = {op: ns for op, ns in trace["own"].items()
            if not op.startswith(skip)
            and parents.get(op, "unscoped") in args["within"]
            and children.get(op, "unscoped") == want}
    top = sorted(mine.items(), key=lambda kv: -kv[1])[:6]
    print("scope %s within %s: %d ops, %.3f ms a tree; most: %s"
          % (args["scope"], " + ".join(args["within"]), len(mine),
             sum(mine.values()) / 1e6 / trees,
             ", ".join("%s %.3f" % (op, ns / 1e6 / trees) for op, ns in top)),
          flush=True)
    return sum(mine.values()) / 1e6 / trees
