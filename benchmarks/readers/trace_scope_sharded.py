"""``readers/trace_scope.py``'s reading on the per-iteration path of a cell
whose rows are sharded: own device time, in ms per traced tree, of the
instructions under one named scope, ``args["scope"]``, chosen among
``args["among"]`` (``"unscoped"``: what none of them claims).

The texts are those of the iteration's programs
(``GBDT.iteration_program_texts()``: the per-row programs, then the sharded
build); an instruction name that two programs share takes the build's scope,
since the build is where the time is.  ``args["exclude_prefixes"]`` leaves ops
out (the kernels, the collectives and the loop instructions have metrics of
their own), ``args["only_prefixes"]`` keeps only those (the collectives, for
the ``comm.*`` scopes).  With the ``tree.*`` scopes, the excluded prefixes of
``xla_glue_ms_per_tree.dp`` and ``"unscoped"`` (the per-row programs count
there), the metrics add up to that one less the loop instructions.  A scope
opened under a transformation (``vmap(comm.best_split)``) counts as the scope.
An instruction that no scope claims takes the scope of the computation it
applies (``to_apply=``): the chip's compiler runs a reduce-scatter as an
all-reduce and a slice, and the all-reduce it makes keeps the program's path
only on its reduction (:func:`applied_scopes`).  A program that cannot give
its texts or its scopes has nothing to read."""
import re
import time

_TRANSFORMED = re.compile(r"\b(?:vmap|jvp|transpose)\(([\w.]+)\)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s(]+) \(.*\{\s*$")
_APPLIES = re.compile(r"^\s*(?:ROOT )?(%[^\s=]+) = .*\bto_apply=(%[^\s,)}]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def applied_scopes(text, among):
    """{"%instruction": scope} for the instructions of a compiled program's
    ``text`` that apply a computation (``to_apply=``) all of whose named
    paths lie in ONE of the scopes ``among``."""
    inside, applies, here = {}, {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            here = head.group(1)
            continue
        found = _APPLIES.match(line)
        if found is not None:
            applies[found.group(1)] = found.group(2)
        path = _OP_NAME.search(line)
        if here is not None and path is not None:
            inside.setdefault(here, set()).update(
                part for part in path.group(1).split("/") if part in among)
    return {name: next(iter(inside[comp])) for name, comp in applies.items()
            if len(inside.get(comp, ())) == 1}


def scope_of_ops(ctx, among):
    """{"%instruction": scope} over the iteration's programs, or None."""
    cache = ctx.setdefault("_sharded_scope_of_ops", {})
    key = tuple(among)
    if key in cache:
        return cache[key]
    cache[key] = None
    try:
        from lightgbm_tpu.obs import scopes
        t0 = time.perf_counter()
        texts = ctx["job"].gbdt.iteration_program_texts()
    except (ImportError, AttributeError):
        return None
    if not texts:
        return None
    found = {}
    for text in texts:                  # the build comes last and wins
        # a scope opened under vmap is written "vmap(comm.best_split)"
        text = _TRANSFORMED.sub(r"\1", text)
        of_text = scopes.op_scopes(text, among)
        for name, scope in applied_scopes(text, among).items():
            if of_text.get(name, scopes.UNSCOPED) == scopes.UNSCOPED:
                of_text[name] = scope
        found.update(of_text)
    print("scope map of the iteration's %d programs among %s: %d "
          "instructions, %.3f s to get and read"
          % (len(texts), ", ".join(among), len(found),
             time.perf_counter() - t0), flush=True)
    cache[key] = found
    return found


def read(args, ctx):
    trace, trees = ctx["trace"], len(ctx["job"].traced_trees)
    if trace is None or not trees:
        return None
    scope_of = scope_of_ops(ctx, args["among"])
    if scope_of is None:
        return None
    skip = tuple(args.get("exclude_prefixes", ()))
    only = tuple(args.get("only_prefixes", ()))
    mine = {op: ns for op, ns in trace["own"].items()
            if not op.startswith(skip) and (not only or op.startswith(only))
            and scope_of.get(op, "unscoped") == args["scope"]}
    top = sorted(mine.items(), key=lambda kv: -kv[1])[:6]
    print("scope %s: %d ops, %.3f ms a tree; most: %s"
          % (args["scope"], len(mine), sum(mine.values()) / 1e6 / trees,
             ", ".join("%s %.3f" % (op, ns / 1e6 / trees) for op, ns in top)),
          flush=True)
    return sum(mine.values()) / 1e6 / trees
