"""Own device time, in ms per traced tree, of the ops whose names start with
``args["prefixes"]`` — or, with ``"complement": true``, of every other op (the
device's busy time less theirs)."""
import trace_reduce


def read(args, ctx):
    trace, trees = ctx["trace"], len(ctx["job"].traced_trees)
    if trace is None or not trees:
        return None
    ns = trace_reduce.own_of(trace["own"], args["prefixes"])
    if args.get("complement"):
        ns = trace["busy_ns"] - ns
    return ns / 1e6 / trees
