"""A kernel's own time a window row, in ns: the device time of the ops whose
names start with ``args["prefixes"]`` over the window rows of the traced
trees (``roofline.tree_rows``: the sum of their splits' ``internal_count``).
Per-tree milliseconds depend on which trees were traced and how large their
windows came out on this seed; time a row does not."""
import roofline
import trace_reduce


def read(args, ctx):
    trace, trees = ctx["trace"], ctx["job"].traced_trees
    if trace is None or not trees:
        return None
    kernel_ns = trace_reduce.own_of(trace["own"], args["prefixes"])
    window_rows = roofline.tree_rows(trees)[0]
    if kernel_ns <= 0 or window_rows <= 0:
        return None
    return kernel_ns / window_rows
