"""A kernel's share of its roofline, in %: the least time the chip could take
for the traced trees' work (``roofline.py``) over the kernel's own time in the
trace.  ``args["prefixes"]`` names the kernel's ops."""
import roofline
import trace_reduce


def read(args, ctx):
    trace, trees = ctx["trace"], ctx["job"].traced_trees
    if trace is None or not trees:
        return None
    kernel_s = trace_reduce.own_of(trace["own"], args["prefixes"]) / 1e9
    if kernel_s <= 0:
        return None
    params = ctx["cfg"]["params"]
    nbytes, ops, window_rows, small_rows = roofline.split_work(
        trees, features=int(ctx["cfg"]["features"]),
        bins=int(params["max_bin"]) + 1)
    least, bound = roofline.least_seconds(
        nbytes, ops, roofline.peaks(ctx["device_kind"]))
    print("roofline of %r over %d traced trees: %d window rows (%.3f ns of "
          "kernel each), %d smaller-child rows; %.4g bytes, %.4g ops; least "
          "time %.6f s, set by %s; kernel %.6f s"
          % (args["prefixes"], len(trees), window_rows,
             1e9 * kernel_s / window_rows, small_rows, nbytes, ops, least,
             bound, kernel_s), flush=True)
    return 100.0 * least / kernel_s
