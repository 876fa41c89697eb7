"""A kernel's share of its roofline, in %: the least time the chip could take
for the traced trees' work (``roofline.py``) over the kernel's own time in the
trace.  ``args["prefixes"]`` names the kernel's ops.

The work is counted over what the kernel reads of a row: the configuration's
features at ``max_bin + 1`` bins, or, for a table the program bundled (EFB),
the widths the job's counters named by ``args["columns"]`` and
``args["bins"]`` hold.  There the kernel moves one byte per GROUP column and
histograms that many bins of each; counted over 700 one-hot features the
bytes would be 20 times what moves.  Nothing to read while a named counter is
missing."""
import roofline
import trace_reduce


def read(args, ctx):
    trace, job = ctx["trace"], ctx["job"]
    trees = job.traced_trees
    if trace is None or not trees:
        return None
    if "columns" in args:
        columns = job.counters.get(args["columns"])
        bins = job.counters.get(args["bins"])
        if not columns or not bins:
            return None
    else:
        columns = ctx["cfg"]["features"]
        bins = int(ctx["cfg"]["params"]["max_bin"]) + 1
    kernel_s = trace_reduce.own_of(trace["own"], args["prefixes"]) / 1e9
    if kernel_s <= 0:
        return None
    nbytes, ops, window_rows, small_rows = roofline.split_work(
        trees, features=int(columns), bins=int(bins))
    least, bound = roofline.least_seconds(
        nbytes, ops, roofline.peaks(ctx["device_kind"]))
    print("roofline of %r over %d traced trees at %d device columns of %d "
          "bins: %d window rows (%.3f ns of kernel each), %d smaller-child "
          "rows; %.4g bytes, %.4g ops; least time %.6f s, set by %s; kernel "
          "%.6f s"
          % (args["prefixes"], len(trees), columns, bins, window_rows,
             1e9 * kernel_s / window_rows, small_rows, nbytes, ops, least,
             bound, kernel_s), flush=True)
    return 100.0 * least / kernel_s
