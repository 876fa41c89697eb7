"""``readers/roofline.py``'s reading on a cell whose rows are sharded over
several chips.  ``trace_reduce.reduce`` averages a kernel's own time over the
chips traced, and the finished trees count the WHOLE table's window rows (the
smaller child is chosen globally, so the chips' smaller-child rows sum to the
table's): the work is divided by ``trace["chips"]`` before it is held against
one chip's peaks and one chip's kernel time."""
import roofline
import trace_reduce


def read(args, ctx):
    trace, trees = ctx["trace"], ctx["job"].traced_trees
    if trace is None or not trees:
        return None
    kernel_s = trace_reduce.own_of(trace["own"], args["prefixes"]) / 1e9
    if kernel_s <= 0:
        return None
    chips = int(trace["chips"])
    params = ctx["cfg"]["params"]
    nbytes, ops, window_rows, small_rows = roofline.split_work(
        trees, features=int(ctx["cfg"]["features"]),
        bins=int(params["max_bin"]) + 1)
    least, bound = roofline.least_seconds(
        nbytes / chips, ops / chips, roofline.peaks(ctx["device_kind"]))
    print("roofline of %r over %d traced trees on %d chips: %d window rows of "
          "the table (%.3f ns of one chip's kernel for each of its share), %d "
          "smaller-child rows; a chip's share %.4g bytes, %.4g ops; least "
          "time %.6f s, set by %s; kernel %.6f s a chip"
          % (args["prefixes"], len(trees), chips, window_rows,
             1e9 * kernel_s * chips / window_rows, small_rows,
             nbytes / chips, ops / chips, least, bound, kernel_s), flush=True)
    return 100.0 * least / kernel_s
