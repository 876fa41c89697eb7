"""Rows a traced tree sends through the split kernel's copy-back: the sum
over its splits of the RIGHT child's rows (``roofline.tree_rows``, which says
why a right row costs more).  Together with ``split_ns_per_window_row`` and
the window rows it says what a right row costs; like them it is the program's
hessian-based estimate (``readers/bucket_launches.py``)."""
import roofline


def read(args, ctx):
    trees = ctx["job"].traced_trees
    if not trees:
        return None
    return roofline.tree_rows(trees)[2] / len(trees)
