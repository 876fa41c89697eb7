"""Rows a traced tree sends through the split kernel's copy-back: the sum
over its splits of the RIGHT child's rows (``internal_count`` of an internal
child, ``leaf_count`` of a leaf, as ``roofline.split_work`` walks them).
The kernel places left rows in the window and streams right rows to a
scratch, then copies that block back behind the left one, so a right row is
read and written twice.  Together with ``split_ns_per_window_row`` and the
window rows it says what a right row costs; like them it is the program's
hessian-based estimate (``readers/bucket_launches.py``)."""


def read(args, ctx):
    trees = ctx["job"].traced_trees
    if not trees:
        return None
    right_rows = 0
    for t in trees:
        for node in range(int(t.num_leaves) - 1):
            c = int(t.right_child[node])
            right_rows += int(t.internal_count[c]) if c >= 0 \
                else int(t.leaf_count[~c])
    return right_rows / len(trees)
