"""The share, in %, of the traced trees' splits that are categorical (a left
set of categories: ``Tree.num_cat`` of a tree's ``num_leaves - 1`` nodes).  A
cell whose trees stop splitting on categories stops measuring the categorical
search, whatever its other numbers say."""


def read(args, ctx):
    trees = ctx["job"].traced_trees
    splits = sum(int(t.num_leaves) - 1 for t in trees)
    if not splits:
        return None
    try:
        return 100.0 * sum(int(t.num_cat) for t in trees) / splits
    except AttributeError:
        return None
