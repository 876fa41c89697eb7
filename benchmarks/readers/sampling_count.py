"""What row subsampling leaves in the split kernel's windows, from the traced
trees themselves: ``args["what"]`` is

- ``bag_rows_share``: the newest finished tree's bag (the program's
  ``sampling.bag_rows``, which the job kind copied) as a share of the
  configuration's rows, in %;
- ``dead_window_rows_share``: the share of the traced trees' window rows
  that are OUT of their tree's bag, in %.  The program moves every row of a
  leaf through the split kernel, bagged or not, with zero gradients for the
  rest; a tree's counts (``internal_count``, what ``roofline.split_work``
  sums into window rows) are of its in-bag rows only, the root's being the
  bag itself.  The bag is drawn independently of the features, so a window
  of ``w`` in-bag rows holds ``w / s`` rows at a bag share of ``s``, and
  ``w / s - w`` of them are dead: work subsampling does not yet save.

None where the program keeps no sampling counts (the kind found no
``lightgbm_tpu.obs.sampling``) or no tree was traced."""
import roofline


def read(args, ctx):
    job = ctx["job"]
    bag_rows = job.counters.get("sampling_bag_rows")
    if bag_rows is None:
        return None
    if args["what"] == "bag_rows_share":
        return 100.0 * bag_rows / job.gbdt.num_data
    if not job.traced_trees:
        return None
    live = dead = 0.0
    for tree in job.traced_trees:
        if int(tree.num_leaves) <= 1:
            continue
        share = int(tree.internal_count[0]) / job.gbdt.num_data
        in_bag = roofline.split_work(
            [tree], features=int(ctx["cfg"]["features"]),
            bins=int(ctx["cfg"]["params"]["max_bin"]) + 1)[2]
        live += in_bag
        dead += in_bag / share - in_bag
    if live <= 0:
        return None
    print("window rows of %d traced trees: %.0f in their tree's bag, %.0f "
          "out of it" % (len(job.traced_trees), live, dead), flush=True)
    return 100.0 * dead / (live + dead)
