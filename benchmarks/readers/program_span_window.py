"""Milliseconds per tree of the window that the host spent in one of the
program's own spans (``obs.spans``): ``args["span"]`` names it, and only the
spans that started and ended inside the measured window count
(``job.t_start`` to ``job.t_end`` on ``perf_counter``, the spans' clock),
divided by ``job.window_trees``.  What the host pays per tree to dispatch, as
against what the device takes to run.  A program without the span API, or
without the span, has nothing to read."""


def read(args, ctx):
    job = ctx["job"]
    try:
        from lightgbm_tpu.obs import spans
        records = spans.records(args["span"])
    except (ImportError, AttributeError):
        return None
    mine = [r["end"] - r["start"] for r in records
            if r["start"] >= job.t_start and r["end"] <= job.t_end]
    if not mine or not job.window_trees:
        return None
    print("span %r: %d inside the window, %.6f s in all, longest %.6f s"
          % (args["span"], len(mine), sum(mine), max(mine)), flush=True)
    return 1e3 * sum(mine) / job.window_trees
