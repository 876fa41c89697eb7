"""Seconds the program spent in one of its own spans (``obs.spans``, always
recorded in memory): ``args["span"]`` names it.  With ``"until": "t_start"``
only the spans that ended before ``job.t_start`` count — ``perf_counter``,
the clock ``gbdt_job.clock`` is — so the compiles and fetches of the AUC and
the checks after the window are not counted as set-up.  The first reading of
a run prints every span's count and seconds up to then, those no metric reads
too (``jax.trace``: nested jits each report theirs, so its sum counts an
inner trace twice).  A program without the span API has nothing to read."""


def _records(name=None):
    try:
        from lightgbm_tpu.obs import spans
        return spans.records(name)
    except (ImportError, AttributeError):
        return None


def _print_all(until, label):
    total, count = {}, {}
    for r in _records():
        if until is None or r["end"] <= until:
            total[r["name"]] = total.get(r["name"], 0.0) + r["end"] - r["start"]
            count[r["name"]] = count.get(r["name"], 0) + 1
    print("the program's spans that ended%s: %s"
          % (label, "; ".join("%s x%d %.6f s" % (n, count[n], total[n])
                              for n in sorted(total))), flush=True)


def read(args, ctx):
    records = _records(args["span"])
    if records is None:
        return None
    until = getattr(ctx["job"], args["until"]) if "until" in args else None
    label = " before job.%s" % args["until"] if "until" in args else ""
    printed = ctx.setdefault("_spans_printed", set())
    if label not in printed:
        printed.add(label)
        _print_all(until, label)
    mine = [r["end"] - r["start"] for r in records
            if until is None or r["end"] <= until]
    print("span %r: %d ended%s, %.6f s in all, longest %.6f s"
          % (args["span"], len(mine), label, sum(mine),
             max(mine, default=0.0)), flush=True)
    return sum(mine)
