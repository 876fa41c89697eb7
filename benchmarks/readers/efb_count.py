"""A count of ``lightgbm_tpu.obs.efb.counts()``, asked when the metric is
read: ``args["count"]``.  The kind copies those counts right after the ingest
(``readers/counter.py`` reads its copy), which is before the learner exists;
what the learner records there, ``efb.search_lanes`` (the candidate lanes its
split search evaluates for one leaf), is read here, in the job's own process.
None where the program keeps no such count."""


def read(args, ctx):
    from lightgbm_tpu.obs import efb
    value = efb.counts().get(args["count"])
    return None if value is None else float(value)
