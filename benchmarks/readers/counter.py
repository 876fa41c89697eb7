"""A count the program keeps and the job kind copied: ``args["counter"]``."""


def read(args, ctx):
    return ctx["job"].counters.get(args["counter"])
