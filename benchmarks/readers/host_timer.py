"""A host-clock reading the job kind took itself: ``args["timer"]`` names it."""


def read(args, ctx):
    return ctx["job"].host_timers.get(args["timer"])
