"""The share of the traced units' span, in %, in which no op ran on the device."""


def read(args, ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_ns"] / trace["window_ns"])
