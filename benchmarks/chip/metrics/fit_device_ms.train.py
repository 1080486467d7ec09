"""Device busy time of the first chip per MapReduce fit."""


def read(ctx):
    trace = ctx["trace"]
    fits = ctx["counters"].get("fits", 0)
    if trace is None or not fits:
        return None
    return trace.first.busy_s * 1e3 / fits
