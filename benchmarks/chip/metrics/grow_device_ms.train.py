"""Device time of growing the trees (scope ``grow``: the per-level
histograms and splits) per MapReduce fit, on the first chip: the union
of the operations whose scope path holds it."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.scope("grow") * 1e3 / ctx["counters"]["fits"]
