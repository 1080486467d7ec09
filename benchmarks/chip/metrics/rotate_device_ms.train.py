"""Device time of the rotations and the bin codes (scope ``rotate``:
each tree's PCA blocks and ``searchsorted`` binning) per MapReduce fit,
on the first chip: the union of the operations whose scope path holds
it."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.scope("rotate") * 1e3 / ctx["counters"]["fits"]
