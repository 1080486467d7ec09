"""Device time of each shard's featurization scan, MSPCA and WPD (scope
``featurize``) per MapReduce fit, on the first chip: the union of the
operations whose scope path holds it."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.scope("featurize") * 1e3 / ctx["counters"]["fits"]
