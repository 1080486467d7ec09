"""Host time per engine step of slot filling (``seizure.fill``:
evictions, each a read-back of the whole state, and admissions), from
the program's span."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.span("seizure.fill") * 1e3 / ctx["counters"]["engine_steps"]
