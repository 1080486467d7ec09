"""Host time per engine step of the read-back of the step's votes,
fractions, alarms and window labels (``seizure.readback``: the host
waits here for the device step and the batch's transfer), from the
program's span."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.span("seizure.readback") * 1e3 / ctx["counters"]["engine_steps"]
