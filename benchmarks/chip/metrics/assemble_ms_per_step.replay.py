"""Host time per engine step of batch assembly (``seizure.assemble``:
zeroing the batch and taking each slot's chunk off its queue), from the
program's span."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.span("seizure.assemble") * 1e3 / ctx["counters"]["engine_steps"]
