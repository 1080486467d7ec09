"""Host time per engine step of the batch's and the mask's
``device_put``s (``seizure.put``; the transfer itself runs on after they
return, and shows in the read-back), from the program's span."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.span("seizure.put") * 1e3 / ctx["counters"]["engine_steps"]
