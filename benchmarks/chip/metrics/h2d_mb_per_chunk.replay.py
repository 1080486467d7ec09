"""Megabytes (1e6 bytes) sent to the device per chunk a step took: the
batch and mask (``seizure.put``'s ``bytes``) and admitted sessions'
state (``seizure.fill``'s ``h2d_bytes``)."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    sent = (prog.arg("seizure.put", "bytes")
            + prog.arg("seizure.fill", "h2d_bytes"))
    return sent / 1e6 / prog.arg("seizure.assemble", "chunks")
