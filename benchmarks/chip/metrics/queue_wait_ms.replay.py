"""Mean time a chunk waited in its session's queue before a step took it
(the ``queue_wait_s`` and ``chunks`` args of ``seizure.assemble``)."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return (prog.arg("seizure.assemble", "queue_wait_s") * 1e3
            / prog.arg("seizure.assemble", "chunks"))
