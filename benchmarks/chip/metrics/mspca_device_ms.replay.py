"""Device time of MSPCA denoising (scope ``mspca``, its ``eigh``s
included) per execution of the engine step, on the chip: the union of
the operations whose scope path holds it."""

from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms_per_execution(ctx, "mspca",
                                                "_engine_step_megabatch")
