"""Device time of the wavelet-packet features (scope ``wpd``) per
execution of the engine step, on the chip: the union of the operations
whose scope path holds it."""

from chipbench import program_trace


def read(ctx):
    return program_trace.scope_ms_per_execution(ctx, "wpd",
                                                "_engine_step_megabatch")
