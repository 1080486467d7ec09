"""Device time of one execution of the engine's jitted step
(``_engine_step_megabatch``: MSPCA, WPD, forest vote, alarm ring), from
the trace's program executions on the chip."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds, calls = trace.program("_engine_step_megabatch")
    if not calls:
        raise RuntimeError("no execution of _engine_step_megabatch in the "
                           "trace: the engine step was renamed or not run")
    return seconds * 1e3 / calls
