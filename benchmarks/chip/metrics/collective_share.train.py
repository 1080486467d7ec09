"""Share of the first chip's busy time spent in collective operations
(the all-gathers of the global moments and of the sub-forests)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.first.busy_s <= 0:
        return None
    return 100.0 * trace.first.collective_s / trace.first.busy_s
