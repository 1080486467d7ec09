"""Host time of ``SeizureEngine.poll`` per engine step it ran (engine
scheduler: slot filling, batch assembly, transfer, dispatch, read-back,
events)."""


def read(ctx):
    seconds, _ = ctx["spans"].get("bench.poll", (0.0, 0))
    steps = ctx["counters"].get("engine_steps", 0)
    return seconds * 1e3 / steps if steps else None
