"""Host time of ``StreamSession.push`` per chunk pushed (sessions layer)."""


def read(ctx):
    seconds, _ = ctx["spans"].get("bench.push", (0.0, 0))
    pushed = ctx["counters"].get("chunks_pushed", 0)
    return seconds * 1e3 / pushed if pushed else None
