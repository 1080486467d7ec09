"""Share of the engine's chunk slots (steps x max_batch x replay_depth)
that held a chunk in the window: the rest is padding the engine step
computes on (engine scheduler: how ``_fill_slots`` packs backlogs)."""


def read(ctx):
    slots = ctx["counters"].get("chunk_slots", 0)
    scored = ctx["counters"].get("chunks_scored", 0)
    return 100.0 * scored / slots if slots else None
