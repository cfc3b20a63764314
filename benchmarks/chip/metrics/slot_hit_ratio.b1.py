"""Slot-cache hits over lookups in the window's decode steps (counters)."""


def read(ctx):
    steps = [s for s in ctx.window.steps if s.kind == "decode"]
    hits = sum(s.delta["slot_hits"] for s in steps)
    lookups = hits + sum(s.delta["slot_misses"] for s in steps)
    return 100.0 * hits / lookups if lookups else None
