"""Slot-cache misses per prefill in the window (counters)."""


def read(ctx):
    steps = [s for s in ctx.window.steps if s.kind == "prefill"]
    if not steps:
        return None
    return sum(s.delta["slot_misses"] for s in steps) / len(steps)
