"""Reads of device values by the slot runtime (``runtime.read.*`` spans)
per decode step: two per MoE layer (the router's top-k, ``post``'s counts)
and the token."""
from chip import tracefile


def read(ctx):
    steps = ctx.trace_steps("decode")
    n = sum(e.name.startswith("runtime.read.")
            for s in steps for e in tracefile.inside(ctx.trace.host, s))
    return n / len(steps) if n else None
