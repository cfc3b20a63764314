"""Expert uploads per decode step: the slot cache's ``slots.upload`` spans
(one per expert staged for the host->device copy, demand or prefetch)
inside the traced ``bench.step.decode`` spans, over those steps. A
program without that span (none anywhere in the trace) reads nothing."""
from chip import tracefile

NAME = "slots.upload"


def read(ctx):
    steps = ctx.trace_steps("decode")
    if not steps or not any(e.name == NAME for e in ctx.trace.host):
        return None
    n = sum(e.name == NAME
            for s in steps for e in tracefile.inside(ctx.trace.host, s))
    return n / len(steps)
