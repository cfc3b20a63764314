"""Host time blocked on the router's top-k (``bench.readback`` spans) per
decode step."""
from chip import tracefile


def read(ctx):
    steps = ctx.trace_steps("decode")
    if not steps:
        return None
    return 1e3 * tracefile.self_seconds(ctx.trace, "bench.readback",
                                        steps) / len(steps)
