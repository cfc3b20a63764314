"""Host self time of the offload engine's per-step bookkeeping (simulator
walk, predictor, tracer: the ``bench.policy`` spans) per decode step."""
from chip import tracefile


def read(ctx):
    steps = ctx.trace_steps("decode")
    if not steps:
        return None
    return 1e3 * tracefile.self_seconds(ctx.trace, "bench.policy",
                                        steps) / len(steps)
