"""The whole decode step's share of the chip's bf16 peak, on the device's
clock: the operations the traced decode steps need (routed experts only,
attention over their real context), over the time in which a program ran
on the device inside those steps' spans (the ``XLA Modules`` line). The
i-th ``bench.step.decode`` span of the trace is the window's i-th decode
step: the profiler starts before the window and stops between steps."""
from chip import tracefile, work


def read(ctx):
    spans = ctx.trace_steps("decode")
    if not spans or ctx.peak is None or not ctx.trace.device:
        return None
    steps = [s for s in ctx.window.steps if s.kind == "decode"]
    if len(spans) > len(steps):
        return None
    seconds = tracefile.busy_within(ctx.trace.device, spans)
    if seconds <= 0:
        return None
    flops = sum(work.decode_token_flops(ctx.config, s.pos + 1)
                for s in steps[:len(spans)])
    return 100.0 * flops / seconds / ctx.peak["bf16_flops_per_s"]
