"""The whole decode step's share of the chip's bf16 peak in an MLA stack
with leading dense layers and shared experts: the operations the traced
decode steps need (``work_mla.decode_token_flops``: absorbed MLA over the
real context, dense layers, router, top-k routed and shared experts, LM
head) over the time in which a program ran on the device inside those
steps' ``bench.step.decode`` spans (``XLA Modules`` line). The i-th span
of the trace is the window's i-th decode step."""
from chip import tracefile, work_mla


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
    flops = sum(work_mla.decode_token_flops(ctx.config, s.pos + 1)
                for s in steps[:len(spans)])
    return 100.0 * flops / seconds / ctx.peak["bf16_flops_per_s"]
