"""The MoE decode ``post`` programs' share of the HBM roofline where
shared experts run beside the routed ones: the least time of the
``top_k`` routed experts' and the shared experts' weights
(``work_mla.post_bytes``, 138.4 MB and 169 us per layer in
deepseek-v2-lite-l9) at the chip's peak, over the device time of the
``jit_slot_decode_post`` programs (``XLA Modules`` line) inside the
traced ``bench.step.decode`` spans."""
import re

from chip import tracefile, work_mla

PROGRAM = re.compile(r"jit_slot_decode_post(?:[(.]|$)")


def read(ctx):
    steps = ctx.trace_steps("decode")
    if not steps or ctx.peak is None or not ctx.trace.device:
        return None
    posts = [e for e in ctx.trace.device if PROGRAM.match(e.name)]
    seconds = tracefile.busy_within(posts, steps)
    if seconds <= 0:
        return None
    need = (work_mla.n_moe_layers(ctx.config) * work_mla.post_bytes(ctx.config)
            * len(steps))
    return 100.0 * need / ctx.peak["hbm_bytes_per_s"] / seconds
