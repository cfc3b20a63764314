"""The MLA MoE layers' decode ``pre`` programs' share of the HBM roofline:
the least time of the bytes each needs (norms, MLA weights, router, and
the latent cache at the step's real context; ``work_mla.pre_bytes``) at
the chip's peak, over the device time of the ``jit_slot_decode_pre``
programs (``XLA Modules`` line) inside the traced ``bench.step.decode``
spans. About 34 us per layer in deepseek-v2-lite-l9."""
import re

from chip import tracefile, work_mla

PROGRAM = re.compile(r"jit_slot_decode_pre(?:[(.]|$)")


def read(ctx):
    spans = ctx.trace_steps("decode")
    if not spans or ctx.peak is None or not ctx.trace.device:
        return None
    steps = [s for s in ctx.window.steps if s.kind == "decode"]
    if len(spans) > len(steps):
        return None
    pres = [e for e in ctx.trace.device if PROGRAM.match(e.name)]
    seconds = tracefile.busy_within(pres, spans)
    if seconds <= 0:
        return None
    n = work_mla.n_moe_layers(ctx.config)
    need = sum(n * work_mla.pre_bytes(ctx.config, s.pos + 1)
               for s in steps[:len(spans)])
    return 100.0 * need / ctx.peak["hbm_bytes_per_s"] / seconds
