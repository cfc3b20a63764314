"""The MoE ``post`` programs' share of the HBM roofline in decode: the
least time of the routed experts (each token's ``top_k`` expert weight sets
in every MoE layer, read once from HBM at the chip's peak) over the device
time of the ``jit_slot_decode_post`` programs (``XLA Modules`` line) inside
the traced ``bench.step.decode`` spans. The least time is the same work
whatever implements ``post``: 11.5 us per layer in switch-base-128."""
import re

from chip import tracefile, work

PROGRAM = re.compile(r"jit_slot_decode_post(?:[(.]|$)")
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx):
    steps = ctx.trace_steps("decode")
    if not steps or ctx.peak is None or not ctx.trace.device:
        return None
    posts = [e for e in ctx.trace.device if PROGRAM.match(e.name)]
    seconds = tracefile.busy_within(posts, steps)
    a = ctx.config["arch"]
    itemsize = ITEMSIZE.get(a["dtype"])
    if seconds <= 0 or itemsize is None:
        return None
    m = a["moe"]
    weights = (work.n_moe_layers(ctx.config) * m["top_k"]
               * work.ffn_mats(ctx.config) * a["d_model"] * m["d_expert"])
    least = (weights * itemsize * len(steps)
             / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
