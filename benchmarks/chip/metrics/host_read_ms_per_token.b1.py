"""Host time in the slot runtime's reads of device values per decode step:
the ``runtime.read.*`` spans (the router's top-k and ``post``'s counts in
every MoE layer, and the token), in which the host waits on the device."""
from chip import tracefile


def read(ctx):
    steps = ctx.trace_steps("decode")
    reads = [e for s in steps for e in tracefile.inside(ctx.trace.host, s)
             if e.name.startswith("runtime.read.")]
    if not reads:
        return None
    return 1e3 * sum(e.dur for e in reads) * 1e-9 / len(steps)
