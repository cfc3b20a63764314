"""Device idle time inside the slot runtime's reads of device values
(``runtime.read.*`` spans) per decode step: the part of each span in which
no program ran on the device (the complement of the ``XLA Modules``
line)."""
from chip import tracefile


def read(ctx):
    steps = ctx.trace_steps("decode")
    if not steps or not ctx.trace.device:
        return None
    reads = [e for s in steps for e in tracefile.inside(ctx.trace.host, s)
             if e.name.startswith("runtime.read.")]
    if not reads:
        return None
    idle = (sum(e.dur for e in reads) * 1e-9
            - tracefile.busy_within(ctx.trace.device, reads))
    return 1e3 * idle / len(steps)
