"""Share of the traced window in which no program ran on the device."""
from chip import tracefile


def read(ctx):
    if ctx.trace is None or ctx.trace_window is None or not ctx.trace.device:
        return None
    lo, hi = ctx.trace_window
    busy = tracefile.total(tracefile.busy(ctx.trace.device, lo, hi))
    return 100.0 * (1.0 - busy / (hi - lo))
