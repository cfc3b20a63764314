"""Expert weight rows the MoE ``post`` programs read per decode step: the
``rows`` of the slot runtime's ``runtime.post`` spans (T·k on the
routed-row path, E where ``post`` dispatches over the whole expert table)
inside the traced ``bench.step.decode`` spans. A program without those
spans reads nothing."""
from chip import tracefile


def read(ctx):
    steps = ctx.trace_steps("decode")
    rows = [e.stat("rows") for s in steps
            for e in tracefile.inside(ctx.trace.host, s)
            if e.name == "runtime.post"]
    rows = [r for r in rows if r is not None]
    if not rows:
        return None
    return sum(rows) / len(steps)
