"""Mean host-clock time of the window's prefill steps: submission's share
of time to first token, from the step that admits the request and emits
its first token."""


def read(ctx):
    steps = [s for s in ctx.window.steps if s.kind == "prefill"]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
