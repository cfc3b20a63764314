"""Host time in the slot cache's bookkeeping per decode step: the union of
the residency sync, the planned uploads, ``ensure`` and ``commit``
(``runtime.sync``, ``runtime.stage``, ``slots.ensure``, ``slots.commit``
spans; they nest, so their union and not their sum)."""
from chip import tracefile

NAMES = ("runtime.sync", "runtime.stage", "slots.ensure", "slots.commit")


def read(ctx):
    steps = ctx.trace_steps("decode")
    spans = [e for s in steps for e in tracefile.inside(ctx.trace.host, s)
             if e.name in NAMES]
    if not spans:
        return None
    held = tracefile.total(tracefile.union((e.t0, e.t1) for e in spans))
    return 1e3 * held * 1e-9 / len(steps)
