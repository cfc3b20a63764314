"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device busy time (union of the program executions on the device),
the benchmark's host spans and their self time, and the breakdown of
device operations and idle gaps.

Two layers: :func:`load` turns an ``.xplane.pb`` into plain event lists
(:class:`Trace`), and everything else is pure arithmetic over those lists,
tested on small hand-made traces. All times are nanoseconds on the
profiler's clock, which it shares between host and device planes.

    python benchmarks/chip/tracefile.py <dir or .xplane.pb>   # look at one
"""
from __future__ import annotations

import bisect
import glob
import os
import sys
from dataclasses import dataclass, field


@dataclass(frozen=True)
class Event:
    name: str
    t0: float
    t1: float
    stats: tuple = ()            # ((key, value), ...)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def stat(self, key, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


@dataclass
class Trace:
    device: list = field(default_factory=list)   # program executions
    host: list = field(default_factory=list)     # the spans' thread
    op_seconds: dict = field(default_factory=dict)   # device op -> seconds


DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _events(line) -> list:
    out = [Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
                 tuple(e.stats))
           for e in line.events]
    out.sort(key=lambda e: (e.t0, -e.t1))
    return out


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """From a TPU trace: the program executions of the first device (its
    ``XLA Modules`` line), the events of the host thread that carries the
    benchmark's spans, and the device ops' time by op inside the traced
    window (the ops line holds one event per op and loop iteration, so it
    is only summed, never kept)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    tr = Trace()
    best = 0
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            n = sum(1 for e in line.events if e.name.startswith(span_prefix))
            if n > best:
                best, tr.host = n, _events(line)
    devices = sorted((p for p in pd.planes
                      if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: p.name)
    if not devices:
        return tr
    lines = {line.name: line for line in devices[0].lines}
    if MODULES_LINE in lines:
        tr.device = _events(lines[MODULES_LINE])
    w = window(tr)
    if w is not None and OPS_LINE in lines:
        lo, hi = w
        for e in lines[OPS_LINE].events:
            t = e.start_ns
            if lo <= t <= hi:
                name = e.name
                tr.op_seconds[name] = (tr.op_seconds.get(name, 0.0)
                                       + e.duration_ns * 1e-9)
    return tr


# -- interval arithmetic -------------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted [t0, t1] intervals."""
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1][1] = t1
        else:
            out.append([t0, t1])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def busy(events, lo: float, hi: float) -> list:
    """Union of the events' intervals inside [lo, hi]."""
    return clip(union((e.t0, e.t1) for e in events), lo, hi)


def busy_within(events, within) -> float:
    """Seconds in which an event of ``events`` ran inside the ``within``
    spans: the union of the events, clipped to each span in turn."""
    u = union((e.t0, e.t1) for e in events)
    return sum(total(clip(u, s.t0, s.t1)) for s in within) * 1e-9


def gaps(busy_intervals, lo: float, hi: float) -> list:
    """The complement of ``busy_intervals`` inside [lo, hi]."""
    out, t = [], lo
    for a, b in busy_intervals:
        if a > t:
            out.append([t, a])
        t = max(t, b)
    if hi > t:
        out.append([t, hi])
    return out


# -- host spans ----------------------------------------------------------------

def spans(trace: Trace, name: str) -> list:
    return [e for e in trace.host if e.name == name]


def inside(events, outer: Event) -> list:
    """The events (sorted by start) that lie within ``outer``."""
    lo = bisect.bisect_left(events, outer.t0, key=lambda e: e.t0)
    out = []
    for e in events[lo:]:
        if e.t0 > outer.t1:
            break
        if e.t1 <= outer.t1:
            out.append(e)
    return out


def self_seconds(trace: Trace, name: str, within: list) -> float:
    """Self time of the ``name`` spans inside the ``within`` spans: each
    span's duration less the part its nested ``bench.*`` spans cover."""
    out = 0.0
    for outer in within:
        mine = [e for e in inside(trace.host, outer) if e.name == name]
        for s in mine:
            kids = [e for e in inside(trace.host, s)
                    if e is not s and e.name.startswith("bench.")]
            covered = total(clip(union((k.t0, k.t1) for k in kids),
                                 s.t0, s.t1))
            out += (s.dur - covered) * 1e-9
    return out


def window(trace: Trace, prefix: str = "bench.step.") -> tuple:
    """[first start, last end] of the step spans: the traced window."""
    steps = [e for e in trace.host if e.name.startswith(prefix)]
    if not steps:
        return None
    return min(e.t0 for e in steps), max(e.t1 for e in steps)


# -- breakdown -------------------------------------------------------------------

def top_device_ops(trace: Trace, n: int = 10) -> list:
    return sorted(([k[:160], v] for k, v in trace.op_seconds.items()),
                  key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """Device idle time inside [lo, hi], by what the host was doing: each
    gap goes to the innermost event on the spans' thread that covers its
    midpoint (events on one thread nest, so that is the last opened)."""
    by = {}
    host, i, stack = trace.host, 0, []
    for a, b in gaps(busy(trace.device, lo, hi), lo, hi):
        mid = 0.5 * (a + b)
        while i < len(host) and host[i].t0 <= mid:
            while stack and stack[-1].t1 < host[i].t0:
                stack.pop()
            stack.append(host[i])
            i += 1
        while stack and stack[-1].t1 < mid:
            stack.pop()
        name = stack[-1].name if stack else "(none)"
        by[name] = by.get(name, 0.0) + (b - a) * 1e-9
    return sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])[:n]


def describe(path: str, n: int = 3) -> None:
    """Print every plane and line of a trace, with a few events each."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    for p in pd.planes:
        print(f"plane {p.name}")
        for line in p.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:n]:
                stats = {k: (str(v)[:160]) for k, v in e.stats}
                print(f"    {e.name[:100]!r} start={e.start_ns} "
                      f"dur={e.duration_ns} {stats}")


if __name__ == "__main__":
    describe(sys.argv[1])
