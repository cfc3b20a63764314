"""One cell's served session: the server built by ``repro.launch.serve.build``
with the slot runtime, warmed up, then driven closed-loop for the window.

The benchmark reaches into the program only here, and only from the
outside: it builds the server through ``serve.build`` (with this cell's
configuration and its weights keyed by the run's seed), submits requests,
calls ``JaxModelServer.step`` and reads the slot cache's counters. With
spans on, it wraps program callables in ``jax.profiler.TraceAnnotation``
spans; it never edits the program.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

COUNTERS = ("slot_hits", "slot_misses", "demand_uploads", "prefetch_uploads",
            "upload_bytes")


def clock() -> float:
    return time.perf_counter()


class Phases:
    """Named set-up phases on the host clock, printed as they end."""

    def __init__(self):
        self.durations: dict = {}

    def add(self, name: str, seconds: float) -> None:
        self.durations[name] = self.durations.get(name, 0.0) + seconds
        print(f"setup phase {name}: {seconds:.3f} s", flush=True)

    @contextlib.contextmanager
    def timed(self, name: str):
        t = clock()
        try:
            yield
        finally:
            self.add(name, clock() - t)


def arch_from(config: dict):
    """The program's ArchConfig for a configuration file's ``arch`` block."""
    from repro.config import ArchConfig, AttnConfig, MoEConfig
    a = dict(config["arch"])
    a["attn"] = AttnConfig(**a["attn"])
    a["moe"] = MoEConfig(**a["moe"])
    return ArchConfig(**a)


@contextlib.contextmanager
def _swapped(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def weight_key(seed: int):
    """The key the run's weights are drawn from (the reference draws its
    own copy from the same number)."""
    return int(seed) & 0xFFFFFFFF


def build(config: dict, max_prompt: int, max_new: int, seed: int,
          phases: Phases):
    """-> ``serve.Served``: the slot-runtime server for this configuration,
    batch one, weights drawn from ``seed`` instead of serve's fixed key."""
    import jax
    from repro.launch import serve
    from repro.serving import slot_runtime

    arch = arch_from(config)
    argv = ["--arch", arch.name, "--requests", "0",
            "--prompt-len", str(max_prompt), "--max-new", str(max_new),
            "--slots", "1",
            "--resident-fraction", str(config["serve"]["resident_fraction"])]
    args = serve.parse_args(argv)
    keys = []
    make_key = jax.random.PRNGKey

    def seeded_key(_):
        keys.append(1)
        return make_key(weight_key(seed))

    marks = {}
    build_eamc, store_cls = serve._build_eamc, slot_runtime.HostExpertStore

    def timed_eamc(*a, **kw):
        marks["eamc0"] = clock()
        out = build_eamc(*a, **kw)
        marks["eamc1"] = clock()
        return out

    def timed_store(*a, **kw):
        t = clock()
        out = store_cls(*a, **kw)
        marks["store"] = clock() - t
        return out

    t0 = clock()
    with _swapped(serve, "get_config", lambda name: arch), \
            _swapped(jax.random, "PRNGKey", seeded_key), \
            _swapped(serve, "_build_eamc", timed_eamc), \
            _swapped(slot_runtime, "HostExpertStore", timed_store):
        served = serve.build(args)
    t1 = clock()
    if len(keys) != 1:
        raise RuntimeError(f"serve.build drew {len(keys)} PRNG keys; the "
                           "benchmark seeds exactly one (the weights)")
    phases.add("init", marks["eamc0"] - t0)
    phases.add("eamc_build", marks["eamc1"] - marks["eamc0"])
    phases.add("expert_copy_to_host", marks["store"])
    phases.add("server", t1 - marks["eamc1"] - marks["store"])
    return served


def counters(srv) -> dict:
    s = srv.slot_runtime.slot_cache.stats()
    return {k: s[k] for k in COUNTERS}


@dataclass
class Step:
    kind: str                  # prefill | decode
    t0: float
    t1: float
    rid: int
    pos: int                   # position of the token fed in (decode)
    delta: dict                # counter deltas over the step


@dataclass
class Record:
    """One request as the client saw it."""
    rid: int
    task: int
    prompt: np.ndarray
    max_new: int
    t_submit: float
    token_times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    finished: bool = False


@dataclass
class Window:
    t0: float
    t1: float
    steps: list
    requests: list
    compiles: int              # jit traces and backend compiles inside

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Session:
    """Closed loop, one user, batch one: the next request is submitted when
    the previous one retires, with no think time."""

    def __init__(self, served):
        from repro.serving.request import Request
        self._Request = Request
        self.served = served
        self.srv = served.srv
        self.rid = 0
        self.span = None          # TraceAnnotation class when spans are on

    def _submit(self, req) -> tuple:
        srv = self.srv
        r = self._Request(rid=self.rid, arrival=float(srv.offload.sim.clock),
                          prompt=np.asarray(req.prompt, np.int32),
                          max_new_tokens=int(req.max_new), task_id=req.task)
        self.rid += 1
        rec = Record(rid=r.rid, task=req.task, prompt=r.prompt,
                     max_new=r.max_new_tokens, t_submit=clock())
        srv.submit(r)
        return r, rec

    def _step(self, r, rec, steps: list) -> None:
        kind = "prefill" if r.n_generated == 0 else "decode"
        pos = r.prompt_len + r.n_generated - 1
        c0 = counters(self.srv)
        t0 = clock()
        if self.span is not None:
            with self.span(f"bench.step.{kind}"):
                self.srv.step()
        else:
            self.srv.step()
        t1 = clock()
        c1 = counters(self.srv)
        rec.token_times.append(t1)
        steps.append(Step(kind, t0, t1, r.rid, pos,
                          {k: c1[k] - c0[k] for k in COUNTERS}))
        if r.state == "done":
            rec.tokens = list(self.srv.generated.pop(r.rid))
            rec.finished = True

    def serve_all(self, reqs) -> list:
        """Serve requests one after another to completion (set-up)."""
        out = []
        for req in reqs:
            r, rec = self._submit(req)
            while not rec.finished:
                self._step(r, rec, [])
            out.append(rec)
        return out

    def window(self, source, seconds: float, compiles: Compiles,
               stop=None) -> Window:
        """Serve ``source`` until ``seconds`` of serving have passed. No
        step starts after the deadline; the window ends with the last step.
        ``stop``, ``(after_s, fn)``, calls ``fn`` between steps once
        ``after_s`` of the window have passed (or at its end), and not
        again; the deadline moves out by the time ``fn`` takes, and
        ``Window.seconds`` leaves it out."""
        from repro.serving.guard import recompile_guard
        traces0 = dict(self.srv.compile_counts)
        n_compiles = compiles.count
        steps, records = [], []
        t0 = clock()
        deadline = t0 + seconds
        paused = 0.0
        with recompile_guard(self.srv, max_traces_per_key=1):
            while clock() < deadline:
                r, rec = self._submit(next(source))
                records.append(rec)
                while not rec.finished and clock() < deadline:
                    self._step(r, rec, steps)
                    if stop is not None and clock() >= t0 + stop[0]:
                        stop, fn = None, stop[1]
                        t = clock()
                        fn()
                        paused = clock() - t
                        deadline += paused
        t1 = steps[-1].t1 if steps else clock()
        if stop is not None:
            stop[1]()
        new_traces = sum(v - traces0.get(k, 0)
                         for k, v in self.srv.compile_counts.items())
        return Window(t0, t1 - paused, steps, records,
                      new_traces + compiles.count - n_compiles)


# -- compile accounting ------------------------------------------------------

class Compiles:
    """Backend compiles in this process from the moment it is created,
    counted through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


# -- spans -------------------------------------------------------------------

def _kind(key) -> str:
    return key if isinstance(key, str) else key[0]


def wrap_runtime_fns(rt, wrap) -> None:
    """Route every jitted piece the slot runtime builds through
    ``wrap(kind, fn) -> fn``; ``kind`` is the runtime's own key name
    (``slot_decode_pre``, ``slot_decode_post``, ``slot_tail``, ...)."""
    build_fn = rt._fn
    wrapped = {}

    def fn(key, builder):
        f = build_fn(key, builder)
        w = wrapped.get(key)
        if w is None or w[0] is not f:
            w = wrapped[key] = (f, wrap(_kind(key), f))
        return w[1]
    rt._fn = fn


def instrument(session) -> None:
    """Put ``bench.*`` spans around the program calls the per-layer metrics
    read: the offload engine's per-step bookkeeping (``bench.policy``),
    every jitted runtime piece (``bench.jit.<kind>``), the host's wait for
    the router's top-k (``bench.readback``), and the slot cache's
    ``ensure`` / ``commit`` / residency sync."""
    from jax.profiler import TraceAnnotation
    srv = session.srv
    rt = srv.slot_runtime
    session.span = TraceAnnotation

    def spanned(name, f):
        def call(*a, **kw):
            with TraceAnnotation(name):
                return f(*a, **kw)
        return call

    srv._execute_iteration = spanned("bench.policy", srv._execute_iteration)
    srv.tracer.record = spanned("bench.policy", srv.tracer.record)
    rt.sync_residency = spanned("bench.sync", rt.sync_residency)
    rt.slot_cache.ensure = spanned("bench.ensure", rt.slot_cache.ensure)
    rt.slot_cache.commit = spanned("bench.commit", rt.slot_cache.commit)

    def wrap(kind, f):
        inner = spanned(f"bench.jit.{kind}", f)
        if not kind.endswith("_pre"):
            return inner

        def pre(*a):
            out = inner(*a)
            with TraceAnnotation("bench.readback"):
                out[-1].block_until_ready()     # the routed expert ids
            return out
        return pre
    wrap_runtime_fns(rt, wrap)
