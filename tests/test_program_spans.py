"""Spans and program names inside the slot runtime, engine and slot cache.

Under ``jax.profiler`` every MoE layer of a decode or prefill walk shows
its host reads (``runtime.read.route`` / ``.counts``) and the slot cache's
``slots.ensure``, each labelled with its layer; every jitted piece lowers
to a module named after its compile-count key; and tracing changes no
token.
"""
import glob

import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import EngineConfig, SchedulerConfig
from repro.serving.engine import JaxModelServer

jax = pytest.importorskip("jax")

N_MOE = 2                 # reduced qwen3-moe: 2 MoE layers x 4 experts


def _build(name):
    from repro.models import Model
    arch = get_config(name).reduced()
    model = Model(arch)
    return arch, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def model_and_params():
    return _build("qwen3-moe-235b-a22b")


@pytest.fixture(scope="module")
def switch():
    """Reduced switch-base-128: a dense layer, then a MoE layer."""
    return _build("switch-base-128")


def _server(model_and_params, **kw):
    arch, model, params = model_and_params
    cfg = EngineConfig(arch=arch, gpu_cache_experts=4, dram_cache_experts=8,
                       scheduler=SchedulerConfig(max_batch=4), **kw)
    return JaxModelServer(cfg, model, params, n_slots=4, cache_len=64)


def _prompts(arch, n=2, seed=5):
    rng = np.random.default_rng(seed)
    return rng.integers(0, arch.vocab, (n, 8)).astype(np.int32)


def _host_events(trace_dir):
    """Every event on the host planes, as (name, t0, t1, stats dict)."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for p in ProfileData.from_file(path).planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                ours = e.name.startswith(("runtime.", "slots.", "engine."))
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats) if ours else {}))
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def _inside(events, outer):
    return [e for e in events if outer[1] <= e[1] and e[2] <= outer[2]
            and e is not outer]


@pytest.fixture(scope="module")
def traced(model_and_params, tmp_path_factory):
    """Tokens served with the profiler on, and the host events it kept."""
    arch, _, _ = model_and_params
    srv = _server(model_and_params, resident_fraction=0.5)
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        out, _ = srv.generate(_prompts(arch), max_new_tokens=5)
    return out, _host_events(d)


def test_tokens_identical_with_profiler_on_and_off(model_and_params, traced):
    arch, _, _ = model_and_params
    srv = _server(model_and_params, resident_fraction=0.5)
    out, _ = srv.generate(_prompts(arch), max_new_tokens=5)
    assert np.array_equal(out, traced[0])


@pytest.mark.parametrize("walk", ["runtime.decode", "runtime.prefill"])
def test_every_moe_layer_emits_read_and_ensure_spans(traced, walk):
    events = traced[1]
    walks = [e for e in events if e[0] == walk]
    assert walks
    for w in walks:
        kids = _inside(events, w)
        for name in ("runtime.read.route", "runtime.read.counts",
                     "slots.ensure"):
            layers = [e[3]["layer"] for e in kids if e[0] == name]
            assert layers == list(range(N_MOE)), (walk, name, layers)
        assert sum(e[0] == "runtime.read.token" for e in kids) == 1
    if walk == "runtime.prefill":
        assert sorted(w[3]["rid"] for w in walks) == [0, 1]


def test_engine_and_cache_spans(traced):
    events = traced[1]
    names = {e[0] for e in events}
    assert {"engine.step", "engine.policy", "runtime.sync",
            "slots.commit"} <= names
    steps = [e for e in events if e[0] == "engine.step"]
    # two requests prefill together, then decode together
    assert steps[0][3] == {"prefill": 2, "decode": 0}
    assert all(s[3] == {"prefill": 0, "decode": 2} for s in steps[1:])
    for s in steps:
        assert sum(e[0] == "runtime.sync" for e in _inside(events, s)) == 1
    ensures = [e for e in events if e[0] == "slots.ensure"]
    assert sum(e[3]["misses"] for e in ensures) > 0


def _abstract(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") else a, tree)


def _kind(key):
    return key if isinstance(key, str) else key[0]


def test_runtime_programs_are_named_by_their_keys(switch):
    arch, _, _ = switch
    srv = _server(switch, resident_fraction=0.5)
    rt = srv.slot_runtime
    calls = {}
    build_fn = rt._fn

    def fn(key, builder):
        f = build_fn(key, builder)

        def call(*a):
            calls.setdefault(key, (f, _abstract(a)))
            return f(*a)
        return call
    rt._fn = fn
    srv.generate(_prompts(arch), max_new_tokens=3)
    kinds = {_kind(k) for k in calls}
    assert kinds == {"slot_embed", "slot_decode", "slot_decode_pre",
                     "slot_decode_post", "slot_tail", "slot_prefill_embed",
                     "slot_prefill_layer", "slot_prefill_pre",
                     "slot_prefill_post", "slot_prefill_tail", "slot_write"}
    assert kinds >= {_kind(k) for k in srv.compile_counts}
    for key, (f, args) in calls.items():
        text = f.lower(*args).as_text()
        assert text.startswith(f"module @jit_{_kind(key)} "), key
    sc = rt.slot_cache
    name = next(iter(sc.bufs))
    buf = sc.bufs[name]
    splice = sc._splice_fns[name].lower(
        *_abstract((buf, buf[0])), jax.ShapeDtypeStruct((), np.int32))
    assert splice.as_text().startswith("module @jit_slot_splice ")


def test_fused_programs_are_named_by_their_keys(switch):
    arch, _, _ = switch
    srv = _server(switch)
    assert srv.slot_runtime is None
    srv.generate(_prompts(arch), max_new_tokens=2)
    assert {_kind(k) for k in srv.compile_counts} == {"decode_step",
                                                      "prefill"}
    jnp = jax.numpy
    step = srv._get_step_fn().lower(
        *_abstract((srv.params, srv._cache)),
        jnp.zeros(srv.n_slots, jnp.int32), jnp.zeros(srv.n_slots, bool))
    assert step.as_text().startswith("module @jit_decode_step ")
    P = next(k[1] for k in srv.compile_counts if _kind(k) == "prefill")
    pre = srv._get_prefill_fn(P).lower(
        *_abstract((srv.params, srv._cache)), jnp.zeros((1, P), jnp.int32),
        jnp.ones(1, jnp.int32), jnp.asarray(0, jnp.int32))
    assert pre.as_text().startswith("module @jit_prefill ")


@pytest.mark.parametrize("walk", ["runtime.decode", "runtime.prefill"])
def test_post_spans_carry_layer_and_rows(traced, walk):
    """Every MoE ``post`` dispatch sits in a ``runtime.post`` span with its
    layer and the expert rows it reads: a pool of four (T·k = 8 >= E) and
    the masked prefill both dispatch over all E = 4 experts' rows."""
    events = traced[1]
    for w in (e for e in events if e[0] == walk):
        posts = [e for e in _inside(events, w) if e[0] == "runtime.post"]
        assert [e[3]["layer"] for e in posts] == list(range(N_MOE))
        assert all(e[3]["rows"] == 4 for e in posts), posts


def test_every_upload_has_a_span_with_layer_and_bytes(model_and_params,
                                                      tmp_path):
    """Each expert the slot cache stages for the host->device copy, on
    demand or as a prefetch, sits in one ``slots.upload`` span carrying its
    MoE layer and the bytes shipped: the spans add up to the counters."""
    arch, _, _ = model_and_params
    srv = _server(model_and_params, resident_fraction=0.5)
    with jax.profiler.trace(str(tmp_path)):
        srv.generate(_prompts(arch), max_new_tokens=5)
    sc = srv.slot_runtime.slot_cache
    ups = [e for e in _host_events(str(tmp_path)) if e[0] == "slots.upload"]
    assert len(ups) == sc.demand_uploads + sc.prefetch_uploads > 0
    assert sum(e[3]["bytes"] for e in ups) == sc.upload_bytes
    assert {e[3]["layer"] for e in ups} <= set(range(N_MOE))
