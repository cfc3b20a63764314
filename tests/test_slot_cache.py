"""Expert slot cache: real weight streaming in model mode (ISSUE 5).

Acceptance pins: (1) the slot path at resident_fraction=1.0 is bit-identical
to the all-resident fused step; (2) a small cache (rf=0.5) produces
identical tokens while reporting nonzero slot hits *and* demand uploads —
i.e. weights really move and the movement never changes the math.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import EngineConfig, SchedulerConfig
from repro.serving.engine import JaxModelServer

jax = pytest.importorskip("jax")

N_MOE, N_EXPERTS = 2, 4          # reduced qwen3-moe: 2 MoE layers x 4 experts
TOTAL = N_MOE * N_EXPERTS


@pytest.fixture(scope="module")
def model_and_params():
    from repro.models import Model
    arch = get_config("qwen3-moe-235b-a22b").reduced()
    model = Model(arch)
    params = model.init(jax.random.PRNGKey(0))
    return arch, model, params


def _server(model_and_params, n_slots=4, **kw):
    arch, model, params = model_and_params
    cfg = EngineConfig(arch=arch, gpu_cache_experts=4, dram_cache_experts=8,
                       scheduler=SchedulerConfig(max_batch=4), **kw)
    return JaxModelServer(cfg, model, params, n_slots=n_slots, cache_len=64)


def _generate(srv, arch, n=3, new=6, seed=5):
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, arch.vocab, (n, 8)).astype(np.int32)
    return srv.generate(prompts, max_new_tokens=new)


@pytest.fixture(scope="module")
def fused_reference(model_and_params):
    arch, _, _ = model_and_params
    srv = _server(model_and_params)
    out, stats = _generate(srv, arch)
    return out, stats["eams"]


# ---------------------------------------------------------------------------
# Acceptance: bit-identity
# ---------------------------------------------------------------------------

def test_all_resident_slot_path_bit_identical(model_and_params,
                                              fused_reference):
    """resident_fraction=1.0 *through the slot path* (every expert in a
    slot) matches the fused all-resident step bit for bit, with zero
    demand uploads — the layered walk and the gathered slot weights change
    nothing about the numbers."""
    arch, _, _ = model_and_params
    out_ref, eams_ref = fused_reference
    srv = _server(model_and_params, n_weight_slots=TOTAL)
    assert srv.slot_runtime is not None
    out, stats = _generate(srv, arch)
    assert np.array_equal(out, out_ref)
    for a, b in zip(stats["eams"], eams_ref):
        assert np.array_equal(a, b)
    assert stats["demand_uploads"] == 0
    assert stats["slot_hits"] > 0
    assert stats["slot_misses"] == 0


def test_small_cache_bit_identical_with_demand_uploads(model_and_params,
                                                       fused_reference):
    """rf=0.5 (4 of 8 experts resident): identical tokens and EAMs, and the
    engine really streamed — nonzero hits, nonzero demand uploads, and the
    byte counter consistent with the upload count."""
    arch, _, _ = model_and_params
    out_ref, eams_ref = fused_reference
    srv = _server(model_and_params, resident_fraction=0.5)
    out, stats = _generate(srv, arch)
    assert np.array_equal(out, out_ref)
    for a, b in zip(stats["eams"], eams_ref):
        assert np.array_equal(a, b)
    assert stats["weight_slots"] == TOTAL // 2
    assert stats["demand_uploads"] > 0
    assert stats["slot_hits"] > 0
    n_uploads = stats["demand_uploads"] + stats["prefetch_uploads"]
    assert stats["upload_bytes"] == \
        n_uploads * srv.slot_runtime.store.expert_bytes
    assert stats["demand_stall_s"] > 0.0


# ---------------------------------------------------------------------------
# Cache mechanics
# ---------------------------------------------------------------------------

def test_slot_table_and_residency_consistent(model_and_params):
    """slot_of / key_of stay inverse maps under churn, the resident set
    never exceeds capacity, and every resident key's slot really holds its
    weights (device buffer row bit-equal to the host store)."""
    arch, _, _ = model_and_params
    srv = _server(model_and_params, resident_fraction=0.5)
    _generate(srv, arch, n=4, new=5, seed=9)
    sc = srv.slot_runtime.slot_cache
    sc.fence()             # land any still-staged uploads before comparing
    resident = sc.resident
    assert len(resident) <= sc.n_slots
    for key in resident:
        slot = int(sc.slot_of[key[0], key[1]])
        assert sc.key_of[slot] == key
    for slot, key in enumerate(sc.key_of):
        if key is None:
            assert slot in sc._free
        else:
            assert int(sc.slot_of[key[0], key[1]]) == slot
            host = sc.store.expert(*key)
            for name, arr in host.items():
                assert np.array_equal(np.asarray(sc.bufs[name][slot]), arr)


def test_stripped_params_hold_no_expert_weights(model_and_params):
    """Slot mode strips the routed-expert leaves out of the device param
    tree (the host store owns them); router + shared weights stay."""
    _, model, params = model_and_params
    from repro.core.slot_cache import EXPERT_WEIGHT_NAMES, HostExpertStore
    store = HostExpertStore(model, params)
    stripped = store.stripped_params
    for pos, blk in enumerate(stripped.get("blocks", [])):
        if "moe" in blk:
            assert not set(EXPERT_WEIGHT_NAMES) & set(blk["moe"])
            assert "w_router" in blk["moe"]
    # the original tree is untouched, and the store is bit-faithful to it
    g = 0
    orig = params["blocks"][0]["moe"]
    w = store.expert(0, 2)
    assert np.array_equal(w["w_up"], np.asarray(orig["w_up"][g][2]))
    assert set(w) == set(store.names)
    assert store.expert_bytes > 0


def test_residency_follows_engine_verdicts(model_and_params):
    """The device slot set is reconciled against the OffloadEngine's GPU
    cache each iteration: after a drain every resident slot key is one the
    engine's cache holds (modulo intra-iteration demand uploads, which the
    next boundary reconciles — after drain there is none)."""
    arch, _, _ = model_and_params
    srv = _server(model_and_params, resident_fraction=0.5)
    _generate(srv, arch, n=3, new=4, seed=11)
    # one more boundary sync (what the next iteration would do); in the
    # double-buffered schedule later layers' uploads are planned, not yet
    # staged — flush to materialize the full verdict set
    srv.slot_runtime.sync_residency(set(srv.offload.gpu_cache.resident))
    srv.slot_runtime.flush_pending()
    assert set(srv.slot_runtime.slot_cache.resident) \
        == set(srv.offload.gpu_cache.resident)


def test_weight_slot_floor_is_one_layer(model_and_params):
    """A resident fraction below one layer's worst case clamps to E slots
    (the layered walk needs at most one layer's routed set resident) and
    still serves correctly."""
    arch, _, _ = model_and_params
    srv = _server(model_and_params, resident_fraction=0.01)
    assert srv.cfg.n_weight_slots == N_EXPERTS
    assert srv.cfg.gpu_cache_experts == N_EXPERTS
    out, stats = _generate(srv, arch, n=2, new=4, seed=13)
    assert out.shape == (2, 4)
    assert stats["demand_uploads"] > 0


def test_zero_recompiles_after_warmup_in_slot_mode(model_and_params):
    """A second generate wave through the slot runtime adds no jit traces:
    per distinct layer signature there is one compile, like the fused
    scan's O(period) warmup."""
    arch, _, _ = model_and_params
    srv = _server(model_and_params, resident_fraction=0.5)
    _generate(srv, arch, n=3, new=4, seed=3)
    warm = dict(srv.compile_counts)
    assert all(v == 1 for v in warm.values()), warm
    _generate(srv, arch, n=3, new=4, seed=4)
    assert srv.compile_counts == warm


# ---------------------------------------------------------------------------
# Routed rows: a pool of one decodes from the T·k routed slot rows alone
# ---------------------------------------------------------------------------

def test_pool_of_one_routed_rows_bit_identical(model_and_params):
    """A pool of one (T·k = 2 < E = 4): decode ``post`` gathers only the
    routed experts' slot rows, and at rf=0.5 gives tokens and EAMs
    bit-equal to the fused step, which takes the routed-row path too."""
    from repro.models.moe import routed_rows
    arch, model, _ = model_and_params
    assert routed_rows(arch, 1, 1, model.decode_capacity_factor)
    out_ref, stats_ref = _generate(_server(model_and_params, n_slots=1),
                                   arch)
    srv = _server(model_and_params, n_slots=1, resident_fraction=0.5)
    out, stats = _generate(srv, arch)
    assert np.array_equal(out, out_ref)
    for a, b in zip(stats["eams"], stats_ref["eams"]):
        assert np.array_equal(a, b)
    assert stats["demand_uploads"] > 0
    assert srv.slot_runtime._post_rows() == arch.moe.top_k


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("dtype", ["fp16", "int8"])
def test_routed_rows_dequantize_only_the_gathered_rows(model_and_params,
                                                       dtype):
    """Narrow wire at a pool of one: the routed-row path widens only the
    T·k gathered slot rows (every int8→fp32 or fp16→fp32 convert of an
    expert weight has T·k rows, none has the table's E or the buffer's
    n_slots), with the scale rows gathered by the same slots, and matches
    gather-everything-then-dispatch within the wire's bound."""
    import jax.numpy as jnp
    from repro.core.slot_cache import HostExpertStore, _moe_param_location
    from repro.models.moe import (gather_slot_weights, grouped_expert_ffn,
                                  moe_ffn, route)
    arch, model, params = model_and_params
    E, k = N_EXPERTS, arch.moe.top_k
    store = HostExpertStore(model, params, transfer_dtype=dtype)
    _, pos, g = _moe_param_location(model, model.moe_layers[0])
    p_moe = jax.tree.map(lambda a: a[g], params["blocks"][pos])["moe"]
    # six slots, expert e in slot perm[e]; the other two hold garbage
    perm = np.array([4, 0, 5, 2])
    imgs = [store.wire_expert(0, e) for e in range(E)]
    slot_weights = {}
    for name in imgs[0]:
        buf = np.zeros((6,) + imgs[0][name].shape, imgs[0][name].dtype)
        buf[perm] = np.stack([im[name] for im in imgs])
        slot_weights[name] = jnp.asarray(buf)
    slot_ids = jnp.asarray(perm, jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 1, arch.d_model),
                          jnp.float32)
    gates, idx, _ = route(p_moe, arch.moe, x.reshape(1, -1))
    cf = model.decode_capacity_factor

    def routed(sw, x):
        return moe_ffn(p_moe, arch, x, capacity_factor=cf,
                       routing=(gates, idx), slot_weights=sw,
                       slot_ids=slot_ids)[0]
    jaxpr = jax.make_jaxpr(routed)(slot_weights, x)
    wire = jnp.int8 if dtype == "int8" else jnp.float16
    widened = [eqn.outvars[0].aval.shape for eqn in jaxpr.eqns
               if eqn.primitive.name == "convert_element_type"
               and eqn.invars[0].aval.dtype == wire]
    assert widened and all(s[0] == k for s in widened), widened
    y = routed(slot_weights, x)
    y_full, _ = moe_ffn(gather_slot_weights(p_moe, slot_weights, slot_ids),
                        arch, x, capacity_factor=cf, routing=(gates, idx),
                        expert_fn=lambda xg, q: grouped_expert_ffn(
                            xg, q, arch.act))
    y_ref, _ = moe_ffn(p_moe, arch, x, capacity_factor=cf,
                       routing=(gates, idx))
    tol = {"fp16": 1e-3, "int8": 1.5e-2}[dtype]   # test_quant_stream's
    assert _rel(y, y_full) <= tol
    assert _rel(y, y_ref) <= tol


@pytest.mark.parametrize("n_slots", [1, 4])
def test_decode_post_gathers_no_full_expert_table(model_and_params, n_slots):
    """The lowered ``slot_decode_post`` of a pool of one has no gather whose
    output leads with E (it takes the T·k routed slot rows); a pool of
    four (T·k = 8 >= E) keeps the dispatch and its E-row gather."""
    import re
    arch, _, _ = model_and_params
    srv = _server(model_and_params, n_slots=n_slots, resident_fraction=0.5)
    rt = srv.slot_runtime
    posts = {}
    build_fn = rt._fn

    def fn(key, builder):
        f = build_fn(key, builder)
        if not isinstance(key, tuple) or key[0] != "slot_decode_post":
            return f

        def call(*a):
            posts.setdefault(key, (f, jax.tree.map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), a)))
            return f(*a)
        return call
    rt._fn = fn
    _generate(srv, arch, n=1, new=3)
    assert posts
    for f, args in posts.values():
        text = f.lower(*args).as_text()
        leads = [int(m) for m in re.findall(
            r'"?stablehlo\.gather"?.*-> tensor<(\d+)x', text)]
        assert leads
        assert (N_EXPERTS in leads) is (n_slots > 1), leads
