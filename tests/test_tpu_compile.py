"""Compile-for-chip tests: the Pallas kernels and one served per-layer jit,
compiled by the TPU compiler for a described (not attached) v5e at
switch-base-128 widths in bf16 (E=128 experts, C=8, d=768, f=3072, 12 heads
of 64). A compile that passes here is not a chip run; it catches what the
chip's compiler refuses (tiling, vector types, VMEM) before chip time is
spent. The topology is described inside a fixture, never at import time:
only one process may load the TPU library, and the test workers each
import this file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.slot_cache import EXPERT_WEIGHT_NAMES
from repro.kernels.flash_decode import flash_decode
from repro.kernels.moe_ffn import moe_ffn, moe_ffn_quant
from repro.models import Model
from repro.serving.slot_runtime import SlotStreamRuntime

E, C, D, F = 128, 8, 768, 3072       # switch-base-128 expert widths
SLOTS = 192                           # a quarter of its 6 x 128 experts
POOL, CACHE_LEN = 4, 128              # decode pool slots x cache length


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _expert_specs(chip, dtype):
    return (_spec(chip, (E, C, D), jnp.bfloat16),
            _spec(chip, (E, D, F), dtype), _spec(chip, (E, F, D), dtype))


def test_moe_ffn_compiles_for_v5e(one_chip):
    xg, wu, wd = _expert_specs(one_chip, jnp.bfloat16)
    compiled = _compile(lambda x, u, d: moe_ffn(x, None, u, d, act="gelu"),
                        xg, wu, wd)
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_ffn_quant_int8_compiles_for_v5e(one_chip):
    xg, wu, wd = _expert_specs(one_chip, jnp.int8)
    su = _spec(one_chip, (E, F), jnp.float32)
    sd = _spec(one_chip, (E, D), jnp.float32)
    compiled = _compile(
        lambda x, u, d, su, sd: moe_ffn_quant(x, None, u, d, None, su, sd,
                                              act="gelu"),
        xg, wu, wd, su, sd)
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_ffn_quant_fp16_compiles_for_v5e(one_chip):
    xg, wu, wd = _expert_specs(one_chip, jnp.float16)
    compiled = _compile(
        lambda x, u, d: moe_ffn_quant(x, None, u, d, act="gelu"), xg, wu, wd)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_compiles_for_v5e(one_chip):
    q = _spec(one_chip, (POOL, 12, 64), jnp.bfloat16)
    kv = _spec(one_chip, (POOL, CACHE_LEN, 12, 64), jnp.bfloat16)
    lens = _spec(one_chip, (POOL,), jnp.int32)
    compiled = _compile(flash_decode, q, kv, kv, lens)
    assert "tpu_custom_call" in compiled.as_text()


def test_served_decode_post_compiles_for_v5e(one_chip):
    """The slot runtime's own decode ``post`` jit for one MoE layer, fed
    the shapes the served switch-base-128 path gives it: stripped layer
    params, SLOTS bf16 slot buffers, the expert->slot row, the layer's
    pool cache and the router's top-1 choice."""
    cfg = get_config("switch-base-128")
    model = Model(cfg)
    pos = model.moe_layers[0] - model.n_prefix
    desc = model.descs[model.moe_layers[0]]
    shapes = model.init_shapes()["blocks"][pos]
    layer = jax.tree.map(
        lambda a: _spec(one_chip, a.shape[1:], a.dtype), shapes)
    layer["moe"] = {k: v for k, v in layer["moe"].items()
                    if k not in EXPERT_WEIGHT_NAMES}
    bufs = {"w_up": _spec(one_chip, (SLOTS, D, F), jnp.bfloat16),
            "w_down": _spec(one_chip, (SLOTS, F, D), jnp.bfloat16)}
    bc = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: model._block_cache(desc, POOL, CACHE_LEN, 0)))
    x = _spec(one_chip, (POOL, 1, D), jnp.bfloat16)
    # the real builder, on a runtime shell that holds no weights
    rt = SlotStreamRuntime.__new__(SlotStreamRuntime)
    rt.model, rt.cfg, rt._jax, rt._jnp = model, cfg, jax, jnp
    rt._fns, rt.compile_counts = {}, {}
    post = rt._decode_post(desc)
    compiled = post.lower(
        layer, bufs, _spec(one_chip, (E,), jnp.int32), bc, x, x,
        _spec(one_chip, (POOL, 1), jnp.float32),
        _spec(one_chip, (POOL, 1), jnp.int32),
        _spec(one_chip, (POOL,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    # a pool of POOL top-1 tokens takes the routed-row path: its
    # temporaries hold at most POOL experts' rows (9.4 MB each), never a
    # copy of the slot buffers (a row gather compiles to slices of the
    # whole buffer, 0.8 GB here) or one layer's E gathered experts
    assert mem.temp_size_in_bytes < POOL * 2 * D * F * 2


def test_served_deepseek_decode_post_compiles_for_v5e(one_chip):
    """The same decode ``post`` for a DeepSeek-V2-Lite MoE layer at its
    published widths (64 SwiGLU experts of 1408, top-6, beside 2 shared
    experts applied as one SwiGLU of 2816) over 128 bf16 slot buffers: the
    routed-row path with three-matrix experts and the shared experts."""
    cfg = get_config("deepseek-v2-lite")
    model = Model(cfg)
    m, d, slots = cfg.moe, cfg.d_model, 128
    desc = model.descs[model.moe_layers[0]]
    shapes = model.init_shapes()["blocks"][0]
    layer = jax.tree.map(
        lambda a: _spec(one_chip, a.shape[1:], a.dtype), shapes)
    layer["moe"] = {k: v for k, v in layer["moe"].items()
                    if k not in EXPERT_WEIGHT_NAMES}
    assert set(layer["moe"]) == {"w_router", "shared"}
    bufs = {"w_gate": _spec(one_chip, (slots, d, m.d_expert), jnp.bfloat16),
            "w_up": _spec(one_chip, (slots, d, m.d_expert), jnp.bfloat16),
            "w_down": _spec(one_chip, (slots, m.d_expert, d), jnp.bfloat16)}
    bc = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: model._block_cache(desc, POOL, CACHE_LEN, 0)))
    x = _spec(one_chip, (POOL, 1, d), jnp.bfloat16)
    rt = SlotStreamRuntime.__new__(SlotStreamRuntime)
    rt.model, rt.cfg, rt._jax, rt._jnp = model, cfg, jax, jnp
    rt._fns, rt.compile_counts = {}, {}
    post = rt._decode_post(desc)
    compiled = post.lower(
        layer, bufs, _spec(one_chip, (m.n_experts,), jnp.int32), bc, x, x,
        _spec(one_chip, (POOL, m.top_k), jnp.float32),
        _spec(one_chip, (POOL, m.top_k), jnp.int32),
        _spec(one_chip, (POOL,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    # the POOL x top-6 routed rows of three matrices (17.3 MB each; 415 MB
    # here, 416 MB compiled) and less than one more expert's worth beside
    # them: never one layer's 64 gathered experts (1.1 GB) nor slices of
    # the 128-slot buffers (2.2 GB)
    row = 3 * d * m.d_expert * 2
    assert mem.temp_size_in_bytes < (POOL * m.top_k + 1) * row
