"""Sharding rules: spec shapes match params, expert-parallel placement,
divisibility fallbacks. Uses a 1-device mesh with named axes (axis size 1
divides everything → exercises the 'shardable' branch) plus direct
param_spec calls with synthetic mesh sizes for the fallback branch."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch.sharding import param_spec, params_shardings
from repro.models import Model


class FakeMesh:
    """Only what param_spec consults: axis_names + shape."""
    def __init__(self, model=16, data=16):
        self.axis_names = ("data", "model")
        self.shape = {"data": data, "model": model}


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_params_shardings_match_tree(arch):
    cfg = get_config(arch).reduced(
        n_layers=4 if arch == "jamba-1.5-large-398b" else 2)
    model = Model(cfg)
    shapes = model.init_shapes()
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = params_shardings(shapes, mesh)
    # same structure, every leaf is a NamedSharding with rank <= param rank
    jax.tree.map(lambda s, n: None, shapes, sh)
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(sh)[0]):
        assert len(spec.spec) <= len(leaf.shape), (path, spec.spec, leaf.shape)


def test_expert_parallel_spec():
    m = FakeMesh(model=16)
    spec = param_spec("blocks/0/moe/w_up", (59, 160, 5120, 1536), m,
                      stacked=True)
    assert spec == P(None, "model", None, None)
    # router replicated
    spec = param_spec("blocks/0/moe/w_router", (59, 5120, 160), m,
                      stacked=True)
    assert spec == P(None, None, None)


def test_gqa_head_fallback_to_head_dim():
    m = FakeMesh(model=16)
    # kv heads = 4 < 16 → shard head_dim (128 % 16 == 0)
    spec = param_spec("blocks/0/attn/w_k", (94, 4096, 4, 128), m,
                      stacked=True)
    assert spec == P(None, None, None, "model")
    # q heads 64 → shard heads
    spec = param_spec("blocks/0/attn/w_q", (94, 4096, 64, 128), m,
                      stacked=True)
    assert spec == P(None, None, "model", None)


def test_indivisible_replicates():
    m = FakeMesh(model=16)
    # 8 heads, head_dim 100: neither divisible -> replicate
    spec = param_spec("blocks/0/attn/w_k", (2, 512, 8, 100), m, stacked=True)
    assert spec == P(None, None, None, None)


def test_rwkv_names_not_confused_with_attention():
    m = FakeMesh(model=16)
    # rwkv w_k is (d, d) 2-D — must route to rwkv rules, not attention
    spec = param_spec("blocks/0/rwkv/w_k", (32, 4096, 4096), m, stacked=True)
    assert spec == P(None, None, "model")
    spec = param_spec("blocks/0/rwkv/w_o", (32, 4096, 4096), m, stacked=True)
    assert spec == P(None, "model", None)


def test_shared_expert_uses_dense_rules():
    m = FakeMesh(model=16)
    spec = param_spec("blocks/0/moe/shared/w_up", (59, 5120, 3072), m,
                      stacked=True)
    assert spec == P(None, None, "model")


def test_embed_vocab_sharding():
    m = FakeMesh(model=16)
    assert param_spec("embed", (151936, 4096), m, stacked=False) == \
        P("model", None)
    assert param_spec("embed", (51865, 768), m, stacked=False) == \
        P(None, None)  # 51865 % 16 != 0 → replicate


@pytest.mark.parametrize("axis_type", ["Explicit", "Auto"])
def test_embed_lookup_vocab_sharded_table(axis_type):
    """The embedding gather on a vocab-sharded table: under explicit axes
    JAX refuses the plain ``table[ids]`` (ambiguous output sharding), so
    the lookup names it. Either way the rows are exactly ``table[ids]``."""
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding
    from repro.models.layers import embed_lookup
    kind = getattr(AxisType, axis_type)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(kind,) * 2)
    table = jax.device_put(jnp.arange(64 * 16, dtype=jnp.float32)
                           .reshape(64, 16), NamedSharding(mesh, P("model")))
    ids = jax.device_put(jnp.array([[1, 5, 63]], jnp.int32),
                         NamedSharding(mesh, P("data")))
    with jax.set_mesh(mesh):
        rows = jax.jit(embed_lookup)(table, ids)
    assert np.array_equal(np.asarray(rows),
                          np.asarray(table)[np.asarray(ids)])
