"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.moe_ffn import moe_ffn
from repro.kernels.wkv6 import wkv6


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("E,C,d,f", [(2, 64, 128, 256), (4, 128, 256, 512),
                                     (1, 128, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["swiglu", "gelu", "relu2"])
def test_moe_ffn_kernel(E, C, d, f, dtype, act):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    xg = jax.random.normal(ks[0], (E, C, d)).astype(dtype)
    gated = act == "swiglu"
    wg = (jax.random.normal(ks[1], (E, d, f)) * 0.05).astype(dtype) \
        if gated else None
    wu = (jax.random.normal(ks[2], (E, d, f)) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (E, f, d)) * 0.05).astype(dtype)
    y = moe_ffn(xg, wg, wu, wd, act=act, block_c=64, block_f=128,
                interpret=True)
    y_ref = ref.moe_ffn_ref(xg, wg, wu, wd, act=act)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


def test_moe_ffn_slots_kernel_matches_dense():
    """Slot-indexed dispatch (expert slot cache): gathering per-slot
    weights through a permuted expert→slot table is bit-identical to the
    dense kernel on the same weights."""
    from repro.kernels.moe_ffn import moe_ffn_slots
    E, C, d, f = 4, 64, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    xg = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    wg = jax.random.normal(ks[1], (E, d, f), jnp.float32) * 0.05
    wu = jax.random.normal(ks[2], (E, d, f), jnp.float32) * 0.05
    wd = jax.random.normal(ks[3], (E, f, d), jnp.float32) * 0.05
    y_dense = moe_ffn(xg, wg, wu, wd, act="swiglu", block_c=64,
                      block_f=128, interpret=True)
    perm = np.array([2, 0, 3, 1])                    # slot s holds expert perm[s]
    slots = {"w_gate": wg[perm], "w_up": wu[perm], "w_down": wd[perm]}
    slot_ids = jnp.asarray(np.argsort(perm), jnp.int32)
    y_slots = moe_ffn_slots(xg, slots, slot_ids, act="swiglu", block_c=64,
                            block_f=128, interpret=True)
    assert np.array_equal(np.asarray(y_dense), np.asarray(y_slots))


@pytest.mark.parametrize("B,H,Hkv,hd,S", [(1, 4, 4, 64, 256),
                                          (2, 8, 2, 64, 512),
                                          (1, 16, 1, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_kernel(B, H, Hkv, hd, S, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd)).astype(dtype)
    for cache_len in (S, S - 17, 1):
        y = flash_decode(q, k, v, cache_len, block_s=128, interpret=True)
        y_ref = ref.flash_decode_ref(q, k, v, cache_len)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_kernel_per_slot_lengths(dtype):
    """Slot-pool decode: each batch row masks its own valid prefix, and a
    row's output is independent of the other rows' lengths."""
    B, H, Hkv, hd, S = 4, 8, 2, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd)).astype(dtype)
    lens = jnp.asarray([S, 7, 129, 1], jnp.int32)
    y = flash_decode(q, k, v, lens, block_s=128, interpret=True)
    y_ref = ref.flash_decode_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))
    # row b under ragged lengths == row b under its batch-shared length
    for b, L in enumerate([S, 7, 129, 1]):
        y_solo = flash_decode(q, k, v, L, block_s=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(y[b]), np.asarray(y_solo[b]))


@pytest.mark.parametrize("BH,T,hd,chunk", [(2, 64, 64, 32), (4, 32, 32, 32),
                                           (1, 128, 64, 64)])
def test_wkv6_kernel(BH, T, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    r = jax.random.normal(ks[0], (BH, T, hd)) * 0.5
    k = jax.random.normal(ks[1], (BH, T, hd)) * 0.5
    v = jax.random.normal(ks[2], (BH, T, hd)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (BH, T, hd)))
    u = jax.random.normal(ks[4], (BH, hd)) * 0.1
    s0 = jax.random.normal(ks[5], (BH, hd, hd)) * 0.1
    o, sN = wkv6(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    o_ref, sN_ref = ref.wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sN), np.asarray(sN_ref),
                               atol=1e-4, rtol=1e-4)


def test_wkv6_state_carries_across_chunks():
    """Chunked result must equal single-chunk result exactly."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    BH, T, hd = 1, 64, 32
    r, k, v = (jax.random.normal(ks[i], (BH, T, hd)) * 0.5 for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (BH, T, hd)))
    u = jax.random.normal(ks[4], (BH, hd)) * 0.1
    s0 = jnp.zeros((BH, hd, hd))
    o1, s1 = wkv6(r, k, v, w, u, s0, chunk=16, interpret=True)
    o2, s2 = wkv6(r, k, v, w, u, s0, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)

