"""Single-token flash-attention decode kernel over a long KV cache (GQA).

One generated token's query attends to a KV cache of up to 524k positions
(the long_500k shape). TPU adaptation:

- Grid (B, S/bs): each program takes one sequence block of K/V across
  *all* KV heads, ``(bs, Hkv, hd)``, and walks the heads in a static loop;
  per head, the ``rep = H/Hkv`` query heads that share it attend as one
  (rep, hd) q tile, so the GQA repetition never materializes in memory (a
  CUDA impl would broadcast K/V across warps). The block spans the full
  Hkv axis because a block of one head would put a 1 on the second-minor
  dim of the (B, S, Hkv, hd) cache, which the TPU's (8, 128) tiling
  refuses whenever Hkv is not 1.
- Online softmax: running (m, l, acc) scratch per head in VMEM, revisited
  across the S grid dimension (sequential innermost dim), so the KV cache
  streams HBM→VMEM exactly once.
- ``cache_len`` arrives as a scalar-prefetch operand (SMEM); positions
  beyond it are masked before the running-max update. It may be a scalar
  (batch-shared length, the lockstep path) or a ``(B,)`` vector of
  *per-slot* lengths — under slot-pool continuous batching every sequence
  in the pool sits at its own decode position, so each batch row masks its
  own valid prefix (indexed via ``program_id(0)`` from SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bs: int, n_kv: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < len_ref[b]
    for h in range(n_kv):
        q = q_ref[0, h]                               # (rep, hd)
        k = k_ref[0, :, h, :]                         # (bs, hd)
        v = v_ref[0, :, h, :]                         # (bs, hd)
        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        scores = jnp.where(valid, scores, NEG_INF)    # (rep, bs)

        m_prev, l_prev = m_ref[h], l_ref[h]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)   # (rep, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)                   # (rep, bs)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(q, k, v, cache_len, *, block_s: int = 512,
                 interpret: bool = False):
    """q: (B, H, hd); k/v: (B, S, Hkv, hd); cache_len: int32 scalar (valid
    prefix length of the cache, batch-shared) or (B,) vector of per-slot
    lengths. -> (B, H, hd)."""
    B, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    rep = H // Hkv
    bs = min(block_s, S)
    assert S % bs == 0
    qg = q.reshape(B, Hkv, rep, hd)
    grid = (B, S // bs)
    scale = hd ** -0.5
    lens = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))

    kv_spec = pl.BlockSpec((1, bs, Hkv, hd), lambda b, s, *_: (b, s, 0, 0))
    q_spec = pl.BlockSpec((1, Hkv, rep, hd), lambda b, s, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, n_kv=Hkv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((Hkv, rep, 1), jnp.float32),   # running max
                pltpu.VMEM((Hkv, rep, 1), jnp.float32),   # running denom
                pltpu.VMEM((Hkv, rep, hd), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        interpret=interpret,
    )(lens, qg, k, v)
    return out.reshape(B, H, hd)
