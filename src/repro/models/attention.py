"""Attention blocks: GQA/MHA (qk-norm, bias, softcap, sliding window) and
DeepSeek-V2 MLA with compressed-latent KV cache (absorbed decode path).

All functions are shape-polymorphic over batch/sequence and operate on
``(B, S, d_model)`` activations. KV caches are explicit pytrees so they can be
sharded by the launcher and donated between decode steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ArchConfig
from repro.models.layers import (apply_rope, rms_norm_headwise, softcap,
                                 yarn_softmax_scale)

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_attn(rng, cfg: ArchConfig, dtype):
    d = cfg.d_model
    a = cfg.attn
    ks = jax.random.split(rng, 8)
    std = d ** -0.5
    if a.mla is not None:
        m = a.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        if m.q_lora_rank:
            p = {
                "w_dq": (jax.random.normal(ks[0], (d, m.q_lora_rank))
                         * std).astype(dtype),
                "w_uq": (jax.random.normal(ks[1], (m.q_lora_rank, cfg.n_heads,
                                                   qk))
                         * m.q_lora_rank ** -0.5).astype(dtype),
                "q_norm_scale": jnp.ones((m.q_lora_rank,), jnp.float32),
            }
        else:                          # no query compression: one projection
            p = {"w_q": (jax.random.normal(ks[0], (d, cfg.n_heads, qk))
                         * std).astype(dtype)}
        p.update({
            "w_dkv": (jax.random.normal(ks[2], (d, m.kv_lora_rank)) * std).astype(dtype),
            "w_kr": (jax.random.normal(ks[3], (d, m.qk_rope_head_dim)) * std).astype(dtype),
            "w_uk": (jax.random.normal(ks[4], (m.kv_lora_rank, cfg.n_heads,
                                               m.qk_nope_head_dim))
                     * m.kv_lora_rank ** -0.5).astype(dtype),
            "w_uv": (jax.random.normal(ks[5], (m.kv_lora_rank, cfg.n_heads,
                                               m.v_head_dim))
                     * m.kv_lora_rank ** -0.5).astype(dtype),
            "w_o": (jax.random.normal(ks[6], (cfg.n_heads, m.v_head_dim, d))
                    * (cfg.n_heads * m.v_head_dim) ** -0.5).astype(dtype),
            "kv_norm_scale": jnp.ones((m.kv_lora_rank,), jnp.float32),
        })
        return p
    hd = cfg.head_dim_
    p = {
        "w_q": (jax.random.normal(ks[0], (d, cfg.n_heads, hd)) * std).astype(dtype),
        "w_k": (jax.random.normal(ks[1], (d, cfg.n_kv_heads, hd)) * std).astype(dtype),
        "w_v": (jax.random.normal(ks[2], (d, cfg.n_kv_heads, hd)) * std).astype(dtype),
        "w_o": (jax.random.normal(ks[3], (cfg.n_heads, hd, d))
                * (cfg.n_heads * hd) ** -0.5).astype(dtype),
    }
    if a.qkv_bias:
        p["b_q"] = jnp.zeros((cfg.n_heads, hd), dtype)
        p["b_k"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
        p["b_v"] = jnp.zeros((cfg.n_kv_heads, hd), dtype)
    if a.qk_norm:
        p["q_norm_scale"] = jnp.ones((hd,), jnp.float32)
        p["k_norm_scale"] = jnp.ones((hd,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask, logit_cap: float):
    """q (B,S,H,hd), k/v (B,T,Hkv,hd); mask (B,1,S,T) or (1,1,S,T) additive.

    GQA is computed by grouping q heads (B,S,Hkv,rep,hd) — K/V are never
    materialized at H heads (§Perf: the jnp.repeat copy costs ~8.6 GB/layer
    per device at decode_32k scale)."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, S, Hkv, rep, hd)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    scores = softcap(scores, logit_cap)
    scores = scores + mask[:, :, None] if mask.ndim == 4 else scores + mask
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrst,btgd->bsgrd", w, v)
    return out.reshape(B, S, H, hd)


def _sdpa_blocked(q, k, v, logit_cap: float, *, offset: int = 0,
                  window: int = 0, causal: bool = True,
                  block_q: int = 512, block_kv: int = 1024):
    """Flash-style blocked attention: q-blocks outer, online softmax over KV
    blocks inner, never materializing the (S, T) score matrix.

    The loop nesting matters (§Perf iteration B2): a kv-outer loop carries
    full-length (S, …) running accumulators, re-reading ~400 MB of carry per
    chunk — measured NO memory-term win over naive scores. With q-outer /
    kv-inner the carry is one q-block (~6 MB), the true flash ordering.
    Pure jnp/lax so it lowers on the dry-run meshes; on TPU the decode path
    uses the Pallas flash_decode kernel with the same tiling.
    """
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    nkv = -(-T // block_kv)
    pad_kv = nkv * block_kv - T
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    kb = jnp.moveaxis(k.reshape(B, nkv, block_kv, Hkv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nkv, block_kv, Hkv, hd), 1, 0)
    nq = -(-S // block_q)
    pad_q = nq * block_q - S
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    qb = jnp.moveaxis(qp.reshape(B, nq, block_q, Hkv, rep, hd), 1, 0)
    scale = hd ** -0.5

    def q_block(qc, iq):
        qpos = iq * block_q + jnp.arange(block_q) + offset

        def kv_body(carry, inp):
            m, l, acc = carry                   # (B, bq, Hkv, rep, ·)
            kc, vc, j = inp
            scores = jnp.einsum("bsgrd,btgd->bsgrt", qc, kc
                                ).astype(jnp.float32) * scale
            scores = softcap(scores, logit_cap)
            kpos = j * block_kv + jnp.arange(block_kv)
            ok = (kpos < T)[None, :]
            if causal:
                ok = ok & (kpos[None, :] <= qpos[:, None])
            if window:
                ok = ok & (kpos[None, :] > qpos[:, None] - window)
            scores = jnp.where(ok[None, :, None, None, :], scores, -2e38)
            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bsgrt,btgd->bsgrd", p.astype(vc.dtype), vc
                ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, block_q, Hkv, rep, 1), -2e38, jnp.float32)
        l0 = jnp.zeros((B, block_q, Hkv, rep, 1), jnp.float32)
        a0 = jnp.zeros((B, block_q, Hkv, rep, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                      (kb, vb, jnp.arange(nkv)))
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (qb, jnp.arange(nq)))                # (nq, B, bq, …)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * block_q, Hkv, rep, hd)
    return out[:, :S].reshape(B, S, H, hd)


def causal_mask(S: int, T: int, offset: int = 0, window: int = 0):
    """Additive (1,1,S,T) mask. ``offset`` = absolute position of query 0.
    ``window``: sliding-window size (0 = full causal)."""
    qpos = jnp.arange(S)[:, None] + offset
    kpos = jnp.arange(T)[None, :]
    ok = kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, NEG_INF)[None, None].astype(jnp.float32)


def decode_mask(T: int, cache_len, window: int = 0):
    """Mask for a single-token query attending to a cache of physical length
    T, logically filled to ``cache_len`` (inclusive of the current token at
    cache_len-1). ``cache_len`` may be a scalar (batch-shared length) or a
    ``(B,)`` vector of per-slot lengths (the slot-pool decode path, where
    every sequence in the pool sits at its own position)."""
    kpos = jnp.arange(T)[None, None, None, :]
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim:
        cache_len = cache_len.reshape(-1, 1, 1, 1)
    ok = kpos < cache_len
    if window:
        ok &= kpos >= cache_len - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def update_rows(buf, upd, pos):
    """Write ``upd`` (B, n, ...) into ``buf`` (B, T, ...) at per-row position
    ``pos`` ((B,) int32 vector, or scalar for the batch-shared legacy path).
    The vmap'd dynamic_update_slice is the slot-pool cache write: each slot
    appends at its own sequence position."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (buf.shape[0],))
    return jax.vmap(
        lambda b, u, p: jax.lax.dynamic_update_slice_in_dim(b, u, p, 0)
    )(buf, upd.astype(buf.dtype), pos)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------


def _project_qkv(p, cfg: ArchConfig, x, positions):
    a = cfg.attn
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["w_k"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["w_v"])
    if a.qkv_bias:
        q, k, v = q + p["b_q"], k + p["b_k"], v + p["b_v"]
    if a.qk_norm:
        q = rms_norm_headwise(q, p["q_norm_scale"])
        k = rms_norm_headwise(k, p["k_norm_scale"])
    if a.use_rope:
        q = apply_rope(q, positions, a.rope_theta, a.mrope_sections)
        k = apply_rope(k, positions, a.rope_theta, a.mrope_sections)
    return q, k, v


def attn_forward(p, cfg: ArchConfig, x, positions, *, window: int = 0,
                 kv: tuple | None = None, mask=None):
    """Full-sequence (train / prefill) self- or cross-attention.

    ``kv``: optional (k, v) for cross-attention (already projected).
    Returns (out, (k, v)) so prefill can seed the cache.
    """
    if kv is None:
        q, k, v = _project_qkv(p, cfg, x, positions)
        if mask is None and cfg.attn_impl == "blocked":
            out = _sdpa_blocked(q, k, v, cfg.attn.logit_softcap,
                                window=window)
            out = jnp.einsum("bshk,hkd->bsd", out, p["w_o"])
            return out, (k, v)
        if mask is None:
            mask = causal_mask(x.shape[1], k.shape[1], window=window)
    else:
        a = cfg.attn
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
        if a.qkv_bias:
            q = q + p["b_q"]
        k, v = kv
        if mask is None:
            mask = jnp.zeros((1, 1, 1, 1), jnp.float32)
    out = _sdpa(q, k, v, mask, cfg.attn.logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["w_o"])
    return out, (k, v)


def cross_kv(p, cfg: ArchConfig, enc_out):
    """Project encoder output once into cross-attention K/V."""
    a = cfg.attn
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["w_k"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["w_v"])
    if a.qkv_bias:
        k, v = k + p["b_k"], v + p["b_v"]
    return k, v


def attn_decode(p, cfg: ArchConfig, x, cache_k, cache_v, pos, *,
                window: int = 0, cross: bool = False):
    """One-token decode. x (B,1,d); cache_k/v (B,T,Hkv,hd); pos is the index
    of the new token — a scalar (batch-shared) or a (B,) vector of per-slot
    positions (slot-pool mode: every sequence writes and masks at its own
    length). Returns (out, new_k_cache, new_v_cache)."""
    a = cfg.attn
    if cross:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
        if a.qkv_bias:
            q = q + p["b_q"]
        mask = jnp.zeros((1, 1, 1, 1), jnp.float32)
        out = _sdpa(q, cache_k, cache_v, mask, a.logit_softcap)
        return jnp.einsum("bshk,hkd->bsd", out, p["w_o"]), cache_k, cache_v
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))
    positions = pos[:, None]
    if a.mrope_sections:
        positions = jnp.broadcast_to(positions, (3,) + positions.shape)
    q, k, v = _project_qkv(p, cfg, x, positions)
    cache_k = update_rows(cache_k, k, pos)
    cache_v = update_rows(cache_v, v, pos)
    mask = decode_mask(cache_k.shape[1], pos + 1, window=window)
    out = _sdpa(q, cache_k, cache_v, mask, a.logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["w_o"]), cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def _mla_q(p, cfg, x, positions):
    m = cfg.attn.mla
    if m.q_lora_rank:
        cq = rms_norm_headwise(x @ p["w_dq"], p["q_norm_scale"])
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions,
                        attn=cfg.attn)
    return q_nope, q_rope


def _mla_latents(p, cfg, x, positions):
    c_kv = rms_norm_headwise(x @ p["w_dkv"], p["kv_norm_scale"])
    k_rope = apply_rope((x @ p["w_kr"])[..., None, :], positions,
                        attn=cfg.attn)[..., 0, :]
    return c_kv, k_rope


def _mla_scale(cfg) -> float:
    m = cfg.attn.mla
    return ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
            * yarn_softmax_scale(cfg.attn))


def mla_forward(p, cfg: ArchConfig, x, positions, mask=None):
    """Full-sequence MLA: latent KV is materialized per head (train/prefill).
    Returns (out, (c_kv, k_rope)) for cache seeding."""
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_latents(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"])
    scale = _mla_scale(cfg)
    scores = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
              + jnp.einsum("bshk,btk->bhst", q_rope, k_rope)).astype(jnp.float32)
    if mask is None:
        mask = causal_mask(x.shape[1], x.shape[1])
    w = jax.nn.softmax(scores * scale + mask, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthk->bshk", w, v)
    out = jnp.einsum("bshk,hkd->bsd", out, p["w_o"])
    return out, (c_kv, k_rope)


def mla_decode(p, cfg: ArchConfig, x, cache_ckv, cache_kr, pos):
    """Absorbed-matrix MLA decode: attention runs in the compressed latent
    space (the serving-efficient path from the DeepSeek-V2 paper). ``pos``
    may be a scalar or a (B,) per-slot position vector."""
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)            # (B,1,H,*)
    c_kv, k_rope = _mla_latents(p, cfg, x, positions)        # (B,1,r), (B,1,rope)
    cache_ckv = update_rows(cache_ckv, c_kv, pos)
    cache_kr = update_rows(cache_kr, k_rope, pos)
    # Absorb W_uk into q: q_abs (B,1,H,r)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])
    scores = (jnp.einsum("bshr,btr->bhst", q_abs, cache_ckv)
              + jnp.einsum("bshk,btk->bhst", q_rope, cache_kr)).astype(jnp.float32)
    scale = _mla_scale(cfg)
    mask = decode_mask(cache_ckv.shape[1], pos + 1)
    w = jax.nn.softmax(scores * scale + mask, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhst,btr->bshr", w, cache_ckv)         # (B,1,H,r)
    out = jnp.einsum("bshr,rhk->bshk", ctx, p["w_uv"])       # absorb W_uv
    out = jnp.einsum("bshk,hkd->bsd", out, p["w_o"])
    return out, cache_ckv, cache_kr
