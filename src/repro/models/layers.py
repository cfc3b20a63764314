"""Shared building blocks: embedding lookup, norms, activations, RoPE /
M-RoPE, FFNs."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.config import ArchConfig


def embed_lookup(table, ids):
    """``table[ids]`` row gather. Under explicit mesh axes a vocab-sharded
    table leaves the gather's output sharding ambiguous, so it is named:
    laid out like ``ids`` with the feature dim replicated. Without a mesh,
    or under Auto axes (GSPMD propagates it), this is the plain gather."""
    mesh = jax.typeof(table).sharding.mesh
    if AxisType.Explicit not in mesh.axis_types:
        return table[ids]
    spec = P(*jax.typeof(ids).sharding.spec, None)
    return table.at[ids].get(out_sharding=NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ArchConfig, d: int):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_norm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


def rms_norm_headwise(x, scale, eps: float = 1e-6):
    """Qwen3 qk-norm: RMSNorm over the last (head) dim with a learned scale."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation(name: str):
    if name in ("swiglu",):
        return jax.nn.silu
    if name in ("geglu", "gelu"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def is_gated(name: str) -> bool:
    return name in ("swiglu", "geglu")


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------


def init_ffn(rng, cfg: ArchConfig, d_ff: int, dtype):
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(rng, 3)
    std = d ** -0.5
    p = {"w_up": (jax.random.normal(k2, (d, d_ff)) * std).astype(dtype),
         "w_down": (jax.random.normal(k3, (d_ff, d)) * d_ff ** -0.5).astype(dtype)}
    if is_gated(cfg.act):
        p["w_gate"] = (jax.random.normal(k1, (d, d_ff)) * std).astype(dtype)
    return p


def apply_ffn(p, x, act_name: str):
    act = activation(act_name)
    up = x @ p["w_up"]
    h = act(x @ p["w_gate"]) * up if "w_gate" in p else act(up)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times (DeepSeek's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(attn, head_dim: int, theta: float):
    """Rotary pairs [low, high] between which YaRN blends the original
    frequency into the stretched one: the pairs that turn ``beta_fast`` and
    ``beta_slow`` times over the original context (DeepSeek's
    ``yarn_find_correction_range``)."""
    def pair(rotations):
        return (head_dim * math.log(attn.yarn_original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(pair(attn.yarn_beta_fast))
    high = math.ceil(pair(attn.yarn_beta_slow))
    return max(low, 0), min(high, head_dim - 1)


def rope_freqs(head_dim: int, theta: float = 10000.0, attn=None):
    """Inverse rotary frequencies, one per pair. ``attn``, when given, sets
    theta (its ``rope_theta``) in place of ``theta``. With YaRN (its
    ``yarn_factor`` > 1) pairs below the correction range keep theirs,
    pairs above it are divided by the factor, and a linear ramp joins
    them."""
    if attn is not None:
        theta = attn.rope_theta
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if attn is None or attn.yarn_factor <= 1:
        return inv
    low, high = yarn_correction_range(attn, head_dim, theta)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / attn.yarn_factor * ramp + inv * (1.0 - ramp)


def yarn_softmax_scale(attn) -> float:
    """Factor on the attention softmax scale under YaRN (1 without)."""
    if not attn.yarn_mscale_all_dim:
        return 1.0
    return yarn_mscale(attn.yarn_factor, attn.yarn_mscale_all_dim) ** 2


def apply_rope(x, positions, theta: float = 10000.0,
               mrope_sections=(), attn=None):
    """Rotate ``x`` (..., S, H, hd) by ``positions``.

    ``positions``: (B, S) int32, or (3, B, S) for M-RoPE where the three planes
    are the temporal/height/width position ids (Qwen2-VL). ``mrope_sections``
    splits the half-dim into per-plane sections. ``attn``: the AttnConfig
    whose ``rope_theta`` replaces ``theta`` and whose YaRN fields stretch
    the frequencies and scale cos and sin by mscale / mscale_all_dim
    (:func:`rope_freqs`).
    """
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta, attn)  # (hd/2,)
    if mrope_sections:
        assert positions.ndim == 3, "M-RoPE needs (3, B, S) position ids"
        secs = list(mrope_sections)
        assert sum(secs) == hd // 2
        plane = jnp.concatenate(
            [jnp.full((n,), i, jnp.int32) for i, n in enumerate(secs)])
        pos = jnp.take_along_axis(
            positions.transpose(1, 2, 0),                      # (B, S, 3)
            jnp.broadcast_to(plane, positions.shape[1:] + (hd // 2,))
            .astype(jnp.int32), axis=-1)                       # (B, S, hd/2)
        ang = pos.astype(jnp.float32) * inv                    # (B, S, hd/2)
    else:
        ang = positions.astype(jnp.float32)[..., None] * inv   # (B, S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                           # (B, S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    if attn is not None and attn.yarn_factor > 1:
        f = (yarn_mscale(attn.yarn_factor, attn.yarn_mscale)
             / yarn_mscale(attn.yarn_factor, attn.yarn_mscale_all_dim))
        if f != 1.0:
            cos, sin = cos * f, sin * f
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)
