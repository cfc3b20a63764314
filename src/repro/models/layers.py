"""Shared building blocks: embedding lookup, norms, activations, RoPE /
M-RoPE, FFNs."""
from __future__ import annotations

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


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0,
               mrope_sections=()):
    """Rotate ``x`` (..., S, H, hd) by ``positions``.

    ``positions``: (B, S) int32, or (3, B, S) for M-RoPE where the three planes
    are the temporal/height/width position ids (Qwen2-VL). ``mrope_sections``
    splits the half-dim into per-plane sections.
    """
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)  # (hd/2,)
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
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)
