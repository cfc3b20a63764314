"""Plain float32 reference for the Switch decoder stack as served.

Independent of the program: it imports nothing of ``repro`` and draws its
own weights from the run's seed, by the same recipe the served model is
initialised with (normal(0, fan_in^-0.5), rounded to the configuration's
dtype; norms at one), and computes layer by layer with full expert tables
and no slot cache, every matrix product at ``precision="highest"``.

What it computes, per the configuration's ``arch`` block:
  x = embed[tokens]
  per layer: x += attn(rms(x)) with RoPE and a causal softmax;
             x += ffn(rms(x)), or in every ``moe_layer_period``-th layer the
             top-1 expert's tanh-GELU FFN scaled by its router probability
  logits = rms(x) @ lm_head
Served semantics it mirrors: prompt tokens pass the MoE with the prefill
capacity bound (per expert, only the first ``floor(S * top_k / E * f) + 1``
prompt tokens in position order are kept; the rest get no expert output),
generated tokens are dropless.

``quant`` computes the control: every matrix product takes its operands
rounded to the next precision below the configuration's, float8 e4m3 for
bfloat16 (``"fp8"``: weights with one scale per tensor, activations one per
row) and bfloat16 for float32 (``"bf16"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


# -- weights ---------------------------------------------------------------------

def _dtype(arch):
    return jnp.dtype(arch["dtype"])


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def layer_keys(arch, seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 8 + arch["n_layers"])


@functools.partial(jax.jit, static_argnums=(0,))
def _globals(shape_args, keys):
    V, d, dtype = shape_args
    return {"embed": _normal(keys[0], (V, d), d ** -0.5, dtype),
            "lm_head": _normal(keys[1], (d, V), d ** -0.5, dtype)}


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(shape_args, key):
    d, H, Hkv, hd, ff, moe, E, dtype = shape_args
    sub = jax.random.split(key, 4)
    a = jax.random.split(sub[0], 8)
    std = d ** -0.5
    w = {"wq": _normal(a[0], (d, H, hd), std, dtype),
         "wk": _normal(a[1], (d, Hkv, hd), std, dtype),
         "wv": _normal(a[2], (d, Hkv, hd), std, dtype),
         "wo": _normal(a[3], (H, hd, d), (H * hd) ** -0.5, dtype)}
    if moe:
        m = jax.random.split(sub[1], 5)
        w["router"] = _normal(m[0], (d, E), std, F32)
        w["up"] = _normal(m[2], (E, d, ff), std, dtype)
        w["down"] = _normal(m[3], (E, ff, d), ff ** -0.5, dtype)
    else:
        k = jax.random.split(sub[1], 3)
        w["up"] = _normal(k[1], (d, ff), std, dtype)
        w["down"] = _normal(k[2], (ff, d), ff ** -0.5, dtype)
    return w


def is_moe(arch, i: int) -> bool:
    m = arch["moe"]
    return i % m["moe_layer_period"] == m["moe_layer_offset"]


def global_weights(arch, seed: int) -> dict:
    keys = layer_keys(arch, seed)
    return _globals((arch["vocab"], arch["d_model"], _dtype(arch)), keys)


def layer_weights(arch, seed: int, i: int) -> dict:
    moe = is_moe(arch, i)
    ff = arch["moe"]["d_expert"] if moe else arch["d_ff"]
    args = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
            arch["head_dim"], ff, moe, arch["moe"]["n_experts"], _dtype(arch))
    return _layer(args, layer_keys(arch, seed)[8 + i])


# -- arithmetic ------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis``."""
    x = x.astype(F32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def _w(w, quant):
    w = w.astype(F32)
    if quant == "fp8":
        return _fp8(w, None)
    return _bf16(w) if quant == "bf16" else w


def _a(x, quant):
    if quant == "fp8":
        return _fp8(x, -1)
    return _bf16(x) if quant == "bf16" else x


def _rms(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _gelu(x):
    return jax.nn.gelu(x, approximate=True)


def _rope(x, theta):
    """x (n, L, H, hd), positions 0..L-1; halves rotated as a pair."""
    L, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(L, dtype=F32)[:, None] * inv          # (L, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(eq, a, w, quant):
    return jnp.einsum(eq, _a(a, quant), _w(w, quant))


def _attention(x, w, theta, quant):
    h = _rms(x)
    q = _rope(_mm("nld,dhk->nlhk", h, w["wq"], quant), theta)
    k = _rope(_mm("nld,dhk->nlhk", h, w["wk"], quant), theta)
    v = _mm("nld,dhk->nlhk", h, w["wv"], quant)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("nqhk,nthk->nhqt", _a(q, quant), _a(k, quant))
    s = s * q.shape[-1] ** -0.5
    L = x.shape[1]
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqt,nthk->nqhk", _a(p, quant), _a(v, quant))
    return x + _mm("nqhk,hkd->nqd", o, w["wo"], quant)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_layer(x, w, theta, quant):
    x = _attention(x, w, theta, quant)
    h = _rms(x)
    return x + _mm("nlf,fd->nld", _gelu(_mm("nld,df->nlf", h, w["up"], quant)),
                   w["down"], quant)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _moe_layer(x, w, prompt_lens, theta, cap_factor, quant):
    x = _attention(x, w, theta, quant)
    h = _rms(x)
    n, L, _ = h.shape
    E = w["router"].shape[1]
    probs = jax.nn.softmax(_mm("nld,de->nle", h, w["router"], quant), -1)
    expert = jnp.argmax(probs, axis=-1)                   # (n, L)
    gate = jnp.max(probs, axis=-1)
    # prefill capacity: per expert, the first C prompt tokens are kept
    prompt = jnp.arange(L)[None, :] < prompt_lens[:, None]
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32) * prompt[..., None]
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=1) - onehot,
                               expert[..., None], axis=-1)[..., 0]
    S = prompt_lens.astype(F32)
    cap = jnp.floor(S * 1.0 / E * cap_factor).astype(jnp.int32) + 1
    cap = jnp.clip(cap, 1, prompt_lens)
    coef = gate * jnp.where(prompt, rank < cap[:, None], True)

    def one(j, y):
        hj = _mm("nlf,fd->nld",
                 _gelu(_mm("nld,df->nlf", h, w["up"][j], quant)),
                 w["down"][j], quant)
        return y + jnp.where(expert == j, coef, 0.0)[..., None] * hj
    return x + jax.lax.fori_loop(0, E, one, jnp.zeros_like(x))


def hidden(arch, seed: int, tokens, prompt_lens, cap_factor: float,
           quant=None):
    """Final normed hidden states (n, L, d) of the token rows, and the LM
    head (d, V), both float32. Layer by layer, so only one layer's weights
    live on the device at a time."""
    if arch["moe"]["top_k"] != 1 or arch["act"] != "gelu" \
            or arch["norm"] != "rmsnorm":
        raise NotImplementedError("this reference covers top-1 GELU "
                                  "RMSNorm Switch stacks")
    theta = float(arch["attn"]["rope_theta"])
    with jax.default_matmul_precision("highest"):
        g = global_weights(arch, seed)
        x = _w(g["embed"], quant)[jnp.asarray(tokens)]
        lens = jnp.asarray(prompt_lens, jnp.int32)
        for i in range(arch["n_layers"]):
            w = layer_weights(arch, seed, i)
            if is_moe(arch, i):
                x = _moe_layer(x, w, lens, theta, float(cap_factor), quant)
            else:
                x = _dense_layer(x, w, theta, quant)
            del w
        return _rms(x), _w(g["lm_head"], quant)
