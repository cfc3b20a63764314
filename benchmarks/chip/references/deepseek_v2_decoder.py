"""Plain float32 reference for the DeepSeek-V2 decoder stack as served.

Independent of the program: it imports nothing of ``repro`` and draws its
own weights from the run's seed, by the same recipe the served model is
initialised with (normal(0, fan_in^-0.5), rounded to the configuration's
dtype; norms at one), and computes layer by layer with full expert tables
and no slot cache, every matrix product at ``precision="highest"``.

What it computes, per the configuration's ``arch`` block:
  x = embed[tokens]
  per layer: x += mla(rms(x)), then x += ffn(rms(x)): a SwiGLU of d_ff in
             the first ``first_dense_layers`` layers, else the MoE below
  logits = rms(x) @ lm_head
MLA, in its plain (non-absorbed) form: q = h @ w_q per head (no query
compression when ``q_lora_rank`` is 0 or null, else rms(h @ w_dq) @ w_uq),
split into a ``qk_nope_head_dim`` part and a ``qk_rope_head_dim`` part;
the latent c = rms(h @ w_dkv) (``kv_lora_rank``) is expanded to per-head
keys c @ w_uk and values c @ w_uv; one rotary key h @ w_kr is shared by
every head. Scores (q_nope.k_nope + q_rope.k_rope) times the softmax scale
go through a causal softmax, and the heads' outputs through w_o.
YaRN RoPE, as DeepSeek-V2 defines it: the inverse frequency of pair i is
  inv_i (1 - ramp_i) + inv_i / factor ramp_i,   inv_i = theta^(-2i/dim),
with ramp_i = clip((i - low) / (high - low), 0, 1), where low = floor and
high = ceil of dim ln(L0 / (2 pi b)) / (2 ln theta) for b = beta_fast and
beta_slow over L0 = original_max_position; cos and sin are multiplied by
mscale(factor, mscale) / mscale(factor, mscale_all_dim), and the softmax
scale is (qk_nope + qk_rope)^-0.5 mscale(factor, mscale_all_dim)^2, with
mscale(s, m) = 0.1 m ln(s) + 1.
MoE: router probabilities softmax(h @ router) in float32; each token takes
its top-k experts with their probabilities as gates (no renormalisation,
routed scaling 1); each expert is a SwiGLU of d_expert; the
``n_shared_experts`` shared experts, one SwiGLU of n_shared x d_shared,
are added for every token.

Departures from the source, which the served program shares:
  - prompt tokens pass the routed experts with the served prefill capacity
    bound: per expert, only the first ``max(k, floor(S k / E f) + 1)``
    prompt tokens routed to it, in position order, are kept (the rest get
    no output from that expert); generated tokens are dropless. The source
    is dropless everywhere.
  - rotary pairs are the two halves of the rotary part, where the source
    interleaves them (even and odd elements); with random weights that is
    a fixed relabelling of the rotary output columns of w_q and w_kr.
  - the dense and MoE layers draw their weights by the program's recipe,
    not from a checkpoint; the router is a plain softmax top-k, as
    ``topk_method`` greedy is.

``quant`` computes the control: every matrix product takes its operands
rounded to the next precision below the configuration's, float8 e4m3 for
bfloat16 (``"fp8"``: weights with one scale per tensor, activations one per
row) and bfloat16 for float32 (``"bf16"``).
"""
from __future__ import annotations

import functools
import math

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


def _swiglu_weights(key, d, ff, dtype):
    k = jax.random.split(key, 3)
    return {"gate": _normal(k[0], (d, ff), d ** -0.5, dtype),
            "up": _normal(k[1], (d, ff), d ** -0.5, dtype),
            "down": _normal(k[2], (ff, d), ff ** -0.5, dtype)}


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(shape_args, key):
    d, H, mla, moe, ff, E, shared, dtype = shape_args
    r, q_rank, nope, rope, vd = mla
    qk = nope + rope
    sub = jax.random.split(key, 4)
    a = jax.random.split(sub[0], 8)
    std = d ** -0.5
    if q_rank:
        w = {"wdq": _normal(a[0], (d, q_rank), std, dtype),
             "wuq": _normal(a[1], (q_rank, H, qk), q_rank ** -0.5, dtype)}
    else:
        w = {"wq": _normal(a[0], (d, H, qk), std, dtype)}
    w.update({"wdkv": _normal(a[2], (d, r), std, dtype),
              "wkr": _normal(a[3], (d, rope), std, dtype),
              "wuk": _normal(a[4], (r, H, nope), r ** -0.5, dtype),
              "wuv": _normal(a[5], (r, H, vd), r ** -0.5, dtype),
              "wo": _normal(a[6], (H, vd, d), (H * vd) ** -0.5, dtype)})
    if moe:
        m = jax.random.split(sub[1], 5)
        w["router"] = _normal(m[0], (d, E), std, F32)
        w["gate"] = _normal(m[1], (E, d, ff), std, dtype)
        w["up"] = _normal(m[2], (E, d, ff), std, dtype)
        w["down"] = _normal(m[3], (E, ff, d), ff ** -0.5, dtype)
        if shared:
            w["shared"] = _swiglu_weights(m[4], d, shared, dtype)
    else:
        w["ffn"] = _swiglu_weights(sub[1], d, ff, dtype)
    return w


def is_moe(arch, i: int) -> bool:
    m = arch["moe"]
    return (i >= m.get("first_dense_layers", 0)
            and i % m.get("moe_layer_period", 1) == m.get("moe_layer_offset",
                                                          0))


def global_weights(arch, seed: int) -> dict:
    keys = layer_keys(arch, seed)
    return _globals((arch["vocab"], arch["d_model"], _dtype(arch)), keys)


def _mla_dims(arch):
    m = arch["attn"]["mla"]
    return (m["kv_lora_rank"], m.get("q_lora_rank") or 0,
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])


def layer_weights(arch, seed: int, i: int) -> dict:
    moe = is_moe(arch, i)
    m = arch["moe"]
    ff = m["d_expert"] if moe else arch["d_ff"]
    shared = m.get("n_shared_experts", 0) * (m.get("d_shared")
                                             or m["d_expert"])
    args = (arch["d_model"], arch["n_heads"], _mla_dims(arch), moe, ff,
            m["n_experts"], shared, _dtype(arch))
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


def _mm(eq, a, w, quant):
    return jnp.einsum(eq, _a(a, quant), _w(w, quant))


def _swiglu(h, w, quant):
    g = _mm("nld,df->nlf", h, w["gate"], quant)
    u = _mm("nld,df->nlf", h, w["up"], quant)
    return _mm("nlf,fd->nld", jax.nn.silu(g) * u, w["down"], quant)


# -- YaRN rotary embedding -------------------------------------------------------

def _mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn(attn: dict, dim: int):
    """(inverse frequencies (dim/2,), cos/sin factor, softmax factor)."""
    theta = float(attn["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    s = float(attn.get("yarn_factor", 1.0))
    if s <= 1:
        return inv, 1.0, 1.0
    L0 = attn.get("yarn_original_max_position", 4096)

    def corr(b):
        return dim * math.log(L0 / (b * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(attn.get("yarn_beta_fast", 32.0))), 0)
    high = min(math.ceil(corr(attn.get("yarn_beta_slow", 1.0))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    inter = 1.0 / (s * theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    inv = inter * ramp + inv * (1.0 - ramp)
    m, m_all = attn.get("yarn_mscale", 1.0), attn.get("yarn_mscale_all_dim",
                                                      0.0)
    rot = _mscale(s, m) / _mscale(s, m_all)
    soft = _mscale(s, m_all) ** 2 if m_all else 1.0
    return inv, rot, soft


def _rope(x, inv, rot):
    """x (n, L, ..., dim), positions 0..L-1; halves rotated as a pair."""
    L = x.shape[1]
    ang = jnp.arange(L, dtype=F32)[:, None] * inv           # (L, dim/2)
    shape = (1, L) + (1,) * (x.ndim - 3) + (inv.shape[0],)
    cos = (jnp.cos(ang) * rot).reshape(shape)
    sin = (jnp.sin(ang) * rot).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# -- layers ----------------------------------------------------------------------

def _mla(x, w, dims, rope_args, quant):
    r, q_rank, nope, rope, vd = dims
    inv, rot, soft = rope_args
    h = _rms(x)
    if q_rank:
        q = _mm("nlr,rhk->nlhk", _rms(_mm("nld,dr->nlr", h, w["wdq"], quant)),
                w["wuq"], quant)
    else:
        q = _mm("nld,dhk->nlhk", h, w["wq"], quant)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], inv, rot)
    c = _rms(_mm("nld,dr->nlr", h, w["wdkv"], quant))
    k_rope = _rope(_mm("nld,dk->nlk", h, w["wkr"], quant), inv, rot)
    k_nope = _mm("nlr,rhk->nlhk", c, w["wuk"], quant)
    v = _mm("nlr,rhk->nlhk", c, w["wuv"], quant)
    s = (jnp.einsum("nqhk,nthk->nhqt", _a(q_nope, quant), _a(k_nope, quant))
         + jnp.einsum("nqhk,ntk->nhqt", _a(q_rope, quant), _a(k_rope, quant)))
    s = s * ((nope + rope) ** -0.5 * soft)
    L = x.shape[1]
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqt,nthk->nqhk", _a(p, quant), _a(v, quant))
    return x + _mm("nqhk,hkd->nqd", o, w["wo"], quant)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _dense_layer(x, w, inv, dims, scales, quant):
    x = _mla(x, w, dims, (inv,) + scales, quant)
    return x + _swiglu(_rms(x), w["ffn"], quant)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _moe_layer(x, w, prompt_lens, inv, dims, scales, top_k, cap_factor,
               quant):
    x = _mla(x, w, dims, (inv,) + scales, quant)
    h = _rms(x)
    n, L, _ = h.shape
    E = w["router"].shape[1]
    probs = jax.nn.softmax(_mm("nld,de->nle", h, w["router"], quant), -1)
    gates, experts = jax.lax.top_k(probs, top_k)          # (n, L, k)
    chosen = jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.int32), axis=2)
    gate_of = jnp.sum(jax.nn.one_hot(experts, E) * gates[..., None], axis=2)
    # prefill capacity: per expert, the first C prompt tokens routed to it
    prompt = jnp.arange(L)[None, :] < prompt_lens[:, None]
    routed = chosen * prompt[..., None]                   # (n, L, E)
    rank = jnp.cumsum(routed, axis=1) - routed
    S = prompt_lens.astype(F32)
    cap = jnp.floor(S * top_k / E * cap_factor).astype(jnp.int32) + 1
    cap = jnp.maximum(top_k, jnp.minimum(cap, prompt_lens))
    keep = jnp.where(prompt[..., None], rank < cap[:, None, None], True)
    coef = gate_of * keep                                 # (n, L, E)

    def one(j, y):
        wj = {"gate": w["gate"][j], "up": w["up"][j], "down": w["down"][j]}
        return y + coef[..., j, None] * _swiglu(h, wj, quant)
    y = jax.lax.fori_loop(0, E, one, jnp.zeros_like(x))
    if "shared" in w:
        y = y + _swiglu(h, w["shared"], quant)
    return x + y


def hidden(arch, seed: int, tokens, prompt_lens, cap_factor: float,
           quant=None):
    """Final normed hidden states (n, L, d) of the token rows, and the LM
    head (d, V), both float32. Layer by layer, so only one layer's weights
    live on the device at a time."""
    attn = arch["attn"]
    if attn.get("mla") is None or arch["act"] != "swiglu" \
            or arch["norm"] != "rmsnorm":
        raise NotImplementedError("this reference covers SwiGLU RMSNorm "
                                  "stacks with MLA attention")
    dims = _mla_dims(arch)
    inv, rot, soft = yarn(attn, dims[3])
    top_k = int(arch["moe"]["top_k"])
    with jax.default_matmul_precision("highest"):
        g = global_weights(arch, seed)
        x = _w(g["embed"], quant)[jnp.asarray(tokens)]
        lens = jnp.asarray(prompt_lens, jnp.int32)
        for i in range(arch["n_layers"]):
            w = layer_weights(arch, seed, i)
            if is_moe(arch, i):
                x = _moe_layer(x, w, lens, inv, dims, (rot, soft), top_k,
                               float(cap_factor), quant)
            else:
                x = _dense_layer(x, w, inv, dims, (rot, soft), quant)
            del w
        return _rms(x), _w(g["lm_head"], quant)
