"""Work a decode token needs in a DeepSeek-V2-style stack: MLA attention,
leading dense layers (``first_dense_layers``), top-k routed SwiGLU experts
beside always-on shared experts. Counted from a configuration's sizes, as
``work.py`` counts the Switch stacks: what one token needs, not what
today's code does; attention over the context that is there (``ctx``
positions, itself included), not the padded cache; a multiply-add is two
operations.

MLA decode is counted in its absorbed form, the one the served decode
runs: the query's no-rope part is multiplied into the latent space
through ``w_uk``, scores and the weighted sum run over the latent cache
(``kv_lora_rank`` + ``qk_rope_head_dim`` values per position), and the
result leaves it through ``w_uv``.
"""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
MATS = 3                          # SwiGLU: gate, up, down


def _a(config: dict) -> dict:
    return config["arch"]


def is_moe_layer(config: dict, i: int) -> bool:
    m = _a(config)["moe"]
    return (i >= m.get("first_dense_layers", 0)
            and i % m.get("moe_layer_period", 1)
            == m.get("moe_layer_offset", 0))


def n_moe_layers(config: dict) -> int:
    return sum(is_moe_layer(config, i) for i in range(_a(config)["n_layers"]))


def _mla(config: dict) -> dict:
    m = dict(_a(config)["attn"]["mla"])
    m["q_lora_rank"] = m.get("q_lora_rank") or 0
    return m


def attn_weights(config: dict) -> int:
    """Weight elements of one MLA block: q (direct, or down and up),
    the latent and rotary-key projections, w_uk, w_uv and w_o."""
    a, m = _a(config), _mla(config)
    d, H = a["d_model"], a["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r = m["kv_lora_rank"]
    if m["q_lora_rank"]:
        q = d * m["q_lora_rank"] + m["q_lora_rank"] * H * qk
    else:
        q = d * H * qk
    return (q + d * (r + m["qk_rope_head_dim"])
            + r * H * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + H * m["v_head_dim"] * d)


def latent_width(config: dict) -> int:
    """Cached values per position: the latent and the rotary key."""
    m = _mla(config)
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def shared_width(config: dict) -> int:
    m = _a(config)["moe"]
    return m.get("n_shared_experts", 0) * (m.get("d_shared") or m["d_expert"])


def pre_bytes(config: dict, ctx: int) -> float:
    """Bytes one decode ``pre`` of a MoE layer must read: its norms and
    the latent norm (float32), the attention weights, the float32 router,
    and the latent cache of ``ctx`` positions."""
    a, m = _a(config), _mla(config)
    item = ITEMSIZE[a["dtype"]]
    d = a["d_model"]
    norms = 4 * (2 * d + m["kv_lora_rank"])
    router = 4 * d * a["moe"]["n_experts"]
    return (norms + router + item * attn_weights(config)
            + item * ctx * latent_width(config))


def post_bytes(config: dict) -> float:
    """Bytes one decode ``post`` of a MoE layer must read at batch one: the
    ``top_k`` routed experts and the shared experts' weights."""
    a = _a(config)
    m = a["moe"]
    return (ITEMSIZE[a["dtype"]] * MATS * a["d_model"]
            * (m["top_k"] * m["d_expert"] + shared_width(config)))


def attn_flops(config: dict, ctx: int) -> float:
    """Operations of one absorbed MLA decode over ``ctx`` positions."""
    a, m = _a(config), _mla(config)
    d, H = a["d_model"], a["n_heads"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    r, vd = m["kv_lora_rank"], m["v_head_dim"]
    if m["q_lora_rank"]:
        q = d * m["q_lora_rank"] + m["q_lora_rank"] * H * (nope + rope)
    else:
        q = d * H * (nope + rope)
    return 2.0 * (q + d * (r + rope)           # q; latent and rotary key
                  + H * nope * r               # q_nope into the latent
                  + H * ctx * (r + rope)       # scores
                  + H * ctx * r                # weighted sum of latents
                  + H * r * vd                 # out of the latent (w_uv)
                  + H * vd * d)                # w_o


def decode_token_flops(config: dict, ctx: int) -> float:
    """Operations one decode token needs at context length ``ctx``: every
    layer's attention, the dense layers' FFN, each MoE layer's router,
    ``top_k`` routed experts and shared experts, and the LM head."""
    a = _a(config)
    d, m = a["d_model"], a["moe"]
    flops = 0.0
    for i in range(a["n_layers"]):
        flops += attn_flops(config, ctx)
        if is_moe_layer(config, i):
            flops += 2.0 * d * m["n_experts"]
            flops += 2.0 * MATS * d * (m["top_k"] * m["d_expert"]
                                       + shared_width(config))
        else:
            flops += 2.0 * MATS * d * a["d_ff"]
    return flops + 2.0 * d * a["vocab"]
