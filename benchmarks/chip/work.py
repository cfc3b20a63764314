"""Work the algorithm needs, counted from a configuration's sizes.

These count what one token *needs*, not what today's code does: a MoE layer
needs its router and its ``top_k`` routed experts, so the MFU share stays
honest when a later change stops touching every slot.
Attention needs the context that is actually there (``ctx`` positions),
not the padded cache. A multiply-add is two operations.
"""
from __future__ import annotations


def _a(config: dict) -> dict:
    return config["arch"]


def is_moe_layer(config: dict, i: int) -> bool:
    m = _a(config)["moe"]
    return i % m["moe_layer_period"] == m["moe_layer_offset"]


def n_moe_layers(config: dict) -> int:
    return sum(is_moe_layer(config, i) for i in range(_a(config)["n_layers"]))


def ffn_mats(config: dict) -> int:
    """Weight matrices per FFN: gated activations have three, others two."""
    return 3 if _a(config)["act"] in ("swiglu", "geglu") else 2


def expert_flops(config: dict) -> float:
    """Operations of the routed experts of one MoE layer for one token."""
    a = _a(config)
    m = a["moe"]
    return 2.0 * ffn_mats(config) * a["d_model"] * m["d_expert"] * m["top_k"]


def decode_token_flops(config: dict, ctx: int) -> float:
    """Operations one decode token needs at context length ``ctx`` (the
    positions it attends to, itself included)."""
    a = _a(config)
    d, hd = a["d_model"], a["head_dim"]
    q = a["n_heads"] * hd
    kv = a["n_kv_heads"] * hd
    flops = 0.0
    for i in range(a["n_layers"]):
        flops += 2.0 * d * (2 * q + 2 * kv)          # q, k, v, o
        flops += 2.0 * 2 * q * ctx                    # scores and weighted sum
        if is_moe_layer(config, i):
            flops += 2.0 * d * a["moe"]["n_experts"]  # router
            flops += expert_flops(config)
        else:
            flops += 2.0 * ffn_mats(config) * d * a["d_ff"]
    flops += 2.0 * d * a["vocab"]                     # LM head
    return flops
