"""Operation and byte counts of the work a decode token needs."""
import json
import os

from chip import work

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_switch_base_decode_token_flops():
    c = config("switch-base-128")
    d, V, f, E = 768, 32128, 3072, 128
    attn = 2 * d * (4 * d)                   # q, k, v, o at 12 x 64 heads
    dense = 2 * 2 * d * f                    # up, down (GELU, not gated)
    moe = 2 * d * E + 2 * 2 * d * f          # router + one routed expert
    fixed = 12 * attn + 6 * dense + 6 * moe + 2 * d * V
    for ctx in (1, 100, 575):
        want = fixed + 12 * 2 * 2 * d * ctx
        assert work.decode_token_flops(c, ctx) == want
    assert work.n_moe_layers(c) == 6


def test_expert_work_counts_the_routed_expert_only():
    c = config("switch-large-128-l8")
    d, f = 1024, 4096
    assert work.expert_flops(c) == 2 * 2 * d * f
    assert work.n_moe_layers(c) == 4
    c["arch"]["moe"]["top_k"] = 2
    assert work.expert_flops(c) == 2 * 2 * 2 * d * f
