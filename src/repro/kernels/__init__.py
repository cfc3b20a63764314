"""Pallas TPU kernels for the serving hot spots.

The paper is a policy paper (no GPU kernels), but MoE serving's compute hot
spots get TPU-native Pallas kernels (DESIGN.md):

- moe_ffn:      grouped expert GEMM with fused (Sw/Ge)GLU — the MoE FFN
- flash_decode: single-token flash attention over a long KV cache (GQA)
- wkv6:         RWKV6 data-dependent-decay recurrence (chunked scan)

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec VMEM tiling)
with a pure-jnp oracle in ref.py. The CPU tests check them against the
oracles with interpret=True; tests/test_tpu_compile.py compiles them for a
described TPU v5e at switch-base-128 widths. None is on the served path
yet: a caller picks the kernel or the oracle explicitly.
"""
