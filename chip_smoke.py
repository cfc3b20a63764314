#!/usr/bin/env python3
"""Bring-up smoke test: serve switch-base-128 at its published widths on a
TPU through the normal entry point, ``repro.launch.serve``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the expert-parallel path on four chips

switch-base-128 runs unreduced: d_model 768, 12 layers, 6 MoE layers of
128 top-1 experts (d_expert 3072), vocab 32128, bfloat16, random weights
from seed 0. Everything runs in this one process, which holds the chip.

One chip, two phases with the same seed and requests:
  a  --resident-fraction 0.25  experts stream through the slot cache
  b  --resident-fraction 1.0   the fused all-resident step
Four chips, two phases:
  a  --devices 4 --resident-fraction 0.25  expert-parallel slot caches
  b  --devices 1 --resident-fraction 0.25  the run it is compared with

Each phase prints serve's own report, then set-up, compile and drain
seconds on the host clock, device memory and slot traffic. These are
bring-up observations, not benchmark metrics. The script exits non-zero,
without the ok line, when JAX finds no TPU, a request misses its token
budget, a jit entry retraces, phase a makes no demand upload, a device
holds more than its share of non-expert params, slot buffers and one
layer's expert temporaries (the full expert set was not released), or the
first generated token of any request differs between the phases. Its last
line is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "switch-base-128"
# headroom over the computed per-device bound: KV pool, token buffers,
# allocator slack
MEMORY_MARGIN = 256 << 20

_compile_s = []       # backend compile seconds; empty until listening


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


def _compile_seconds() -> float:
    """Backend compile seconds so far in this process (counted from the
    first call, through JAX's monitoring events)."""
    if not _compile_s:
        import jax
        _compile_s.append(0.0)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return _compile_s[0]


class SmokeFailure(RuntimeError):
    pass


def serve_argv(resident_fraction: float, devices: int = 1, *,
               arch: str = ARCH, reduced: bool = False, requests: int = 5,
               max_new: int = 16) -> list:
    """serve's command line for one phase (prompts of at most 64 tokens)."""
    argv = ["--arch", arch, "--requests", str(requests),
            "--prompt-len", "64", "--max-new", str(max_new), "--seed", "0",
            "--resident-fraction", str(resident_fraction),
            "--devices", str(devices)]
    return argv + ["--reduced"] if reduced else argv


def _memory(devices) -> list:
    """Per-device memory_stats (None where the backend keeps none)."""
    return [d.memory_stats() for d in devices]


def _fmt_bytes(stats, key: str) -> str:
    return "[" + " ".join("n/a" if s is None else f"{s[key] / 2**30:.3f}"
                          for s in stats) + "]GiB"


def _device_bounds(srv, devices) -> list:
    """Most bytes each device may hold once the server is built: its
    non-expert params, its slot buffers and one layer's gathered experts."""
    import jax
    rt = srv.slot_runtime
    caches = getattr(rt.slot_cache, "caches", [rt.slot_cache])
    nonexpert = sum(leaf.nbytes for leaf in jax.tree.leaves(rt.params))
    gathered = rt.store.n_experts * rt.store.wire_expert_bytes
    bounds = []
    for dev in devices:
        slots = sum(b.nbytes for c in caches for b in c.bufs.values()
                    if b.devices() == {dev})
        bounds.append((slots, nonexpert + slots + gathered + MEMORY_MARGIN))
    return bounds


def run_phase(name: str, argv: list, *, streamed: bool = False) -> dict:
    """Build, serve and report one phase through repro.launch.serve and
    check it. Returns its tokens and observations."""
    import jax
    from repro.launch import serve

    print(f"== phase {name}: serve {' '.join(argv)}", flush=True)
    args = serve.parse_args(argv)
    devices = jax.devices()[:args.devices]
    c0, t0 = _compile_seconds(), time.perf_counter()
    served = serve.build(args)
    setup_s = time.perf_counter() - t0
    setup_compile_s = _compile_seconds() - c0
    gc.collect()
    mem_built = _memory(devices)
    bounds = _device_bounds(served.srv, devices) if streamed else None
    c1 = _compile_seconds()
    serve.run(served)                  # raises RecompileError on a retrace
    drain_compile_s = _compile_seconds() - c1
    serve.report(served)
    mem_after = _memory(devices)
    stats = served.srv.stats()
    print(f"phase {name}: setup={setup_s:.3f}s "
          f"(backend compile {setup_compile_s:.3f}s) "
          f"drain={served.drain_s:.3f}s "
          f"(backend compile {drain_compile_s:.3f}s), host clock")
    print(f"phase {name}: bytes_in_use after build="
          f"{_fmt_bytes(mem_built, 'bytes_in_use')} after drain="
          f"{_fmt_bytes(mem_after, 'bytes_in_use')} peak="
          f"{_fmt_bytes(mem_after, 'peak_bytes_in_use')}")
    print(f"phase {name}: compiles={dict(served.srv.compile_counts)}")

    short = [r.rid for r in served.reqs
             if len(served.tokens[r.rid]) != r.max_new_tokens]
    if short:
        raise SmokeFailure(f"phase {name}: requests {short} did not finish "
                           "their token budget")
    if streamed:
        print(f"phase {name}: slot hits={stats['slot_hits']} "
              f"misses={stats['slot_misses']} "
              f"demand-uploads={stats['demand_uploads']} "
              f"prefetch-uploads={stats['prefetch_uploads']} "
              f"upload-bytes={stats['upload_bytes']}")
        print(f"phase {name}: per-device bound="
              + "[" + " ".join(f"{b / 2**30:.3f}" for _, b in bounds)
              + "]GiB slot buffers="
              + "[" + " ".join(f"{s / 2**30:.3f}" for s, _ in bounds)
              + "]GiB")
        if stats["demand_uploads"] == 0:
            raise SmokeFailure(f"phase {name}: no demand uploads — nothing "
                               "streamed")
        for dev, mem, (slots, bound) in zip(devices, mem_built, bounds):
            if slots == 0:
                raise SmokeFailure(f"phase {name}: {dev} holds no slot "
                                   "buffers")
            if mem is not None and mem["bytes_in_use"] > bound:
                raise SmokeFailure(
                    f"phase {name}: {dev} holds {mem['bytes_in_use']} bytes "
                    f"> bound {bound}: the full expert set is still on the "
                    "device")
    return {"name": name, "tokens": served.tokens}


def compare(a: dict, b: dict) -> None:
    """Print token agreement between two phases; fail on any first-token
    difference."""
    rids = sorted(a["tokens"])
    first = sum(a["tokens"][r][0] == b["tokens"][r][0] for r in rids)
    full = sum(a["tokens"][r] == b["tokens"][r] for r in rids)
    same = sum(x == y for r in rids
               for x, y in zip(a["tokens"][r], b["tokens"][r]))
    total = sum(len(a["tokens"][r]) for r in rids)
    print(f"agreement {a['name']} vs {b['name']}: first-token "
          f"{first}/{len(rids)} full-sequence {full}/{len(rids)} "
          f"tokens {same}/{total}")
    if first != len(rids):
        raise SmokeFailure("first generated tokens differ between phases "
                           f"{a['name']} and {b['name']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    if dev.memory_stats() is None:
        print("chip_smoke: the device reports no memory_stats",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.configs import get_config
    from repro.launch import serve
    from repro.serving.guard import RecompileError

    serve.init_compile_cache()
    cfg = get_config(ARCH)
    n_moe = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    print(f"config: {cfg.name} d_model={cfg.d_model} n_layers={cfg.n_layers} "
          f"moe_layers={n_moe} n_experts={cfg.moe.n_experts} "
          f"top_k={cfg.moe.top_k} d_expert={cfg.moe.d_expert} "
          f"vocab={cfg.vocab} dtype={cfg.dtype}", flush=True)
    try:
        if opts.chips == 4:
            a = run_phase("a", serve_argv(0.25, devices=4), streamed=True)
            gc.collect()
            b = run_phase("b", serve_argv(0.25, devices=1), streamed=True)
        else:
            a = run_phase("a", serve_argv(0.25), streamed=True)
            gc.collect()
            b = run_phase("b", serve_argv(1.0))
        compare(a, b)
    except (SmokeFailure, RecompileError) as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
