"""Grouped expert FFN kernel: (E, C, d) tokens × per-expert (d, f) weights.

TPU adaptation notes (vs a CUDA grouped-GEMM):
- Grid (E, C/bc, f/bf): one expert per leading grid dim so each program
  touches exactly one expert's weight slices — the expert dim is also the
  expert-parallel sharding axis, so under shard_map the per-device grid is
  the local expert count.
- The f dim is the contraction of the *second* GEMM (down-projection), so
  the output block is revisited across the f grid dim and accumulated in
  place (MXU-friendly: all tiles are multiples of (8, 128) for f32/bf16).
- VMEM budget per program: x (bc, d) + w_gate/w_up (d, bf) + h (bc, bf) +
  y (bc, d). With bc=128, bf=512, d≤8192, bf16: ≈ 2·8·0.5 + 2·0.13 MB ≈ 9MB
  — inside the ~16MB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, y_ref, *, act: str, bf: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[0]                       # (bc, d)
    wu = wu_ref[0]                     # (d, bf)
    up = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    if wg_ref is not None:
        wg = wg_ref[0]
        gate = jnp.dot(x, wg, preferred_element_type=jnp.float32)
        if act == "swiglu":
            h = jax.nn.silu(gate) * up
        else:                           # geglu
            h = jax.nn.gelu(gate, approximate=True) * up
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:                               # gelu
        h = jax.nn.gelu(up, approximate=True)
    wd = wd_ref[0]                      # (bf, d)
    y_ref[...] += jnp.dot(h.astype(x.dtype), wd,
                          preferred_element_type=jnp.float32
                          )[None].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block_c", "block_f",
                                             "interpret"))
def moe_ffn(xg, w_gate, w_up, w_down, *, act: str = "swiglu",
            block_c: int = 128, block_f: int = 512,
            interpret: bool = False):
    """xg: (E, C, d); w_*: (E, d, f) / w_down: (E, f, d). -> (E, C, d)."""
    E, C, d = xg.shape
    f = w_up.shape[2]
    bc = min(block_c, C)
    bf = min(block_f, f)
    assert C % bc == 0 and f % bf == 0, (C, bc, f, bf)
    grid = (E, C // bc, f // bf)

    in_specs = [
        pl.BlockSpec((1, bc, d), lambda e, i, j: (e, i, 0)),       # xg
        pl.BlockSpec((1, d, bf), lambda e, i, j: (e, 0, j)),       # w_gate
        pl.BlockSpec((1, d, bf), lambda e, i, j: (e, 0, j)),       # w_up
        pl.BlockSpec((1, bf, d), lambda e, i, j: (e, j, 0)),       # w_down
    ]
    operands = [xg, w_gate, w_up, w_down]
    kernel = functools.partial(_kernel, act=act, bf=bf)
    if w_gate is None:
        in_specs.pop(1)   # drop the w_gate spec (xg stays at index 0)
        operands.pop(1)
        kernel = functools.partial(
            lambda x_ref, wu_ref, wd_ref, y_ref, **kw:
            _kernel(x_ref, None, wu_ref, wd_ref, y_ref, **kw),
            act=act, bf=bf)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc, d), lambda e, i, j: (e, i, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), xg.dtype),
        interpret=interpret,
    )(*operands)


def _quant_kernel(refs, *, act: str, bf: int, gated: bool, scaled: bool):
    """Dequantizing variant: weight refs arrive in a narrow wire dtype
    (int8, or fp32 for the widened fp16 wire) plus optional
    per-output-channel fp32 scale refs, and are widened to fp32 *inside*
    the kernel, right before each GEMM — so the wire dtype never touches
    the math (compute accumulates fp32, like the dense kernel) and VMEM
    holds the narrow int8 blocks, not widened copies."""
    it = iter(refs)
    x_ref = next(it)
    wg_ref = next(it) if gated else None
    wu_ref, wd_ref = next(it), next(it)
    sg_ref = next(it) if (gated and scaled) else None
    su_ref = next(it) if scaled else None
    sd_ref = next(it) if scaled else None
    y_ref = next(it)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def deq(w_ref, s_ref):               # (1, a, b) wire + (1, 1, b) scales
        w = w_ref[0].astype(jnp.float32)
        return w if s_ref is None else w * s_ref[0]

    x = x_ref[0].astype(jnp.float32)        # (bc, d)
    up = jnp.dot(x, deq(wu_ref, su_ref), preferred_element_type=jnp.float32)
    if wg_ref is not None:
        gate = jnp.dot(x, deq(wg_ref, sg_ref),
                       preferred_element_type=jnp.float32)
        if act == "swiglu":
            h = jax.nn.silu(gate) * up
        else:                               # geglu
            h = jax.nn.gelu(gate, approximate=True) * up
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:                                   # gelu
        h = jax.nn.gelu(up, approximate=True)
    y_ref[...] += jnp.dot(h, deq(wd_ref, sd_ref),
                          preferred_element_type=jnp.float32
                          )[None].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block_c", "block_f",
                                             "interpret"))
def moe_ffn_quant(xg, w_gate, w_up, w_down, sg=None, su=None, sd=None, *,
                  act: str = "swiglu", block_c: int = 128,
                  block_f: int = 512, interpret: bool = False):
    """Grouped expert FFN over wire-dtype weights (DESIGN.md §7).

    ``w_*``: (E, d, f)/(E, f, d) in fp16 or int8; ``su``/``sg``: (E, f) and
    ``sd``: (E, d) fp32 per-output-channel scales (int8 only — None for
    fp16). Dequantization happens on-device inside the kernel; with fp32
    weights and no scales this *delegates* to :func:`moe_ffn`, so the fp32
    wire path is literally the dense kernel (bit-identity by construction).

    TPU layout: fp16 weights are widened to fp32 before the kernel (the
    v5e's Mosaic has no f16 vector loads; the widening is exact, so the
    result equals in-kernel widening), and the scales enter as
    (E, 1, f)/(E, 1, d) so each block's last two dims are (1, full) — a
    (1, bf) block of an (E, f) array breaks the (8, 128) tiling rule.
    """
    if su is None and w_up.dtype == xg.dtype:
        return moe_ffn(xg, w_gate, w_up, w_down, act=act, block_c=block_c,
                       block_f=block_f, interpret=interpret)

    def widen(w):
        return w if w is None or w.dtype != jnp.float16 \
            else w.astype(jnp.float32)
    w_gate, w_up, w_down = widen(w_gate), widen(w_up), widen(w_down)
    E, C, d = xg.shape
    f = w_up.shape[2]
    bc = min(block_c, C)
    bf = min(block_f, f)
    assert C % bc == 0 and f % bf == 0, (C, bc, f, bf)
    grid = (E, C // bc, f // bf)
    gated = w_gate is not None
    scaled = su is not None

    w_spec = pl.BlockSpec((1, d, bf), lambda e, i, j: (e, 0, j))
    in_specs = [pl.BlockSpec((1, bc, d), lambda e, i, j: (e, i, 0))]
    operands = [xg]
    if gated:
        in_specs.append(w_spec)
        operands.append(w_gate)
    in_specs += [w_spec, pl.BlockSpec((1, bf, d), lambda e, i, j: (e, j, 0))]
    operands += [w_up, w_down]
    if scaled:
        f_scale = pl.BlockSpec((1, 1, bf), lambda e, i, j: (e, 0, j))
        d_scale = pl.BlockSpec((1, 1, d), lambda e, i, j: (e, 0, 0))
        if gated:
            in_specs.append(f_scale)
            operands.append(sg.reshape(E, 1, f))
        in_specs += [f_scale, d_scale]
        operands += [su.reshape(E, 1, f), sd.reshape(E, 1, d)]

    kernel = functools.partial(
        lambda *refs, **kw: _quant_kernel(refs, **kw),
        act=act, bf=bf, gated=gated, scaled=scaled)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc, d), lambda e, i, j: (e, i, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), xg.dtype),
        interpret=interpret,
    )(*operands)


def _grouped_ffn_jnp(xg, w_gate, w_up, w_down, *, act: str):
    """Pure-jnp grouped expert FFN, op-for-op the same einsum contraction
    order as ``repro.models.moe.grouped_expert_ffn`` (duplicated here so the
    kernel package stays import-independent of the model package): the
    fallback expert impl for hosts where the Pallas kernel cannot run
    compiled (CPU serving), with bit-identity to the unsharded jnp path."""
    if act == "swiglu":
        act_fn = jax.nn.silu
    elif act == "geglu":
        act_fn = functools.partial(jax.nn.gelu, approximate=True)
    elif act == "relu2":
        act_fn = lambda v: jnp.square(jax.nn.relu(v))  # noqa: E731
    else:
        act_fn = functools.partial(jax.nn.gelu, approximate=True)
    up = jnp.einsum("ecd,edf->ecf", xg, w_up)
    if w_gate is not None:
        h = act_fn(jnp.einsum("ecd,edf->ecf", xg, w_gate)) * up
    else:
        h = act_fn(up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_ffn_sharded(xg, w_gate, w_up, w_down, *, mesh, axis_name="expert",
                    act: str = "swiglu", block_c: int = 128,
                    block_f: int = 512, interpret: bool = False,
                    impl: str = "pallas"):
    """Expert-parallel grouped FFN over a device mesh (DESIGN.md §8).

    ``xg``: (E, C, d) capacity-dispatched token blocks, sharded (or
    shardable) over C; ``w_*``: (E, d, f)/(E, f, d) expert weights sharded
    over the leading expert axis — exactly the sharding story the dense
    kernel's grid was designed for. Inside ``shard_map`` each device holds
    (E, C/D, d) tokens and (E/D, d, f) weights; an ``all_to_all`` over
    ``axis_name`` exchanges token sub-blocks so device ``i`` ends up with
    the *full* C rows of its own expert slice (E/D, C, d), runs the
    grouped-expert GEMM locally (``impl="pallas"`` = :func:`moe_ffn`,
    ``impl="jnp"`` = the einsum fallback), and the reverse ``all_to_all``
    restores the (E, C/D, d) layout. C is zero-padded up to a multiple of D
    (pad rows are all-zero token blocks: each token row is independent in
    the FFN, so padding never perturbs real rows).

    Per-token numerics are unchanged by the sharding: the contraction dims
    (d, and the f-blocking inside the kernel) are not partitioned, and the
    two all-to-alls are exact permutations — D=1 is bit-identical to
    :func:`moe_ffn` by construction (no pad, identity exchange, same
    kernel), and D>1 is bit-identical per token row.
    """
    from jax.sharding import PartitionSpec as P

    D = int(mesh.shape[axis_name])
    E, C, d = xg.shape
    if E % D != 0:
        raise ValueError(f"n_experts {E} must divide by the expert-parallel "
                         f"degree {D}")
    Cp = -(-C // D) * D
    if Cp != C:
        xg = jnp.pad(xg, ((0, 0), (0, Cp - C), (0, 0)))
    gated = w_gate is not None
    Cb = Cp // D

    def local(xg_l, *ws):
        wg_l, wu_l, wd_l = ws if gated else (None,) + ws
        if D > 1:
            t = xg_l.reshape(D, E // D, Cb, d)
            t = jax.lax.all_to_all(t, axis_name, split_axis=0, concat_axis=2,
                                   tiled=True)
            xg_x = t.reshape(E // D, Cp, d)
        else:
            xg_x = xg_l
        if impl == "pallas":
            y_l = moe_ffn(xg_x, wg_l, wu_l, wd_l, act=act, block_c=block_c,
                          block_f=block_f, interpret=interpret)
        else:
            y_l = _grouped_ffn_jnp(xg_x, wg_l, wu_l, wd_l, act=act)
        if D > 1:
            t = y_l.reshape(E // D, D, Cb, d)
            t = jax.lax.all_to_all(t, axis_name, split_axis=1, concat_axis=0,
                                   tiled=True)
            y_l = t.reshape(E, Cb, d)
        return y_l

    x_spec = P(None, axis_name, None)
    w_spec = P(axis_name, None, None)
    operands = (xg,) + ((w_gate,) if gated else ()) + (w_up, w_down)
    in_specs = (x_spec,) + (w_spec,) * (len(operands) - 1)
    y = jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=x_spec,
                      check_vma=False)(*operands)
    return y[:, :C] if Cp != C else y


def moe_ffn_slots(xg, slot_weights, slot_ids, *, act: str = "swiglu",
                  block_c: int = 128, block_f: int = 512,
                  interpret: bool = False):
    """Slot-indexed grouped expert FFN: the kernel entry point for the
    device-resident expert slot cache (DESIGN.md §6).

    ``slot_weights``: {w_up (n_slots, d, f), w_down (n_slots, f, d),
    w_gate? (n_slots, d, f)} — the stacked per-slot buffers; ``slot_ids``:
    (E,) int32 expert→slot table row for this layer. The gather
    materializes per-expert weight views in the same (E, d, f) layout the
    kernel's expert-major grid expects, so the grid/BlockSpec structure —
    and the expert-parallel sharding story on the leading axis — is
    unchanged from the dense path. Numerically identical to `moe_ffn` on
    the dense weights the slots were uploaded from (bit-equal gather).

    Wire-dtype buffers (DESIGN.md §7): when the slot cache streams fp16 or
    int8, ``slot_weights`` holds narrow buffers plus ``<name>_scale``
    fp32 per-output-channel scales (int8); the gather stays in the wire
    dtype (cheap) and :func:`moe_ffn_quant` dequantizes inside the grouped
    GEMM."""
    def take(name):
        return (jnp.take(slot_weights[name], slot_ids, axis=0)
                if name in slot_weights else None)
    wg, wu, wd = take("w_gate"), take("w_up"), take("w_down")
    sg, su, sd = take("w_gate_scale"), take("w_up_scale"), \
        take("w_down_scale")
    return moe_ffn_quant(xg, wg, wu, wd, sg, su, sd, act=act,
                         block_c=block_c, block_f=block_f,
                         interpret=interpret)
