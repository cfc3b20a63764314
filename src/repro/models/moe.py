"""Routed mixture-of-experts FFN.

Two global paths, picked at trace time from shapes and arguments alone
(:func:`routed_rows`):

* **Dispatch** (prefill, training, wide batches): sort+gather based
  (GShard-style capacity bound, but without the O(T·E·C) one-hot dispatch
  tensors): tokens are flattened, their (token, expert) assignments sorted
  by expert, capacity-clipped, gathered into dense per-expert blocks
  ``(E, C, d)`` and processed by a grouped GEMM. Compute is therefore
  proportional to *active* experts (``T·k·d·f``), which keeps the
  roofline's MODEL_FLOPS / HLO_FLOPs ratio honest; the weights read are all
  ``E`` experts' (on the slot path, an ``E``-row gather of the slot
  buffers).
* **Routed rows** (decode: dropless, ``T·k < E``): each (token, k)
  assignment gathers its one expert's weight row and applies it to its
  token, so a step reads ``T·k`` expert rows instead of ``E``.

Expert weights are stored stacked ``(E, d, f)`` so that (a) expert parallelism
is one PartitionSpec on the leading axis and (b) the serving engine's offload
store can move one ``E``-slice per fetch (the paper's per-expert I/O fusion).

Per-sequence expert activation counts — the paper's EAM rows — fall out of
routing for free and are returned as aux.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ArchConfig, MoEConfig
from repro.models.layers import activation, init_ffn, apply_ffn, is_gated


# Optional PartitionSpecs for the grouped dispatch intermediates, set by the
# launcher (jit-traced model code cannot name mesh axes itself):
#   xg / yg (B, E, C, d)  — typically P(batch_axes, "model", None, None)
_DISPATCH_CONSTRAINT = None


def set_dispatch_constraint(spec) -> None:
    """Launcher hook: force the grouped-dispatch per-expert blocks to stay
    batch-sharded (GSPMD otherwise replicates the expert GEMMs across the
    data axis — the §Perf finding: 16x per-device waste on a 16x16 mesh)."""
    global _DISPATCH_CONSTRAINT
    _DISPATCH_CONSTRAINT = spec


def init_moe(rng, cfg: ArchConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(rng, 5)
    std = d ** -0.5
    p = {
        "w_router": (jax.random.normal(ks[0], (d, m.n_experts)) * std
                     ).astype(jnp.float32),
        "w_up": (jax.random.normal(ks[2], (m.n_experts, d, m.d_expert)) * std
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (m.n_experts, m.d_expert, d))
                   * m.d_expert ** -0.5).astype(dtype),
    }
    if is_gated(cfg.act):
        p["w_gate"] = (jax.random.normal(ks[1], (m.n_experts, d, m.d_expert))
                       * std).astype(dtype)
    if m.n_shared_experts:
        d_sh = (m.d_shared or m.d_expert) * m.n_shared_experts
        p["shared"] = init_ffn(ks[4], cfg, d_sh, dtype)
    return p


def capacity(T: int, m: MoEConfig, factor: float | None = None) -> int:
    f = m.capacity_factor if factor is None else factor
    c = int(T * m.top_k / m.n_experts * f) + 1
    return max(m.top_k, min(c, T))


def _traced_capacity(n_tokens, m: MoEConfig, factor: float | None):
    """``capacity`` over a *traced* token count (same formula, jnp ops).

    Padded ragged prefill keeps the static block shape C(T_padded) but must
    drop tokens exactly as an exact-length prefill would — i.e. at
    C(T_real), which is only known at run time. C is monotone in T, so the
    traced bound never exceeds the static shape. (The arithmetic runs in
    f32 rather than python f64; all assigned configs have power-of-two
    n_experts, where T·k/E·f is exact and the floor cannot flip.)"""
    f = m.capacity_factor if factor is None else factor
    c = jnp.floor(n_tokens * m.top_k / m.n_experts * f).astype(jnp.int32) + 1
    return jnp.maximum(m.top_k, jnp.minimum(c, n_tokens))


def route(p, m: MoEConfig, xf):
    """xf (T, d) -> (gates (T,k), idx (T,k), probs (T,E))."""
    logits = (xf.astype(jnp.float32) @ p["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.router_norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
    return gates, idx, probs


def _take(a, rows):
    return jnp.take(a, rows, axis=0)


def _take_rows(a, rows):
    """``a[rows]`` for a short (static-length) index vector, as one dynamic
    slice of the leading axis per row. XLA's TPU gather of a few rows first
    slices the *whole* operand along its minor axis (its mini-gather), which
    on a slot buffer copies every slot to read one."""
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(a, rows[i], 1)
                            for i in range(rows.shape[0])])


def gather_slot_weights(p, slot_weights, slot_ids, take=_take):
    """Substitute slot-cache expert weights into a MoE param dict.

    ``slot_weights``: stacked per-slot triples {w_up (n_slots, d, f),
    w_down (n_slots, f, d), w_gate? (n_slots, d, f)} — the device-resident
    expert slot buffers. ``slot_ids``: the slots to take, in order — the
    layer's whole (E,) expert→slot table row on the dispatch path (and for
    the sharded runtime's per-device gathers), or the (T·k,) slots of the
    routed assignments on the routed-row path. Non-resident experts must be
    clamped to a valid slot by the caller; their weights are garbage but
    harmless — an expert no real token routes to contributes nothing to the
    output (zero gate / empty capacity block), so only *activated* experts
    need live slots.

    Wire dtypes (DESIGN.md §7): when the slot cache streams fp16/int8, the
    buffers are narrow and int8 ships with ``<name>_scale`` fp32
    per-output-channel rows. The gather stays in the wire dtype and
    dequantization happens here, in-jit on device, on the gathered rows
    only — ``q.astype(f32) * scale`` broadcast over the input axis — so
    compute downstream is fp32 regardless of the wire. The fp32 wire path
    takes the exact PR-5 gather (no cast, no scale): bit-identity
    preserved. ``take(buffer, slot_ids)`` reads the rows: a gather, or
    :func:`_take_rows` on the routed-row path."""
    p = dict(p)
    for name in ("w_gate", "w_up", "w_down"):
        if name in slot_weights:
            w = take(slot_weights[name], slot_ids)
            sname = name + "_scale"
            if sname in slot_weights:
                s = take(slot_weights[sname], slot_ids)
                w = w.astype(jnp.float32) * s[:, None, :]
            elif w.dtype == jnp.float16:
                w = w.astype(jnp.float32)
            p[name] = w
        else:
            p.pop(name, None)
    return p


def moe_ffn(p, cfg: ArchConfig, x, *, capacity_factor: float | None = None,
            expert_fn=None, token_mask=None, routing=None,
            slot_weights=None, slot_ids=None):
    """Apply the routed MoE to x (B, S, d).

    Returns (y, aux) where aux = {"counts": (B, E) int32 per-sequence expert
    activation counts (an EAM row), "aux_loss": load-balance loss scalar}.
    ``expert_fn``: optional override for the grouped expert computation with
    signature (xg (E,C,d), p) -> (E,C,d) — the Pallas kernel hook.
    ``token_mask``: optional (B, S) bool validity mask (slot-pool padded
    prefill): masked-out tokens are routed nowhere — they consume no expert
    capacity (so they cannot displace real tokens) and contribute nothing to
    ``counts`` (so pad tokens never reach the EAM or the offload engine).
    ``routing``: optional precomputed (gates (T,k), idx (T,k)) from
    :func:`route` over the flattened tokens — the slot-cache runtime routes
    in a separate jitted call so the host can upload missing experts before
    the expert GEMM runs; aux_loss is 0 on this path (serving never uses it).
    ``slot_weights``/``slot_ids``: expert weights live in the device slot
    cache instead of ``p`` (see :func:`gather_slot_weights`).

    When :func:`routed_rows` holds, the routed-row path runs instead of the
    capacity dispatch (:func:`_moe_ffn_routed`): same math, nothing
    dropped, ``T·k`` expert rows read instead of ``E``.
    """
    B, S, d = x.shape
    if routed_rows(cfg, B, S, capacity_factor,
                   masked=token_mask is not None,
                   custom_experts=expert_fn is not None):
        return _moe_ffn_routed(p, cfg, x, routing, slot_weights, slot_ids)
    if slot_weights is not None:
        p = gather_slot_weights(p, slot_weights, slot_ids)
    if cfg.moe_dispatch == "grouped" and B > 1:
        return moe_ffn_grouped(p, cfg, x, capacity_factor=capacity_factor,
                               expert_fn=expert_fn, token_mask=token_mask,
                               routing=routing)
    m = cfg.moe
    T = B * S
    xf = x.reshape(T, d)
    if routing is None:
        gates, idx, probs = route(p, m, xf)                 # (T,k) (T,k) (T,E)
    else:
        gates, idx = routing
        probs = None
    C = capacity(T, m, capacity_factor)
    E, k = m.n_experts, m.top_k

    flat_e = idx.reshape(T * k)
    C_drop = C
    if token_mask is not None:
        # pad tokens route to sentinel expert E: they sort past every real
        # segment, so they never occupy a capacity slot ahead of real tokens
        flat_e = jnp.where(jnp.repeat(token_mask.reshape(T), k), flat_e, E)
        # drop exactly as an exact-length prefill would: capacity over the
        # *real* token count (traced; <= the static shape bound C)
        C_drop = _traced_capacity(token_mask.sum(), m, capacity_factor)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]                                 # (T*k,)
    token_of = order // k
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(T * k) - seg_start[jnp.minimum(sorted_e, E - 1)]
    keep = (pos_in_e < C_drop) & (sorted_e < E)
    slot = jnp.minimum(sorted_e, E - 1) * C + jnp.minimum(pos_in_e, C - 1)

    # token index feeding each (E*C) slot; T = "no token" sentinel.
    # Dropped (over-capacity) entries scatter to index E*C, discarded by
    # mode="drop" — they must not clobber a real slot.
    slot_idx = jnp.where(keep, slot, E * C)
    slot_token = jnp.full((E * C,), T, jnp.int32)
    slot_token = slot_token.at[slot_idx].set(token_of.astype(jnp.int32),
                                             mode="drop")
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xg = x_pad[slot_token].reshape(E, C, d)

    if expert_fn is not None:
        yg = expert_fn(xg, p)
    else:
        yg = grouped_expert_ffn(xg, p, cfg.act)
    yg = yg.reshape(E * C, d)

    gate_flat = gates.reshape(T * k)[order]
    slot_gate = jnp.zeros((E * C,), gates.dtype).at[slot_idx].set(
        gate_flat, mode="drop")
    contrib = yg * slot_gate[:, None].astype(yg.dtype)
    y = jax.ops.segment_sum(contrib, slot_token, num_segments=T + 1)[:T]
    y = y.reshape(B, S, d).astype(x.dtype)

    if m.n_shared_experts:
        y = y + apply_ffn(p["shared"], x, cfg.act)

    return y, _routing_aux(m, idx, probs, B, S, token_mask)


def _routing_aux(m: MoEConfig, idx, probs, B: int, S: int, token_mask=None):
    """Per-sequence expert counts (B, E) — the EAM rows, pad tokens left
    out — and the load-balance loss (0 when routing came precomputed)."""
    E, k = m.n_experts, m.top_k
    one_hot = jax.nn.one_hot(idx.reshape(B, S * k), E, dtype=jnp.int32)
    if token_mask is not None:
        one_hot = one_hot * jnp.repeat(token_mask.astype(jnp.int32), k,
                                       axis=1)[..., None]
    counts = one_hot.sum(axis=1)                             # (B, E)
    if probs is None:
        aux_loss = jnp.float32(0)
    else:
        frac_tokens = counts.sum(axis=0).astype(jnp.float32) / (B * S * k)
        frac_probs = probs.mean(axis=0)
        aux_loss = m.aux_loss_coef * E * jnp.sum(frac_tokens * frac_probs)
    return {"counts": counts, "aux_loss": aux_loss}


def routed_rows(cfg: ArchConfig, B: int, S: int,
                capacity_factor: float | None = None, *,
                masked: bool = False, custom_experts: bool = False) -> bool:
    """Whether :func:`moe_ffn` over x (B, S, d) takes the routed-row path.

    Static (shapes and arguments only), so the path is fixed at trace time.
    It needs: no ``expert_fn`` (``custom_experts``) and no ``token_mask``
    (``masked``); a dropless capacity, C >= T (a token's k experts are
    distinct, so no expert can then overflow and the dispatch would drop
    nothing); and T·k < E, below which the routed rows read less than the
    whole expert table. The grouped dispatch (B > 1) keeps its own path."""
    m = cfg.moe
    T = B * S
    if cfg.moe_dispatch == "grouped" and B > 1:
        return False
    return (not masked and not custom_experts
            and capacity(T, m, capacity_factor) >= T
            and T * m.top_k < m.n_experts)


def expert_rows(cfg: ArchConfig, B: int, S: int,
                capacity_factor: float | None = None, *,
                masked: bool = False, custom_experts: bool = False) -> int:
    """Expert weight rows one :func:`moe_ffn` call over x (B, S, d) reads:
    T·k on the routed-row path, E where it dispatches over the whole
    table."""
    if routed_rows(cfg, B, S, capacity_factor, masked=masked,
                   custom_experts=custom_experts):
        return B * S * cfg.moe.top_k
    return cfg.moe.n_experts


def _moe_ffn_routed(p, cfg: ArchConfig, x, routing, slot_weights, slot_ids):
    """Routed-row MoE: each (token, k) assignment gathers its one expert's
    weight row — ``idx`` into ``p``'s (E, …) stacks, or ``slot_ids[idx]``
    into the slot buffers, dequantizing only those rows — and applies it to
    its token: ``y_t = Σ_k gate_{t,k} · FFN_{idx_{t,k}}(x_t)``. The FFN is
    :func:`grouped_expert_ffn` over (T·k, 1, d) blocks, so contraction order
    and dtypes are the dispatch path's."""
    m = cfg.moe
    B, S, d = x.shape
    T, k = B * S, m.top_k
    xf = x.reshape(T, d)
    if routing is None:
        gates, idx, probs = route(p, m, xf)
    else:
        (gates, idx), probs = routing, None
    flat_e = idx.reshape(T * k)
    if slot_weights is not None:
        w = gather_slot_weights({}, slot_weights, slot_ids[flat_e],
                                take=_take_rows)
    else:
        w = {name: _take_rows(p[name], flat_e)
             for name in ("w_gate", "w_up", "w_down") if name in p}
    xg = jnp.repeat(xf, k, axis=0)[:, None]                  # (T·k, 1, d)
    yg = grouped_expert_ffn(xg, w, cfg.act)[:, 0]            # (T·k, d)
    contrib = yg * gates.reshape(T * k)[:, None].astype(yg.dtype)
    y = contrib.reshape(T, k, d).sum(axis=1)
    y = y.reshape(B, S, d).astype(x.dtype)
    if m.n_shared_experts:
        y = y + apply_ffn(p["shared"], x, cfg.act)
    return y, _routing_aux(m, idx, probs, B, S)


def moe_ffn_grouped(p, cfg: ArchConfig, x, *,
                    capacity_factor: float | None = None, expert_fn=None,
                    token_mask=None, routing=None):
    """Per-sequence-group dispatch (GShard grouping, G = batch).

    The group dim stays sharded on the batch/data mesh axes end-to-end, so
    each data shard dispatches only its own tokens: per-device expert
    compute is E_local × G_local × C_g instead of E_local × C_global — the
    §Perf fix for the data-replicated expert compute of the global dispatch
    (16× per-device dot-flops reduction on the 16×16 mesh).

    Capacity is per group (C_g = S·k/E·f): slightly higher drop variance
    than the global bound at equal factor — the classic GShard trade.
    """
    m = cfg.moe
    B, S, d = x.shape
    E, k = m.n_experts, m.top_k
    if routing is None:
        gates, idx, probs = route(p, m, x.reshape(B * S, d))
    else:
        (gates, idx), probs = routing, None
    gates = gates.reshape(B, S, k)
    idx = idx.reshape(B, S, k)
    C = capacity(S, m, capacity_factor)

    flat_e = idx.reshape(B, S * k)
    C_drop = C
    if token_mask is not None:
        # sentinel expert E: pads sort last, take no capacity; drops use the
        # per-row real token count's capacity (see moe_ffn)
        flat_e = jnp.where(jnp.repeat(token_mask, k, axis=1), flat_e, E)
        C_drop = _traced_capacity(token_mask.sum(axis=1), m,
                                  capacity_factor)[:, None]
    order = jnp.argsort(flat_e, axis=-1, stable=True)           # (B, S·k)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    token_of = order // k                                        # (B, S·k)
    seg_start = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(E), side="left"))(sorted_e)
    pos_in_e = jnp.arange(S * k) - jnp.take_along_axis(
        seg_start, jnp.minimum(sorted_e, E - 1), axis=-1)
    keep = (pos_in_e < C_drop) & (sorted_e < E)
    slot = jnp.minimum(sorted_e, E - 1) * C + jnp.minimum(pos_in_e, C - 1)
    slot_idx = jnp.where(keep, slot, E * C)                     # OOB = drop

    def scatter_tokens(slot_idx_b, token_of_b):
        st = jnp.full((E * C,), S, jnp.int32)
        return st.at[slot_idx_b].set(token_of_b.astype(jnp.int32),
                                     mode="drop")
    slot_token = jax.vmap(scatter_tokens)(slot_idx, token_of)   # (B, E·C)
    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, d), x.dtype)], axis=1)
    xg = jnp.take_along_axis(
        x_pad, slot_token[..., None], axis=1).reshape(B, E, C, d)
    if _DISPATCH_CONSTRAINT is not None:
        xg = jax.lax.with_sharding_constraint(xg, _DISPATCH_CONSTRAINT)

    if expert_fn is not None:
        yg = jax.vmap(lambda g: expert_fn(g, p))(xg)
    else:
        act = activation(cfg.act)
        up = jnp.einsum("becd,edf->becf", xg, p["w_up"])
        if "w_gate" in p:
            h = act(jnp.einsum("becd,edf->becf", xg, p["w_gate"])) * up
        else:
            h = act(up)
        yg = jnp.einsum("becf,efd->becd", h, p["w_down"])
    yg = yg.reshape(B, E * C, d)

    gate_flat = jnp.take_along_axis(gates.reshape(B, S * k), order, axis=-1)
    slot_gate = jax.vmap(
        lambda si, gf: jnp.zeros((E * C,), gates.dtype).at[si].set(
            gf, mode="drop"))(slot_idx, gate_flat)
    contrib = yg * slot_gate[..., None].astype(yg.dtype)
    y = jax.vmap(lambda c, st: jax.ops.segment_sum(
        c, st, num_segments=S + 1)[:S])(contrib, slot_token)
    y = y.astype(x.dtype)

    if m.n_shared_experts:
        y = y + apply_ffn(p["shared"], x, cfg.act)

    return y, _routing_aux(m, idx, probs, B, S, token_mask)


def grouped_expert_ffn(xg, p, act_name: str):
    """(E, C, d) -> (E, C, d) grouped GEMM expert FFN (pure-jnp path)."""
    act = activation(act_name)
    up = jnp.einsum("ecd,edf->ecf", xg, p["w_up"])
    if "w_gate" in p:
        h = act(jnp.einsum("ecd,edf->ecf", xg, p["w_gate"])) * up
    else:
        h = act(up)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def moe_ffn_dense_oracle(p, cfg: ArchConfig, x):
    """O(T·E) dense-mask reference used by tests (computes every expert on
    every token, then masks). Numerically identical modulo capacity drops."""
    m = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    gates, idx, _ = route(p, m, xf)
    act = activation(cfg.act)
    up = jnp.einsum("td,edf->tef", xf, p["w_up"])
    if "w_gate" in p:
        h = act(jnp.einsum("td,edf->tef", xf, p["w_gate"])) * up
    else:
        h = act(up)
    ye = jnp.einsum("tef,efd->ted", h, p["w_down"])          # (T,E,d)
    w = jnp.zeros((B * S, m.n_experts), ye.dtype)
    for j in range(m.top_k):
        w = w.at[jnp.arange(B * S), idx[:, j]].add(gates[:, j].astype(ye.dtype))
    y = jnp.einsum("ted,te->td", ye, w).reshape(B, S, d)
    if m.n_shared_experts:
        y = y + apply_ffn(p["shared"], x, cfg.act)
    return y.astype(x.dtype)
