"""Unified composable model covering all assigned architecture families.

A model is a stack of layers described by :class:`LayerDesc`. The stack is
split into an (optional) irregular *prefix* plus a periodic tail; the tail is
executed as a ``lax.scan`` over *super-blocks* (one period of layers) with all
parameters stacked on a leading group axis. This keeps the HLO size O(period)
instead of O(n_layers) — required to compile 94-layer models on this host —
and gives the launcher a single leading axis to shard expert/layer params on.

Entry points
  init(rng)                          -> params
  forward(params, batch)             -> (logits, aux)        # train / eval
  init_cache(B, cache_len)           -> cache (zeros)        # decode state
  prefill(params, batch, cache)      -> (last_logits, cache)
  serve_step(params, cache, token)   -> (logits, cache)      # one token
  loss(params, batch)                -> scalar (LM + MoE aux)

``aux["counts"]`` carries per-sequence expert-activation counts for every MoE
layer — the rows of the paper's Expert Activation Matrix — so the serving
engine's tracer gets EAMs directly from the forward pass.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ArchConfig, BLOCK_ATTN, BLOCK_MAMBA, BLOCK_RWKV
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import rwkv6 as rwkv_lib
from repro.models.layers import (apply_ffn, apply_norm, embed_lookup,
                                 init_ffn, init_norm, softcap)
from repro.models.moe import init_moe, moe_ffn


@dataclass(frozen=True)
class LayerDesc:
    kind: str          # attn | mamba | rwkv
    is_moe: bool
    window: int        # sliding window for this layer (0 = full)


def layer_descs(cfg: ArchConfig):
    out = []
    for i in range(cfg.n_layers):
        kind = cfg.block_kind(i)
        win = cfg.attn.sliding_window if cfg.is_local_attn_layer(i) else 0
        out.append(LayerDesc(kind, cfg.is_moe_layer(i), win))
    return out


def split_periodic(descs):
    """-> (n_prefix, period): tail [n_prefix:] is periodic with ``period``.

    Chooses the split with the MOST scan groups (a period equal to the whole
    tail is a degenerate "1 group" match that would unroll every layer into
    one scan body — a 60-layer DeepSeek body made XLA compile for 30+ min).
    Ties prefer the shortest prefix. Models with no periodic tail of ≥2
    groups run prefix-only (no scan)."""
    n = len(descs)
    best = (n, 1)
    best_groups = 1 if n else 0
    for prefix in range(0, n):
        m = n - prefix
        for period in range(1, m):
            if m % period:
                continue
            if all(descs[prefix + i] == descs[prefix + i % period]
                   for i in range(m)):
                groups = m // period
                if groups > best_groups:
                    best, best_groups = (prefix, period), groups
                break  # smallest period at this prefix is its best
    if best == (n, 1) and n:
        # no real periodicity: treat everything as prefix (unrolled)
        return n, 1
    return best


# ---------------------------------------------------------------------------


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.descs = layer_descs(cfg)
        self.n_prefix, self.period = split_periodic(self.descs)
        self.n_groups = (cfg.n_layers - self.n_prefix) // self.period
        self.dtype = jnp.dtype(cfg.dtype)
        # global MoE layer order (layer idx) for EAM bookkeeping
        self.moe_layers = [i for i, d in enumerate(self.descs) if d.is_moe]

    # -- init --------------------------------------------------------------
    def _init_block(self, rng, desc: LayerDesc):
        cfg = self.cfg
        ks = jax.random.split(rng, 4)
        p = {"norm1": init_norm(cfg, cfg.d_model)}
        if desc.kind == BLOCK_ATTN:
            p["attn"] = attn_lib.init_attn(ks[0], cfg, self.dtype)
        elif desc.kind == BLOCK_MAMBA:
            p["mamba"] = mamba_lib.init_mamba(ks[0], cfg, self.dtype)
        elif desc.kind == BLOCK_RWKV:
            p["rwkv"] = rwkv_lib.init_rwkv(ks[0], cfg, self.dtype)
        if desc.kind != BLOCK_RWKV:
            p["norm2"] = init_norm(cfg, cfg.d_model)
            if desc.is_moe:
                p["moe"] = init_moe(ks[1], cfg, self.dtype)
            else:
                p["ffn"] = init_ffn(ks[1], cfg, cfg.d_ff, self.dtype)
        else:
            p["norm2"] = init_norm(cfg, cfg.d_model)
        if cfg.post_block_norm:
            p["post_norm1"] = init_norm(cfg, cfg.d_model)
            p["post_norm2"] = init_norm(cfg, cfg.d_model)
        if cfg.is_encoder_decoder and desc.kind == BLOCK_ATTN:
            p["cross_attn"] = attn_lib.init_attn(ks[2], cfg, self.dtype)
            p["norm_cross"] = init_norm(cfg, cfg.d_model)
        return p

    def init(self, rng):
        cfg = self.cfg
        ks = jax.random.split(rng, 8 + cfg.n_layers)
        std = cfg.d_model ** -0.5
        params = {
            "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model))
                      * std).astype(self.dtype),
            "final_norm": init_norm(cfg, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(
                ks[1], (cfg.d_model, cfg.vocab)) * std).astype(self.dtype)
        if not cfg.attn.use_rope:
            params["pos_embed"] = (jax.random.normal(
                ks[2], (cfg.max_seq_len, cfg.d_model)) * std).astype(self.dtype)
        params["prefix"] = [
            self._init_block(ks[8 + i], self.descs[i])
            for i in range(self.n_prefix)]
        # periodic tail: stack params per position within the period
        blocks = []
        if self.n_groups:
            for pos in range(self.period):
                desc = self.descs[self.n_prefix + pos]
                per_group = [
                    self._init_block(
                        ks[8 + self.n_prefix + g * self.period + pos], desc)
                    for g in range(self.n_groups)]
                blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs),
                                           *per_group))
        params["blocks"] = blocks
        if cfg.is_encoder_decoder:
            enc_desc = LayerDesc(BLOCK_ATTN, False, 0)
            enc_blocks = [self._init_block(jax.random.fold_in(ks[3], g), enc_desc)
                          for g in range(cfg.n_encoder_layers)]
            # encoder blocks never need cross-attn
            for b in enc_blocks:
                b.pop("cross_attn", None)
                b.pop("norm_cross", None)
            params["encoder"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                             *enc_blocks)
            params["enc_pos_embed"] = (jax.random.normal(
                ks[4], (cfg.encoder_seq_len, cfg.d_model)) * std
                ).astype(self.dtype)
            params["enc_final_norm"] = init_norm(cfg, cfg.d_model)
        return params

    def init_shapes(self):
        """Parameter ShapeDtypeStructs without allocating (for the dry-run)."""
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # -- full-sequence block application ------------------------------------
    # Blocks are split into a *pre* half (mixer: attn/mamba/rwkv time-mix,
    # cross-attn, norm2 → h2) and a *post* half (FFN: rwkv channel-mix,
    # routed MoE, or dense FFN → residual). The fused paths compose the two;
    # the expert-slot-cache runtime jits them separately so the host can see
    # the router's expert choices (computed from h2) and upload missing
    # expert weights *before* the expert GEMM consumes them (DESIGN.md §6).
    def _apply_block_pre(self, p, desc: LayerDesc, x, positions, *,
                         enc_kv=None):
        """Mixer half. Returns (x_mid, h2, aux) — aux carries the mixer
        state (kv/mamba_state/rwkv_state/rwkv_tm) prefill seeding needs."""
        cfg = self.cfg
        aux = {}
        h = apply_norm(p["norm1"], x)
        if desc.kind == BLOCK_ATTN:
            y, kv = attn_lib.attn_forward(p["attn"], cfg, h, positions,
                                          window=desc.window) \
                if cfg.attn.mla is None else attn_lib.mla_forward(
                    p["attn"], cfg, h, positions)
            aux["kv"] = kv
        elif desc.kind == BLOCK_MAMBA:
            y, state = mamba_lib.mamba_forward(p["mamba"], cfg, h)
            aux["mamba_state"] = state
        else:  # rwkv
            y, (state, last_tm) = rwkv_lib.rwkv_time_mix(p["rwkv"], cfg, h)
            aux["rwkv_state"], aux["rwkv_tm"] = state, last_tm
        if cfg.post_block_norm:
            y = apply_norm(p["post_norm1"], y)
        x = x + y
        if enc_kv is not None and "cross_attn" in p:
            hc = apply_norm(p["norm_cross"], x)
            yc, _ = attn_lib.attn_forward(p["cross_attn"], cfg, hc, positions,
                                          kv=enc_kv)
            x = x + yc
        h2 = apply_norm(p["norm2"], x)
        return x, h2, aux

    def _apply_block_post(self, p, desc: LayerDesc, x_mid, h2, *,
                          capacity_factor=None, expert_fn=None,
                          token_mask=None, routing=None, slot_weights=None,
                          slot_ids=None):
        """FFN half. Returns (x_out, aux) — aux carries counts/aux_loss
        (MoE) or rwkv_cm (rwkv channel-mix shift state)."""
        cfg = self.cfg
        aux = {}
        if desc.kind == BLOCK_RWKV:
            y2, last_cm = rwkv_lib.rwkv_channel_mix(p["rwkv"], cfg, h2)
            aux["rwkv_cm"] = h2[:, -1]
            del last_cm
        elif desc.is_moe:
            y2, moe_aux = moe_ffn(p["moe"], cfg, h2,
                                  capacity_factor=capacity_factor,
                                  expert_fn=expert_fn, token_mask=token_mask,
                                  routing=routing, slot_weights=slot_weights,
                                  slot_ids=slot_ids)
            aux["counts"] = moe_aux["counts"]
            aux["aux_loss"] = moe_aux["aux_loss"]
        else:
            y2 = apply_ffn(p["ffn"], h2, cfg.act)
        if cfg.post_block_norm:
            y2 = apply_norm(p["post_norm2"], y2)
        return x_mid + y2, aux

    def _apply_block(self, p, desc: LayerDesc, x, positions, *,
                     enc_kv=None, capacity_factor=None, expert_fn=None,
                     token_mask=None):
        x_mid, h2, aux = self._apply_block_pre(p, desc, x, positions,
                                               enc_kv=enc_kv)
        x_out, aux_ffn = self._apply_block_post(
            p, desc, x_mid, h2, capacity_factor=capacity_factor,
            expert_fn=expert_fn, token_mask=token_mask)
        aux.update(aux_ffn)
        return x_out, aux

    def _embed(self, params, batch):
        cfg = self.cfg
        if "embeds" in batch:
            x = batch["embeds"].astype(self.dtype)
        else:
            x = embed_lookup(params["embed"], batch["tokens"])
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, self.dtype)
        B, S = x.shape[:2]
        if "positions" in batch:
            positions = batch["positions"]
        else:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            if cfg.attn.mrope_sections:
                positions = jnp.broadcast_to(positions, (3, B, S))
        if not cfg.attn.use_rope:
            pos1d = positions if positions.ndim == 2 else positions[0]
            x = x + params["pos_embed"][pos1d]
        return x, positions

    def _encode(self, params, enc_embeds):
        """Whisper-style bidirectional encoder over stub frame embeddings."""
        cfg = self.cfg
        x = enc_embeds.astype(self.dtype) + params["enc_pos_embed"][None]
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        mask = jnp.zeros((1, 1, S, S), jnp.float32)
        desc = LayerDesc(BLOCK_ATTN, False, 0)

        def body(h, p):
            hn = apply_norm(p["norm1"], h)
            y, _ = attn_lib.attn_forward(p["attn"], cfg, hn, positions,
                                         mask=mask)
            h = h + y
            h2 = apply_norm(p["norm2"], h)
            return h + apply_ffn(p["ffn"], h2, cfg.act), None
        x, _ = jax.lax.scan(body, x, params["encoder"])
        del desc
        return apply_norm(params["enc_final_norm"], x)

    # -- public: forward ----------------------------------------------------
    def forward(self, params, batch, *, capacity_factor=None, remat=False,
                expert_fn=None):
        """Full-sequence forward. Returns (logits (B,S,V), aux) with
        aux = {"counts": (n_moe_layers, B, E) or None, "aux_loss": scalar}."""
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        enc_kv = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["enc_embeds"])
            # cross K/V differ per decoder layer; computed inside blocks
            enc_kv = enc_out

        counts, aux_losses = [], []

        def run_block(p, desc, h):
            ekv = None
            if enc_kv is not None:
                ekv = attn_lib.cross_kv(p["cross_attn"], cfg, enc_kv)
            return self._apply_block(p, desc, h, positions, enc_kv=ekv,
                                     capacity_factor=capacity_factor,
                                     expert_fn=expert_fn)

        for i in range(self.n_prefix):
            x, aux = run_block(params["prefix"][i], self.descs[i], x)
            if "counts" in aux:
                counts.append(aux["counts"][None])
                aux_losses.append(aux["aux_loss"])

        if self.n_groups:
            descs = [self.descs[self.n_prefix + p] for p in range(self.period)]

            def group_body(h, block_params):
                g_counts, g_loss = [], jnp.float32(0)
                for pos in range(self.period):
                    h, aux = run_block(block_params[pos], descs[pos], h)
                    if "counts" in aux:
                        g_counts.append(aux["counts"])
                        g_loss = g_loss + aux["aux_loss"]
                out = (jnp.stack(g_counts) if g_counts
                       else jnp.zeros((0,), jnp.int32))
                return h, (out, g_loss)

            if remat:
                policy = None
                if cfg.remat_policy == "dots":
                    policy = (jax.checkpoint_policies
                              .dots_with_no_batch_dims_saveable)
                group_body = jax.checkpoint(group_body, policy=policy)
            x, (scan_counts, scan_losses) = jax.lax.scan(
                group_body, x, tuple(params["blocks"]))
            if scan_counts.ndim > 2:
                # (G, n_moe_in_period, B, E) -> (G * n_moe_in_period, B, E)
                counts.append(scan_counts.reshape(
                    -1, *scan_counts.shape[2:]))
                aux_losses.append(jnp.sum(scan_losses))

        x = apply_norm(params["final_norm"], x)
        logits = self._logits(params, x)
        aux = {
            "counts": (jnp.concatenate(counts, axis=0) if counts else None),
            "aux_loss": (jnp.sum(jnp.stack(aux_losses)) if aux_losses
                         else jnp.float32(0)),
        }
        return logits, aux

    def _logits(self, params, x):
        cfg = self.cfg
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
        return softcap(logits, cfg.final_logit_softcap)

    def loss(self, params, batch, *, capacity_factor=None, remat=True):
        """Next-token LM loss + MoE load-balance aux."""
        logits, aux = self.forward(params, batch,
                                   capacity_factor=capacity_factor,
                                   remat=remat)
        if "labels" in batch:
            labels, lg = batch["labels"], logits
        else:
            labels, lg = batch["tokens"][:, 1:], logits[:, :-1]
        lp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
        return nll.mean() + aux["aux_loss"]

    # -- caches --------------------------------------------------------------
    def _block_cache(self, desc: LayerDesc, B: int, cache_len: int,
                     decode_window: int):
        cfg = self.cfg
        win = desc.window or decode_window
        L = min(cache_len, win) if win else cache_len
        if desc.kind == BLOCK_ATTN:
            if cfg.attn.mla is not None:
                m = cfg.attn.mla
                return {"ckv": jnp.zeros((B, L, m.kv_lora_rank), self.dtype),
                        "kr": jnp.zeros((B, L, m.qk_rope_head_dim), self.dtype)}
            hd = cfg.head_dim_
            c = {"k": jnp.zeros((B, L, cfg.n_kv_heads, hd), self.dtype),
                 "v": jnp.zeros((B, L, cfg.n_kv_heads, hd), self.dtype)}
            if cfg.is_encoder_decoder:
                Se = cfg.encoder_seq_len
                c["cross_k"] = jnp.zeros((B, Se, cfg.n_kv_heads, hd), self.dtype)
                c["cross_v"] = jnp.zeros((B, Se, cfg.n_kv_heads, hd), self.dtype)
            return c
        if desc.kind == BLOCK_MAMBA:
            d_in, _ = mamba_lib._dims(cfg)
            return {"conv": jnp.zeros((B, cfg.mamba.d_conv - 1, d_in), self.dtype),
                    "ssm": jnp.zeros((B, d_in, cfg.mamba.d_state), jnp.float32)}
        # rwkv
        H = cfg.d_model // cfg.rwkv.head_dim
        hd = cfg.rwkv.head_dim
        return {"state": jnp.zeros((B, H, hd, hd), jnp.float32),
                "tm": jnp.zeros((B, cfg.d_model), self.dtype),
                "cm": jnp.zeros((B, cfg.d_model), self.dtype)}

    def init_cache(self, B: int, cache_len: int, decode_window: int = 0):
        """Zeroed decode cache. ``decode_window``: cap attention caches to a
        ring buffer of this many tokens (the long_500k windowed variant).

        ``pos`` is a per-slot (B,) vector: under the slot-pool serving
        engine every batch row is an independent sequence at its own
        position; lockstep callers simply keep all rows equal."""
        cache = {
            "pos": jnp.zeros((B,), jnp.int32),
            "prefix": [self._block_cache(self.descs[i], B, cache_len,
                                         decode_window)
                       for i in range(self.n_prefix)],
            "blocks": [],
        }
        for pos in range(self.period if self.n_groups else 0):
            desc = self.descs[self.n_prefix + pos]
            one = self._block_cache(desc, B, cache_len, decode_window)
            cache["blocks"].append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (self.n_groups,) + a.shape),
                one))
        # NOTE: decode_window is NOT stored in the pytree (it must stay a
        # static python int under jit) — pass it to serve_step explicitly.
        return cache

    def write_slot(self, pool, one, slot):
        """Write a B=1 cache ``one`` into row ``slot`` of a pooled cache
        (same ``cache_len``). This is slot-pool admission: a joining
        request's per-request prefill lands in a free slot while the other
        slots' state is untouched. ``slot`` may be a traced int32 scalar, so
        one jitted prefill-and-place compiles per prompt bucket, not per
        slot index."""
        out = {"pos": pool["pos"].at[slot].set(one["pos"][0])}
        out["prefix"] = [
            jax.tree.map(
                lambda pb, ob: jax.lax.dynamic_update_slice_in_dim(
                    pb, ob.astype(pb.dtype), slot, 0), pb_i, ob_i)
            for pb_i, ob_i in zip(pool["prefix"], one["prefix"])]
        # block leaves carry the scan-group axis first: batch is axis 1
        out["blocks"] = [
            jax.tree.map(
                lambda pb, ob: jax.lax.dynamic_update_slice_in_dim(
                    pb, ob.astype(pb.dtype), slot, 1), pb_j, ob_j)
            for pb_j, ob_j in zip(pool["blocks"], one["blocks"])]
        return out

    # -- decode-path block ----------------------------------------------------
    def _decode_block_pre(self, p, desc: LayerDesc, bc, x, pos,
                          decode_window, active=None):
        """Mixer half of one-token decode (norm1 → attn/mamba/rwkv-TM →
        cross-attn → norm2). Cache rows of inactive slots stay frozen.
        Returns (x_mid, h2, bc)."""
        cfg = self.cfg
        prev = dict(bc)
        win = desc.window or decode_window
        h = apply_norm(p["norm1"], x)
        if desc.kind == BLOCK_ATTN:
            if cfg.attn.mla is not None:
                wpos = self._ring(pos, bc["ckv"].shape[1], win)
                y, bc["ckv"], bc["kr"] = attn_lib.mla_decode(
                    p["attn"], cfg, h, bc["ckv"], bc["kr"], wpos)
            else:
                wpos = self._ring(pos, bc["k"].shape[1], win)
                y, bc["k"], bc["v"] = attn_lib.attn_decode(
                    p["attn"], cfg, h, bc["k"], bc["v"], wpos,
                    window=0 if bc["k"].shape[1] <= (win or 1 << 30) else win)
        elif desc.kind == BLOCK_MAMBA:
            y, bc["conv"], bc["ssm"] = mamba_lib.mamba_decode(
                p["mamba"], cfg, h, bc["conv"], bc["ssm"])
        else:
            y, (bc["state"], bc["tm"]) = rwkv_lib.rwkv_time_mix(
                p["rwkv"], cfg, h, bc["state"], bc["tm"])
        if cfg.post_block_norm:
            y = apply_norm(p["post_norm1"], y)
        x = x + y
        if cfg.is_encoder_decoder and desc.kind == BLOCK_ATTN:
            hc = apply_norm(p["norm_cross"], x)
            yc, _, _ = attn_lib.attn_decode(p["cross_attn"], cfg, hc,
                                            bc["cross_k"], bc["cross_v"], pos,
                                            cross=True)
            x = x + yc
        h2 = apply_norm(p["norm2"], x)
        if active is not None:
            bc = {key: (val if val is prev[key]
                        else _gate_rows(active, val, prev[key]))
                  for key, val in bc.items()}
        return x, h2, bc

    @property
    def decode_capacity_factor(self) -> float:
        """MoE capacity factor of one-token decode: dropless (C >= T) by
        default; serving deployments may trade exactness for 1/16th the
        expert-slot padding (§Perf)."""
        cfg = self.cfg
        return cfg.decode_capacity_factor or cfg.moe.n_experts / cfg.moe.top_k

    def _decode_block_post(self, p, desc: LayerDesc, bc, x_mid, h2, *,
                           expert_fn=None, active=None, routing=None,
                           slot_weights=None, slot_ids=None):
        """FFN half of one-token decode. Returns (x_out, bc, counts)."""
        cfg = self.cfg
        prev = dict(bc)
        counts = None
        if desc.kind == BLOCK_RWKV:
            y2, bc["cm"] = rwkv_lib.rwkv_channel_mix(p["rwkv"], cfg, h2,
                                                     bc["cm"])
        elif desc.is_moe:
            y2, moe_aux = moe_ffn(p["moe"], cfg, h2,
                                  capacity_factor=self.decode_capacity_factor,
                                  expert_fn=expert_fn, routing=routing,
                                  slot_weights=slot_weights,
                                  slot_ids=slot_ids)
            counts = moe_aux["counts"]
        else:
            y2 = apply_ffn(p["ffn"], h2, cfg.act)
        if cfg.post_block_norm:
            y2 = apply_norm(p["post_norm2"], y2)
        if active is not None:
            bc = {key: (val if val is prev[key]
                        else _gate_rows(active, val, prev[key]))
                  for key, val in bc.items()}
        return x_mid + y2, bc, counts

    def _decode_block(self, p, desc: LayerDesc, bc, x, pos, decode_window,
                      expert_fn=None, active=None):
        """One-token decode through one block. ``pos`` may be a (B,) per-slot
        position vector; ``active`` an optional (B,) bool mask — cache rows of
        inactive slots are left untouched (attention K/V, ring pointers, and
        recurrent mamba/rwkv state all stay frozen), so free or
        just-prefilled slots in a slot pool never advance their state."""
        x_mid, h2, bc = self._decode_block_pre(p, desc, bc, x, pos,
                                               decode_window, active=active)
        return self._decode_block_post(p, desc, bc, x_mid, h2,
                                       expert_fn=expert_fn, active=active)

    @staticmethod
    def _ring(pos, cache_phys_len, win):
        """Physical write index: identity if the cache holds all positions,
        ring index when the cache is a window buffer."""
        if win and cache_phys_len <= win:
            return pos % cache_phys_len
        return pos

    def _seed_mixer_cache(self, p, desc: LayerDesc, bc, h_in, aux, ekv=None):
        """Seed a block cache's *mixer* state from a full-prompt prefill
        pass: attention K/V tails (+ cross K/V), mamba conv/ssm, rwkv
        time-mix state. ``aux`` is the mixer aux of `_apply_block_pre`;
        ``h_in`` the block's input activations (the mamba conv tail and the
        rwkv time-mix shift are functions of the *normed block input*, not
        of any mixer output). The rwkv channel-mix shift (``cm``) comes
        from the post half and is seeded by the caller."""
        cfg = self.cfg
        bc = dict(bc)
        if desc.kind == BLOCK_ATTN:
            if cfg.attn.mla is not None:
                ckv, kr = aux["kv"]
                bc["ckv"] = _seed(bc["ckv"], ckv)
                bc["kr"] = _seed(bc["kr"], kr)
            else:
                k, v = aux["kv"]
                bc["k"] = _seed(bc["k"], k)
                bc["v"] = _seed(bc["v"], v)
                if ekv is not None:
                    bc["cross_k"] = ekv[0].astype(bc["cross_k"].dtype)
                    bc["cross_v"] = ekv[1].astype(bc["cross_v"].dtype)
        elif desc.kind == BLOCK_MAMBA:
            xin_norm = apply_norm(p["norm1"], h_in)
            bc["conv"] = _conv_tail(xin_norm, cfg, p["mamba"]).astype(
                bc["conv"].dtype)
            bc["ssm"] = aux["mamba_state"]
        else:  # rwkv
            bc["state"] = aux["rwkv_state"]
            # time-mix shift = last *normed* block input token
            bc["tm"] = apply_norm(p["norm1"], h_in)[:, -1].astype(
                bc["tm"].dtype)
        return bc

    # -- public: prefill / serve_step -----------------------------------------
    def prefill(self, params, batch, cache, *, expert_fn=None,
                true_len=None):
        """Run the full prompt, fill the cache, return last-token logits.

        For window-capped caches the prompt must fit the window (the serving
        engine chunks longer prompts through serve_step).

        ``true_len``: optional per-row real prompt length ((B,) vector or
        scalar) for right-padded ragged prefill (slot-pool admission). Pad
        tokens beyond ``true_len`` are causally invisible to real queries,
        take no MoE capacity, contribute no expert counts, and the returned
        logits come from each row's *last real* token. Their K/V garbage sits
        at cache positions >= true_len, masked during decode and overwritten
        as the sequence grows. Recurrent (mamba/rwkv) prefill state is NOT
        pad-corrected — the serving engine prefills those families at exact
        lengths (see JaxModelServer)."""
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        B, S = x.shape[:2]
        token_mask = None
        if true_len is not None:
            true_len = jnp.broadcast_to(
                jnp.asarray(true_len, jnp.int32), (B,))
            token_mask = jnp.arange(S)[None, :] < true_len[:, None]
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["enc_embeds"])

        counts_all = []

        def seed_block_full(p, desc, bc, h):
            ekv = None
            if enc_out is not None and desc.kind == BLOCK_ATTN:
                ekv = attn_lib.cross_kv(p["cross_attn"], cfg, enc_out)
            h2, aux = self._apply_block(p, desc, h, positions, enc_kv=ekv,
                                        capacity_factor=2.0,
                                        expert_fn=expert_fn,
                                        token_mask=token_mask)
            bc = self._seed_mixer_cache(p, desc, bc, h, aux, ekv)
            if desc.kind == BLOCK_RWKV:
                # channel-mix shift = last normed pre-CM token
                bc["cm"] = aux["rwkv_cm"].astype(bc["cm"].dtype)
            return h2, bc, aux.get("counts")

        x_cur = x
        new_prefix = []
        for i in range(self.n_prefix):
            x_cur, bc, cnt = seed_block_full(params["prefix"][i],
                                             self.descs[i],
                                             cache["prefix"][i], x_cur)
            new_prefix.append(bc)
            if cnt is not None:
                counts_all.append(cnt[None])
        cache["prefix"] = new_prefix

        if self.n_groups:
            descs = [self.descs[self.n_prefix + p] for p in range(self.period)]

            def group_body(h, xs):
                block_params, bcs = xs
                new_bcs, g_counts = [], []
                for pos in range(self.period):
                    h, bc, cnt = seed_block_full(block_params[pos], descs[pos],
                                                 bcs[pos], h)
                    new_bcs.append(bc)
                    if cnt is not None:
                        g_counts.append(cnt)
                out_counts = (jnp.stack(g_counts) if g_counts
                              else jnp.zeros((0,), jnp.int32))
                return h, (tuple(new_bcs), out_counts)

            x_cur, (new_blocks, scan_counts) = jax.lax.scan(
                group_body, x_cur,
                (tuple(params["blocks"]), tuple(cache["blocks"])))
            cache["blocks"] = list(new_blocks)
            if scan_counts.ndim > 2:
                counts_all.append(scan_counts.reshape(-1, *scan_counts.shape[2:]))

        if true_len is None:
            cache["pos"] = jnp.full((B,), S, jnp.int32)
            x_last = x_cur[:, -1:]
        else:
            cache["pos"] = true_len
            # each row's last *real* token feeds the logits
            x_last = jnp.take_along_axis(
                x_cur, (true_len - 1)[:, None, None], axis=1)
        x_last = apply_norm(params["final_norm"], x_last)
        logits = self._logits(params, x_last)[:, 0]
        aux = {"counts": (jnp.concatenate(counts_all, 0) if counts_all else None)}
        return logits, cache, aux

    def serve_step(self, params, cache, token_or_embeds, *, expert_fn=None,
                   decode_window: int = 0, active=None):
        """One decode step. ``token_or_embeds``: (B,) int tokens or (B,1,d)
        embeddings. ``decode_window``: static int; must match the
        ``decode_window`` the cache was initialized with.

        ``active``: optional (B,) bool mask for slot-pool serving — rows of
        inactive slots are computed (the batch shape is fixed) but their
        cache state, position and counts are left untouched, so a free slot
        can carry arbitrary garbage without perturbing live sequences.
        Returns (logits (B,V), cache, aux)."""
        cfg = self.cfg
        B = token_or_embeds.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), (B,))
        if active is not None:
            active = jnp.asarray(active, bool)
        if token_or_embeds.ndim == 1:
            x = embed_lookup(params["embed"], token_or_embeds)[:, None]
        else:
            x = token_or_embeds.astype(self.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, self.dtype)
        if not cfg.attn.use_rope:
            x = x + params["pos_embed"][pos][:, None]

        counts_all = []
        new_prefix = []
        x_cur = x
        for i in range(self.n_prefix):
            x_cur, bc, cnt = self._decode_block(
                params["prefix"][i], self.descs[i], dict(cache["prefix"][i]),
                x_cur, pos, decode_window, expert_fn=expert_fn, active=active)
            new_prefix.append(bc)
            if cnt is not None:
                counts_all.append(cnt[None])
        cache["prefix"] = new_prefix

        if self.n_groups:
            descs = [self.descs[self.n_prefix + p] for p in range(self.period)]

            def group_body(h, xs):
                block_params, bcs = xs
                new_bcs, g_counts = [], []
                for posn in range(self.period):
                    h, bc, cnt = self._decode_block(
                        block_params[posn], descs[posn], dict(bcs[posn]), h,
                        pos, decode_window, expert_fn=expert_fn, active=active)
                    new_bcs.append(bc)
                    if cnt is not None:
                        g_counts.append(cnt)
                out_counts = (jnp.stack(g_counts) if g_counts
                              else jnp.zeros((0,), jnp.int32))
                return h, (tuple(new_bcs), out_counts)

            x_cur, (new_blocks, scan_counts) = jax.lax.scan(
                group_body, x_cur,
                (tuple(params["blocks"]), tuple(cache["blocks"])))
            cache["blocks"] = list(new_blocks)
            if scan_counts.ndim > 2:
                counts_all.append(scan_counts.reshape(-1, *scan_counts.shape[2:]))

        cache["pos"] = pos + (1 if active is None
                              else active.astype(jnp.int32))
        x_last = apply_norm(params["final_norm"], x_cur)
        logits = self._logits(params, x_last)[:, 0]
        counts = jnp.concatenate(counts_all, 0) if counts_all else None
        if counts is not None and active is not None:
            counts = counts * active.astype(counts.dtype)[None, :, None]
        aux = {"counts": counts}
        return logits, cache, aux


def _gate_rows(active, new, old):
    """Per-row select: keep ``old`` rows where ``active`` is False (slot-pool
    mode — frozen slots must not advance KV, ring, or recurrent state)."""
    a = active.reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(a, new, old)


def _seed(buf, full):
    """Write the (tail of the) prefill sequence into a cache buffer."""
    L = buf.shape[1]
    return jax.lax.dynamic_update_slice_in_dim(
        buf, full[:, -L:].astype(buf.dtype), 0, 1)


def _conv_tail(xin, cfg, pm):
    """Last d_conv-1 *conv inputs* (pre-conv activations) for mamba decode."""
    m = cfg.mamba
    xz = xin @ pm["w_in"]
    xr, _ = jnp.split(xz, 2, axis=-1)
    B, S, d_in = xr.shape
    n = m.d_conv - 1
    pad = jnp.zeros((B, max(0, n - S), d_in), xr.dtype)
    return jnp.concatenate([pad, xr[:, -n:]], axis=1)


