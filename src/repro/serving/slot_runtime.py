"""Layered streaming execution for model-mode serving with the expert slot
cache (DESIGN.md §6).

The fused slot-pool step (`JaxModelServer._get_step_fn`) jits the whole
model, which requires every expert the iteration might touch to be device
resident *before* the step launches — impossible to know, since layer
``l``'s router runs on activations produced by layer ``l-1``. This runtime
instead walks the stack one layer at a time with the block split at the MoE
boundary:

    pre  (jit)  — mixer half + norm2 + **router top-k** for this layer
    host        — read the routed expert ids, `ensure` them in the slot
                  cache (misses = timed demand uploads, victims = the
                  engine's Algorithm-2 verdict)
    post (jit)  — the expert FFN over the slot buffers
                  (`moe_ffn(routing=…, slot_weights=…, slot_ids=…)`): in
                  decode, the routed-row path gathers only the T·k routed
                  experts' slot rows (`slot_ids[idx]`, on the device); in
                  prefill, the capacity dispatch over all E gathered rows

so only ONE layer's routed expert set must ever be resident at use time
(the capacity floor is ``E``, not ``L×E``), and prefetch uploads issued at
iteration boundaries overlap the layers still executing in front of them —
the fence is the data dependence of the first ``post`` that consumes the
updated buffer, exactly "block at use time".

Double-buffered schedule (DESIGN.md §7, default): the iteration boundary
no longer issues every prefetch upload up front. `sync_residency` applies
evictions, stages the *first* MoE layer's uploads, and files the rest in a
per-layer plan; the walk then stages layer ``li+1``'s planned uploads
immediately after dispatching layer ``li``'s ``post`` — the host→device
copies run while ``post`` computes. Every staged upload lands in the slot
cache's staging set (a second buffer set) and is spliced into the slot
buffers by ``commit()`` right before the next ``post`` dispatch, so an
in-flight kernel never observes a slot mutating under it, and demand
misses block only through the data dependence of the kernel that consumes
the committed buffers. ``fenced=True`` restores the PR-5 schedule (stage
everything at the boundary, wall-clock fence on every demand miss) for the
bit-identity smoke comparison.

Numerics are bit-identical to the fused path: the per-layer jits run the
same ops on the same values (verified by tests/test_slot_cache.py), the
router is evaluated once per layer in ``pre`` and its (gates, idx) handed
to ``post`` verbatim, and a gathered slot triple is bit-equal to the dense
expert weight it was uploaded from.

Compile accounting: every jitted piece counts its traces into the server's
``compile_counts`` under ``("slot_*", …)`` keys; per distinct layer
signature there is one compile, not one per layer instance, so warmup cost
is O(period), like the fused scan. Each jitted function is named after its
key, so a device trace shows ``jit_slot_decode_post`` and not ``jit_impl``.

Spans (``jax.profiler.TraceAnnotation``, free unless a profiler runs):
``runtime.decode`` / ``runtime.prefill`` around one layer walk, the host's
reads of the router's top-k, ``post``'s counts and the token as
``runtime.read.route`` / ``.counts`` / ``.token`` (metadata ``layer``),
``runtime.post`` around each MoE ``post`` dispatch (metadata ``layer`` and
``rows``, the expert weight rows that program reads), ``runtime.sync``
around the residency sync and ``runtime.stage`` around a planned layer's
uploads.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.slot_cache import ExpertSlotCache, HostExpertStore
from repro.models.layers import embed_lookup
from repro.models.moe import expert_rows, route
from repro.serving.guard import bump_trace_count


class SlotStreamRuntime:
    """Per-layer jitted prefill/decode over a pooled, slot-indexed cache,
    streaming expert weights through an :class:`ExpertSlotCache`."""

    def __init__(self, model, params, *, n_pool_slots: int,
                 n_weight_slots: int, victim_fn=None, compile_counts=None,
                 transfer_dtype: str = "fp32", fenced: bool = False):
        import jax
        import jax.numpy as jnp
        if model.cfg.is_encoder_decoder:
            raise NotImplementedError(
                "slot-cache streaming does not support encoder-decoder "
                "models yet; run them all-resident (resident_fraction=1.0)")
        self._jax, self._jnp = jax, jnp
        self.model = model
        self.cfg = model.cfg
        self.store = HostExpertStore(model, params,
                                     transfer_dtype=transfer_dtype)
        self.params = self.store.stripped_params
        self._init_slot_caches(n_weight_slots, fenced)
        self.fenced = bool(fenced)
        self._upload_plan: Dict[int, List] = {}
        self.victim_fn = victim_fn
        self.n_pool_slots = n_pool_slots
        self.compile_counts = (compile_counts if compile_counts is not None
                               else {})
        self.cache_len: Optional[int] = None
        self.pos = np.zeros(n_pool_slots, np.int32)
        self.layer_caches: List = []
        self._fns: Dict = {}
        # per-layer device param slices (expert weights already stripped)
        self._layer_params = []
        for i in range(len(model.descs)):
            if i < model.n_prefix:
                self._layer_params.append(self.params["prefix"][i])
            else:
                off = i - model.n_prefix
                pos_, g = off % model.period, off // model.period
                self._layer_params.append(jax.tree.map(
                    lambda a, g=g: a[g], self.params["blocks"][pos_]))
        self._moe_li = {idx: li for li, idx in enumerate(model.moe_layers)}

    def _init_slot_caches(self, n_weight_slots: int, fenced: bool) -> None:
        """One device-resident slot cache (the sharded runtime overrides
        this with one cache per mesh device)."""
        self.slot_cache = ExpertSlotCache(self.store, n_weight_slots,
                                          fenced=fenced)

    # -- pool lifecycle ------------------------------------------------------
    def build_pool(self, cache_len: int) -> None:
        """(Re)build the pooled per-layer decode caches (flat per-layer
        list — the layered walk never needs the fused scan's group
        stacking). Jitted pieces close over ``cache_len``, so they rebuild
        with the pool."""
        self.cache_len = cache_len
        B = self.n_pool_slots
        self.layer_caches = [
            self.model._block_cache(d, B, cache_len, 0)
            for d in self.model.descs]
        self.pos = np.zeros(B, np.int32)
        self._fns.clear()

    def sync_residency(self, target_keys) -> int:
        """Iteration-boundary reconciliation: the OffloadEngine's GPU-cache
        verdicts (admissions, prefetch arrivals, evictions) become real
        async uploads/slot releases.

        Double-buffered mode: evictions apply now, the first MoE layer's
        uploads are staged now (they overlap the embed + any leading dense
        layers), and the remaining uploads are *planned* per layer — the
        walk stages layer ``li+1``'s plan while layer ``li``'s ``post``
        computes (:meth:`_stage_plan`). Fenced mode stages everything at
        the boundary, like PR 5."""
        with TraceAnnotation("runtime.sync"):
            if self.fenced:
                return self.slot_cache.sync(target_keys)
            sc = self.slot_cache
            target = set(target_keys)
            for key in sc.resident:
                if key not in target:
                    sc.evict(key)
            plan: Dict[int, List] = {}
            for key in sorted(target):
                if key not in sc:
                    plan.setdefault(key[0], []).append(key)
            self._upload_plan = plan
            return self._stage_plan(0)

    def _stage_plan(self, li: int) -> int:
        """Stage the planned prefetch-class uploads for MoE layer ``li``
        (issued while the previous layer's ``post`` computes)."""
        keys = self._upload_plan.pop(li, None)
        if not keys:
            return 0
        with TraceAnnotation("runtime.stage", layer=li, keys=len(keys)):
            return self.slot_cache.prefetch(keys)

    def flush_pending(self) -> None:
        """Stage any still-planned uploads and commit the staging set —
        residency then exactly matches the last sync's verdicts (used at
        drain boundaries and by the residency-consistency checks)."""
        for li in sorted(self._upload_plan):
            self.slot_cache.prefetch(self._upload_plan[li])
        self._upload_plan.clear()
        self.slot_cache.commit()

    # -- jit bookkeeping -----------------------------------------------------
    def _count(self, key) -> None:
        bump_trace_count(self.compile_counts, key,
                         getattr(self, "_trace_limit", None))

    def _fn(self, key, builder):
        f = self._fns.get(key)
        if f is None:
            f = self._fns[key] = builder()
        return f

    def _is_moe(self, i: int) -> bool:
        return i in self._moe_li

    def _ensure(self, li: int, expert_ids) -> None:
        self.slot_cache.ensure([(li, int(e)) for e in expert_ids],
                               self.victim_fn)

    def _post_rows(self, prompt_len: Optional[int] = None) -> int:
        """Expert weight rows one MoE ``post`` reads (the ``rows`` of its
        ``runtime.post`` span), by the predicate that picks ``moe_ffn``'s
        path: decode over the pool at the decode capacity, or the masked
        prefill of one padded prompt of ``prompt_len``."""
        if prompt_len is None:
            return expert_rows(self.cfg, self.n_pool_slots, 1,
                               self.model.decode_capacity_factor)
        return expert_rows(self.cfg, 1, prompt_len, 2.0, masked=True)

    # -- decode --------------------------------------------------------------
    def _decode_embed(self):
        def build():
            jax, jnp = self._jax, self._jnp
            model, cfg = self.model, self.cfg

            def slot_embed(params, tok, pos):
                self._count("slot_embed")
                x = embed_lookup(params["embed"], tok)[:, None]
                if cfg.embed_scale:
                    x = x * jnp.asarray(cfg.d_model ** 0.5, model.dtype)
                if not cfg.attn.use_rope:
                    x = x + params["pos_embed"][pos][:, None]
                return x
            return jax.jit(slot_embed)
        return self._fn("slot_embed", build)

    def _decode_layer(self, desc):
        key = ("slot_decode", desc)

        def build():
            model = self.model

            def slot_decode(p, bc, x, pos, active):
                self._count(key)
                x_out, bc, _ = model._decode_block(p, desc, dict(bc), x, pos,
                                                   0, active=active)
                return x_out, bc
            # the pool cache is rebound to the output every call — donate
            # it (as the fused step does) so XLA updates the n_slots ×
            # cache_len state in place instead of copying it per token
            return self._jax.jit(slot_decode, donate_argnums=(1,))
        return self._fn(key, build)

    def _decode_pre(self, desc):
        key = ("slot_decode_pre", desc)

        def build():
            model, cfg = self.model, self.cfg

            def slot_decode_pre(p, bc, x, pos, active):
                self._count(key)
                x_mid, h2, bc = model._decode_block_pre(
                    p, desc, dict(bc), x, pos, 0, active=active)
                B, S, d = h2.shape
                gates, idx, _ = route(p["moe"], cfg.moe, h2.reshape(B * S, d))
                return x_mid, h2, bc, gates, idx
            return self._jax.jit(slot_decode_pre, donate_argnums=(1,))
        return self._fn(key, build)

    def _decode_post(self, desc):
        key = ("slot_decode_post", desc)

        def build():
            model = self.model

            def slot_decode_post(p, bufs, row, bc, x_mid, h2, gates, idx,
                                 active):
                self._count(key)
                x_out, bc, counts = model._decode_block_post(
                    p, desc, dict(bc), x_mid, h2, active=active,
                    routing=(gates, idx), slot_weights=bufs, slot_ids=row)
                counts = counts * active.astype(counts.dtype)[:, None]
                return x_out, bc, counts
            return self._jax.jit(slot_decode_post, donate_argnums=(3,))
        return self._fn(key, build)

    def _decode_tail(self):
        def build():
            from repro.models.layers import apply_norm
            jax, jnp, model = self._jax, self._jnp, self.model

            def slot_tail(params, x):
                self._count("slot_tail")
                x_last = apply_norm(params["final_norm"], x)
                logits = model._logits(params, x_last)[:, 0]
                return jnp.argmax(logits, axis=-1)
            return jax.jit(slot_tail)
        return self._fn("slot_tail", build)

    def _run_decode_post(self, desc, li, p, bc, x_mid, h2, gates, idx,
                         active):
        """Dispatch one MoE layer's ``post`` against the freshly committed
        slot buffers (the sharded runtime overrides this with the
        expert-parallel all-to-all path)."""
        jnp = self._jnp
        row = jnp.asarray(self.slot_cache.table_row(li))
        # splice staged uploads in *now*: post is dispatched against
        # the committed value, while anything still executing keeps
        # the buffers it was given (no-alias by construction)
        bufs = self.slot_cache.commit()
        return self._decode_post(desc)(p, bufs, row, bc, x_mid, h2, gates,
                                       idx, active)

    def decode(self, tok_np: np.ndarray, active_np: np.ndarray):
        """One pooled decode step. Returns (new tokens (B,) np, counts
        (n_moe, B, E) np — inactive rows zeroed, like the fused step)."""
        jnp = self._jnp
        with TraceAnnotation("runtime.decode"):
            tok = jnp.asarray(tok_np)
            pos = jnp.asarray(self.pos)
            active = jnp.asarray(active_np, bool)
            x = self._decode_embed()(self.params, tok, pos)
            counts_rows = []
            for i, desc in enumerate(self.model.descs):
                p, bc = self._layer_params[i], self.layer_caches[i]
                if self._is_moe(i):
                    x_mid, h2, bc, gates, idx = self._decode_pre(desc)(
                        p, bc, x, pos, active)
                    li = self._moe_li[i]
                    with TraceAnnotation("runtime.read.route", layer=li):
                        idx_np = np.asarray(idx)      # (B·1, k) — sync point
                    rows = np.asarray(active_np, bool)
                    used = (np.unique(idx_np[rows]) if rows.any()
                            else np.empty(0, np.int64))
                    self._ensure(li, used)
                    with TraceAnnotation("runtime.post", layer=li,
                                         rows=self._post_rows()):
                        x, bc, cnts = self._run_decode_post(
                            desc, li, p, bc, x_mid, h2, gates, idx, active)
                    # double-buffered overlap: issue the next MoE layer's
                    # planned uploads while this post computes
                    self._stage_plan(li + 1)
                    with TraceAnnotation("runtime.read.counts", layer=li):
                        counts_rows.append(np.asarray(cnts))
                else:
                    x, bc = self._decode_layer(desc)(p, bc, x, pos, active)
                self.layer_caches[i] = bc
            tok_dev = self._decode_tail()(self.params, x)
            with TraceAnnotation("runtime.read.token"):
                tok_new = np.asarray(tok_dev)
            self.pos = self.pos + np.asarray(active_np, np.int32)
            return tok_new, np.stack(counts_rows)

    # -- prefill -------------------------------------------------------------
    def _prefill_embed(self, P):
        key = ("slot_prefill_embed", P)

        def build():
            model = self.model

            def slot_prefill_embed(params, toks):
                self._count(key)
                return model._embed(params, {"tokens": toks})
            return self._jax.jit(slot_prefill_embed)
        return self._fn(key, build)

    def _prefill_layer(self, desc, P):
        key = ("slot_prefill_layer", desc, P)

        def build():
            from repro.config import BLOCK_RWKV
            model, cache_len = self.model, self.cache_len

            def slot_prefill_layer(p, x, positions, true_len):
                self._count(key)
                S = x.shape[1]
                token_mask = (self._jnp.arange(S)[None, :]
                              < true_len[:, None])
                x_mid, h2, aux = model._apply_block_pre(p, desc, x, positions)
                bc = model._block_cache(desc, 1, cache_len, 0)
                bc = model._seed_mixer_cache(p, desc, bc, x, aux)
                x_out, aux2 = model._apply_block_post(
                    p, desc, x_mid, h2, capacity_factor=2.0,
                    token_mask=token_mask)
                if desc.kind == BLOCK_RWKV:
                    bc["cm"] = aux2["rwkv_cm"].astype(bc["cm"].dtype)
                return x_out, bc
            return self._jax.jit(slot_prefill_layer)
        return self._fn(key, build)

    def _prefill_pre(self, desc, P):
        key = ("slot_prefill_pre", desc, P)

        def build():
            model, cfg, cache_len = self.model, self.cfg, self.cache_len

            def slot_prefill_pre(p, x, positions):
                self._count(key)
                x_mid, h2, aux = model._apply_block_pre(p, desc, x, positions)
                bc = model._block_cache(desc, 1, cache_len, 0)
                bc = model._seed_mixer_cache(p, desc, bc, x, aux)
                B, S, d = h2.shape
                gates, idx, _ = route(p["moe"], cfg.moe, h2.reshape(B * S, d))
                return x_mid, h2, bc, gates, idx
            return self._jax.jit(slot_prefill_pre)
        return self._fn(key, build)

    def _prefill_post(self, desc, P):
        key = ("slot_prefill_post", desc, P)

        def build():
            model = self.model

            def slot_prefill_post(p, bufs, row, x_mid, h2, gates, idx,
                                  true_len):
                self._count(key)
                S = h2.shape[1]
                token_mask = (self._jnp.arange(S)[None, :]
                              < true_len[:, None])
                x_out, aux = model._apply_block_post(
                    p, desc, x_mid, h2, capacity_factor=2.0,
                    token_mask=token_mask, routing=(gates, idx),
                    slot_weights=bufs, slot_ids=row)
                return x_out, aux["counts"]
            return self._jax.jit(slot_prefill_post)
        return self._fn(key, build)

    def _prefill_tail(self, P):
        key = ("slot_prefill_tail", P)

        def build():
            from repro.models.layers import apply_norm
            jax, jnp, model = self._jax, self._jnp, self.model

            def slot_prefill_tail(params, x, true_len):
                self._count(key)
                x_last = jnp.take_along_axis(
                    x, (true_len - 1)[:, None, None], axis=1)
                x_last = apply_norm(params["final_norm"], x_last)
                logits = model._logits(params, x_last)[:, 0]
                return jnp.argmax(logits, axis=-1)
            return jax.jit(slot_prefill_tail)
        return self._fn(key, build)

    def _write_slot(self, desc):
        key = ("slot_write", desc)

        def build():
            jax = self._jax

            def slot_write(pool_bc, one_bc, slot):
                self._count(key)
                return jax.tree.map(
                    lambda pb, ob: jax.lax.dynamic_update_slice_in_dim(
                        pb, ob.astype(pb.dtype), slot, 0), pool_bc, one_bc)
            return jax.jit(slot_write, donate_argnums=(0,))
        return self._fn(key, build)

    def _run_prefill_post(self, desc, P, li, p, x_mid, h2, gates, idx, tl):
        jnp = self._jnp
        row = jnp.asarray(self.slot_cache.table_row(li))
        bufs = self.slot_cache.commit()
        return self._prefill_post(desc, P)(p, bufs, row, x_mid, h2, gates,
                                           idx, tl)

    def prefill(self, padded_prompt: np.ndarray, true_len: int, slot: int,
                rid: int = -1):
        """Stream one right-padded B=1 prompt through the stack and land
        its per-layer caches in pool row ``slot``. Returns (first generated
        token, counts (n_moe, E) np — pad tokens excluded). ``rid`` only
        labels the ``runtime.prefill`` span."""
        jnp = self._jnp
        with TraceAnnotation("runtime.prefill", rid=rid, slot=slot):
            P = len(padded_prompt)
            toks = jnp.asarray(np.asarray(padded_prompt, np.int32)[None])
            tl = jnp.asarray([true_len], jnp.int32)
            slot_dev = jnp.asarray(slot, jnp.int32)
            x, positions = self._prefill_embed(P)(self.params, toks)
            counts_rows = []
            for i, desc in enumerate(self.model.descs):
                p = self._layer_params[i]
                if self._is_moe(i):
                    x_mid, h2, bc_one, gates, idx = self._prefill_pre(
                        desc, P)(p, x, positions)
                    li = self._moe_li[i]
                    with TraceAnnotation("runtime.read.route", layer=li):
                        idx_np = np.asarray(idx)[:true_len]   # real tokens
                    self._ensure(li, np.unique(idx_np))
                    with TraceAnnotation("runtime.post", layer=li,
                                         rows=self._post_rows(P)):
                        x, cnts = self._run_prefill_post(
                            desc, P, li, p, x_mid, h2, gates, idx, tl)
                    self._stage_plan(li + 1)
                    with TraceAnnotation("runtime.read.counts", layer=li):
                        counts_rows.append(np.asarray(cnts)[0])
                else:
                    x, bc_one = self._prefill_layer(desc, P)(
                        p, x, positions, tl)
                self.layer_caches[i] = self._write_slot(desc)(
                    self.layer_caches[i], bc_one, slot_dev)
            tok_dev = self._prefill_tail(P)(self.params, x, tl)
            with TraceAnnotation("runtime.read.token"):
                tok0 = int(np.asarray(tok_dev)[0])
            self.pos[slot] = true_len
            return tok0, np.stack(counts_rows)


# ---------------------------------------------------------------------------
# Expert-parallel sharded runtime (DESIGN.md §8)
# ---------------------------------------------------------------------------


class _CacheGroupView:
    """Aggregate façade over the per-device slot caches: summed counters for
    the engine's stats crosswalk, plus the union residency view the
    consistency checks read. Not a cache — movement goes through the
    per-device instances."""

    def __init__(self, caches):
        self.caches = caches

    @property
    def n_slots(self) -> int:
        return sum(c.n_slots for c in self.caches)

    @property
    def resident(self):
        return [k for c in self.caches for k in c.resident]

    def __contains__(self, key) -> bool:
        return any(key in c for c in self.caches)

    def stats(self) -> dict:
        per_dev = [c.stats() for c in self.caches]
        agg = dict(per_dev[0])
        for s in per_dev[1:]:
            for k, v in s.items():
                if isinstance(v, (int, float)):
                    agg[k] = agg[k] + v
        # non-additive fields: identical across devices, keep one copy
        agg["transfer_dtype"] = per_dev[0]["transfer_dtype"]
        agg["wire_expert_bytes"] = per_dev[0]["wire_expert_bytes"]
        agg["n_devices"] = len(per_dev)
        agg["per_device"] = per_dev
        return agg


class ShardedSlotRuntime(SlotStreamRuntime):
    """Expert-parallel serving over a 1-D ``("expert",)`` device mesh.

    Same per-layer walk as :class:`SlotStreamRuntime`, with three
    substitutions (DESIGN.md §8):

    * **per-device slot caches** — one :class:`ExpertSlotCache` pinned to
      each mesh device, so D independent host→device upload streams run
      concurrently; residency is partitioned by the placement policy's
      *home* assignment (the OffloadEngine's global Algorithm-2 verdicts
      still decide *what* is resident);
    * **sharded expert compute** — each MoE ``post`` gathers its layer's
      dequantized expert weights per device (positions in ``placement.perm``
      order), assembles them zero-copy into one global array sharded over
      the ``"expert"`` axis, and runs
      :func:`repro.kernels.moe_ffn.moe_ffn_sharded` (all-to-all token
      exchange + local grouped FFN) through the ``expert_fn`` seam;
    * **replicated runtime state** — params, per-layer param slices and the
      pool caches are committed to ``NamedSharding(mesh, P())``, so every
      per-layer jit runs SPMD-replicated over the mesh and only the expert
      dimension is ever partitioned. Replicated values compute exactly the
      single-device answer, the all-to-all is an exact permutation, and the
      local FFN partitions no contraction dim — tokens are bit-identical
      to the D=1 path.

    ``perm``/``inv_perm`` are *traced* arguments, so EAMC-driven placement
    rebalances never recompile anything.
    """

    def __init__(self, model, params, *, mesh, placement, **kw):
        if model.cfg.moe_dispatch == "grouped":
            raise NotImplementedError(
                "expert-parallel serving requires global dispatch "
                "(moe_dispatch='grouped' vmaps the expert computation, "
                "which cannot wrap the all-to-all shard_map)")
        D = mesh.shape["expert"]
        E = model.cfg.moe.n_experts
        if E % D != 0:
            raise ValueError(f"n_experts {E} must divide by the "
                             f"expert-parallel degree {D}")
        self.mesh = mesh
        self.placement = placement
        super().__init__(model, params, **kw)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._rep = NamedSharding(mesh, P())
        self._shard = NamedSharding(mesh, P("expert"))
        # replicate all device-side runtime state over the mesh so every
        # per-layer jit is one SPMD computation on the same device set; the
        # store's reference moves too, or the unreplicated copy would stay
        # alive on the default device
        self.params = self.store.stripped_params = jax.device_put(
            self.params, self._rep)
        self._layer_params = jax.device_put(self._layer_params, self._rep)

    def _init_slot_caches(self, n_weight_slots: int, fenced: bool) -> None:
        import numpy as np  # noqa: F811 (module-level import shadow-safe)
        devices = list(self.mesh.devices.flat)
        D = len(devices)
        # every device must at least hold one layer's worst-case routed
        # slice of its own homes (cap = E/D experts)
        per_dev = max(n_weight_slots // D, self.placement.cap)
        self.slot_caches = [
            ExpertSlotCache(self.store, per_dev, fenced=fenced, device=dev)
            for dev in devices]
        self.slot_cache = _CacheGroupView(self.slot_caches)

    # -- pool lifecycle ------------------------------------------------------
    def build_pool(self, cache_len: int) -> None:
        super().build_pool(cache_len)
        self.layer_caches = self._jax.device_put(self.layer_caches,
                                                 self._rep)

    def _partition_targets(self, target_keys):
        """Split a global residency target by placement home, trimmed to
        each device's capacity (already-resident keys keep their slots
        first — minimal churn under a home flip)."""
        targets = [set() for _ in self.slot_caches]
        for key in target_keys:
            targets[self.placement.device_of(*key)].add(key)
        out = []
        for cache, tgt in zip(self.slot_caches, targets):
            if len(tgt) > cache.n_slots:
                keep = sorted(k for k in tgt if k in cache)
                rest = sorted(k for k in tgt if k not in cache)
                tgt = set((keep + rest)[: cache.n_slots])
            out.append(tgt)
        return out

    def sync_residency(self, target_keys) -> int:
        with TraceAnnotation("runtime.sync"):
            targets = self._partition_targets(target_keys)
            if self.fenced:
                return sum(c.sync(t)
                           for c, t in zip(self.slot_caches, targets))
            plan: Dict[int, List] = {}
            for dev, (cache, tgt) in enumerate(zip(self.slot_caches,
                                                   targets)):
                for key in cache.resident:
                    if key not in tgt:
                        cache.evict(key)
                for key in sorted(tgt):
                    if key not in cache:
                        plan.setdefault(key[0], []).append((dev, key))
            self._upload_plan = plan
            return self._stage_plan(0)

    def _stage_plan(self, li: int) -> int:
        entries = self._upload_plan.pop(li, None)
        if not entries:
            return 0
        with TraceAnnotation("runtime.stage", layer=li, keys=len(entries)):
            return sum(self.slot_caches[dev].prefetch([key])
                       for dev, key in entries)

    def flush_pending(self) -> None:
        for li in sorted(self._upload_plan):
            for dev, key in self._upload_plan[li]:
                self.slot_caches[dev].prefetch([key])
        self._upload_plan.clear()
        for cache in self.slot_caches:
            cache.commit()

    def _ensure(self, li: int, expert_ids) -> None:
        groups: Dict[int, List] = {}
        for e in expert_ids:
            e = int(e)
            groups.setdefault(self.placement.device_of(li, e),
                              []).append((li, e))
        for dev, keys in groups.items():
            self.slot_caches[dev].ensure(keys, self.victim_fn)

    def _post_rows(self, prompt_len: Optional[int] = None) -> int:
        # every device gathers its cap rows for the all-to-all FFN
        return self.placement.E

    # -- sharded expert weights ---------------------------------------------
    def _gather_fn(self):
        def build():
            from repro.models.moe import gather_slot_weights

            def slot_shard_gather(bufs, row):
                self._count("slot_shard_gather")
                return gather_slot_weights({}, bufs, row)
            return self._jax.jit(slot_shard_gather)
        return self._fn("slot_shard_gather", build)

    def _gathered_weights(self, li: int):
        """Dequantized (E, …) expert weight arrays for layer ``li``,
        assembled zero-copy from per-device gathers: position ``p`` holds
        expert ``perm[p]``, device ``i`` owns positions [i·cap, (i+1)·cap).
        Per-device staged uploads are committed here (the same dispatch
        point as the unsharded runtime's single commit)."""
        jax, jnp = self._jax, self._jnp
        perm = self.placement.perm(li)
        cap = self.placement.cap
        parts: Dict[str, List] = {}
        gather = self._gather_fn()
        for dev, cache in enumerate(self.slot_caches):
            homes = perm[dev * cap:(dev + 1) * cap]
            row = np.maximum(cache.slot_of[li, homes], 0).astype(np.int32)
            bufs = cache.commit()
            g = gather(bufs, jax.device_put(row, cache.device))
            for name, arr in g.items():
                parts.setdefault(name, []).append(arr)
        wts = {}
        for name, shards in parts.items():
            shape = (self.placement.E,) + shards[0].shape[1:]
            wts[name] = jax.make_array_from_single_device_arrays(
                shape, self._shard, shards)
        return wts, perm

    # -- sharded post dispatch ----------------------------------------------
    def _decode_post_sharded(self, desc):
        key = ("slot_decode_post_sharded", desc)

        def build():
            from repro.kernels.moe_ffn import moe_ffn_sharded
            jax, jnp = self._jax, self._jnp
            model, cfg, mesh, rep = self.model, self.cfg, self.mesh, self._rep

            def slot_decode_post_sharded(p, wts, perm, inv_perm, bc, x_mid,
                                         h2, gates, idx, active):
                self._count(key)

                def expert_fn(xg, _p):
                    xg_p = jnp.take(xg, perm, axis=0)
                    yg_p = moe_ffn_sharded(
                        xg_p, wts.get("w_gate"), wts["w_up"], wts["w_down"],
                        mesh=mesh, impl="jnp", act=cfg.act)
                    yg = jnp.take(yg_p, inv_perm, axis=0)
                    # hand the combine a replicated value so the scatter/
                    # segment-sum below runs exactly the D=1 computation
                    return jax.lax.with_sharding_constraint(yg, rep)

                x_out, bc, counts = model._decode_block_post(
                    p, desc, dict(bc), x_mid, h2, active=active,
                    routing=(gates, idx), expert_fn=expert_fn)
                counts = counts * active.astype(counts.dtype)[:, None]
                return x_out, bc, counts
            return self._jax.jit(slot_decode_post_sharded,
                                 donate_argnums=(4,))
        return self._fn(key, build)

    def _prefill_post_sharded(self, desc, P):
        key = ("slot_prefill_post_sharded", desc, P)

        def build():
            from repro.kernels.moe_ffn import moe_ffn_sharded
            jax, jnp = self._jax, self._jnp
            model, cfg, mesh, rep = self.model, self.cfg, self.mesh, self._rep

            def slot_prefill_post_sharded(p, wts, perm, inv_perm, x_mid,
                                          h2, gates, idx, true_len):
                self._count(key)
                S = h2.shape[1]
                token_mask = (jnp.arange(S)[None, :] < true_len[:, None])

                def expert_fn(xg, _p):
                    xg_p = jnp.take(xg, perm, axis=0)
                    yg_p = moe_ffn_sharded(
                        xg_p, wts.get("w_gate"), wts["w_up"], wts["w_down"],
                        mesh=mesh, impl="jnp", act=cfg.act)
                    yg = jnp.take(yg_p, inv_perm, axis=0)
                    return jax.lax.with_sharding_constraint(yg, rep)

                x_out, aux = model._apply_block_post(
                    p, desc, x_mid, h2, capacity_factor=2.0,
                    token_mask=token_mask, routing=(gates, idx),
                    expert_fn=expert_fn)
                return x_out, aux["counts"]
            return self._jax.jit(slot_prefill_post_sharded)
        return self._fn(key, build)

    def _run_decode_post(self, desc, li, p, bc, x_mid, h2, gates, idx,
                         active):
        jnp = self._jnp
        wts, perm = self._gathered_weights(li)
        inv = self.placement.inv_perm(li)
        return self._decode_post_sharded(desc)(
            p, wts, jnp.asarray(perm), jnp.asarray(inv), bc, x_mid, h2,
            gates, idx, active)

    def _run_prefill_post(self, desc, P, li, p, x_mid, h2, gates, idx, tl):
        jnp = self._jnp
        wts, perm = self._gathered_weights(li)
        inv = self.placement.inv_perm(li)
        return self._prefill_post_sharded(desc, P)(
            p, wts, jnp.asarray(perm), jnp.asarray(inv), x_mid, h2, gates,
            idx, tl)
