"""Device-resident expert slot cache — the *real* half of the offload stack.

The simulator (`repro.core.memsim`) decides *when* expert movement happens
and what it costs; this module is where expert weights actually move. A
:class:`HostExpertStore` pins the full expert parameter set in host memory
(and strips it out of the device param tree), and an :class:`ExpertSlotCache`
owns a fixed-shape device buffer of ``n_slots ≪ L×E`` stacked expert triples
(``w_gate/w_up/w_down`` per slot) plus the ``(L, E) → slot`` table the
model reads its expert rows through (:func:`repro.models.moe.moe_ffn`:
the routed rows in decode, :func:`repro.models.moe.gather_slot_weights`
in prefill).

Wire tiers (DESIGN.md §7): the store quantizes each expert into the
configured ``transfer_dtype`` (fp32/fp16/int8 + per-output-channel scales,
see `repro.core.quant`) the first time it ships and keeps the wire image as
the host storage tier, so re-uploads after eviction pay neither the
quantization cost nor the fp32 byte count. The slot buffers hold the
*narrow* dtype (plus fp32 scale rows under int8); dequantization happens
on device inside the consuming kernel.

Upload discipline (DESIGN.md §6–7): every upload is *staged*, not applied —
``jax.device_put`` starts the host→device copy into a standalone staging
array (the second buffer set), and :meth:`commit` later splices the staged
rows into the slot buffers with donated in-place updates. Because the
splice produces a *new* functional value of ``bufs``, a kernel already
dispatched against the previous value keeps reading the weights it was
given — an in-flight upload can never alias a slot the executing kernel
reads. Demand-class misses (`ensure`) block only through the data
dependence of the kernel that consumes the committed buffers; the explicit
wall-clock fence of the PR-5 path survives behind ``fenced=True`` for
stats and the bit-identity smoke comparison.
"""
from __future__ import annotations

import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import quant

Key = Tuple[int, int]          # (moe_layer_idx, expert_idx)

EXPERT_WEIGHT_NAMES = ("w_gate", "w_up", "w_down")


def _moe_param_location(model, layer_idx: int):
    """-> ("prefix", i) | ("blocks", pos, g) for a MoE layer's param dict."""
    if layer_idx < model.n_prefix:
        return ("prefix", layer_idx)
    off = layer_idx - model.n_prefix
    return ("blocks", off % model.period, off // model.period)


def strip_expert_weights(params):
    """A copy of ``params`` with every routed-expert weight leaf removed
    (router and shared-expert weights stay device-resident — they are used
    by every token, so offloading them would only add latency)."""
    out = dict(params)
    if params.get("prefix"):
        out["prefix"] = [
            {**b, "moe": {k: v for k, v in b["moe"].items()
                          if k not in EXPERT_WEIGHT_NAMES}}
            if "moe" in b else b
            for b in params["prefix"]]
    if params.get("blocks"):
        out["blocks"] = [
            {**b, "moe": {k: v for k, v in b["moe"].items()
                          if k not in EXPERT_WEIGHT_NAMES}}
            if "moe" in b else b
            for b in params["blocks"]]
    return out


def slot_splice(buf, w, s):
    """``buf`` with row ``s`` replaced by ``w`` (the program
    ``jit_slot_splice``, donated: the slot buffer is updated in place)."""
    return jax.lax.dynamic_update_slice_in_dim(buf, w[None], s, 0)


class HostExpertStore:
    """Host-pinned full expert parameter set, keyed ``(moe_layer, expert)``.

    Extracts every MoE layer's stacked expert weights out of an initialized
    param tree into host numpy arrays (the paper's DRAM/SSD tier contents)
    and exposes :attr:`stripped_params` — the same tree with the expert
    leaves removed, which is what the serving step functions close over, so
    the device never holds more than the slot cache's ``n_slots`` experts.

    ``transfer_dtype`` selects the wire tier: :meth:`wire_expert` returns
    (and caches) the expert's wire image — the narrow weight leaves plus
    ``<name>_scale`` fp32 rows under int8 — and :attr:`wire_expert_bytes`
    is its exact byte count, the number every upload-accounting path and
    the simulator's transfer model share.
    """

    def __init__(self, model, params, *, transfer_dtype: str = "fp32"):
        if transfer_dtype not in quant.WIRE_DTYPES:
            raise ValueError(f"unknown transfer_dtype {transfer_dtype!r}; "
                             f"expected one of {quant.WIRE_DTYPES}")
        self.transfer_dtype = transfer_dtype
        self.n_moe = len(model.moe_layers)
        self.n_experts = model.cfg.moe.n_experts
        self._layers: List[Dict[str, np.ndarray]] = []
        for layer_idx in model.moe_layers:
            loc = _moe_param_location(model, layer_idx)
            if loc[0] == "prefix":
                moe_p = params["prefix"][loc[1]]["moe"]
                pick = {k: np.asarray(moe_p[k]) for k in EXPERT_WEIGHT_NAMES
                        if k in moe_p}
            else:
                _, pos, g = loc
                moe_p = params["blocks"][pos]["moe"]
                pick = {k: np.asarray(moe_p[k][g]) for k in EXPERT_WEIGHT_NAMES
                        if k in moe_p}
            self._layers.append(pick)                # each leaf: (E, …)
        self.names = tuple(self._layers[0]) if self._layers else ()
        self.stripped_params = strip_expert_weights(params)
        # dtype/shape of one expert's triple (slot-buffer layout)
        self.slot_shapes = {k: self._layers[0][k].shape[1:]
                            for k in self.names}
        self.dtypes = {k: self._layers[0][k].dtype for k in self.names}
        self.expert_bytes = int(sum(
            np.prod(self.slot_shapes[k]) * self.dtypes[k].itemsize
            for k in self.names))
        # wire tier: lazily quantized per-expert images (the storage tier
        # an evicted expert re-ships from) + the fixed wire layout
        self._wire: Dict[Key, Dict[str, np.ndarray]] = {}
        self.wire_dtypes = {
            k: quant.wire_np_dtype(transfer_dtype, self.dtypes[k])
            for k in self.names}
        self.wire_shapes = dict(self.slot_shapes)
        if transfer_dtype == "int8":
            for k in self.names:
                sk = quant.scale_name(k)
                self.wire_shapes[sk] = (self.slot_shapes[k][-1],)
                self.wire_dtypes[sk] = np.dtype(np.float32)
        self.wire_names = tuple(self.wire_shapes)
        self.wire_expert_bytes = int(sum(
            np.prod(self.wire_shapes[k]) * self.wire_dtypes[k].itemsize
            for k in self.wire_names))

    def expert(self, li: int, e: int) -> Dict[str, np.ndarray]:
        """Host views of one expert's fp32-master weight triple (no copy)."""
        return {k: v[e] for k, v in self._layers[li].items()}

    def wire_expert(self, li: int, e: int) -> Dict[str, np.ndarray]:
        """The expert's wire image in the configured transfer dtype
        (quantized once, then served from the host wire tier)."""
        if self.transfer_dtype == "fp32":
            return self.expert(li, e)
        key = (li, e)
        img = self._wire.get(key)
        if img is None:
            img = self._wire[key] = quant.quantize_expert(
                self.expert(li, e), self.transfer_dtype)
        return img

    def layer(self, li: int) -> Dict[str, np.ndarray]:
        return self._layers[li]


class ExpertSlotCache:
    """Fixed-shape device buffers of ``n_slots`` expert triples plus the
    ``(L, E) → slot`` routing table.

    Residency is reconciled with the OffloadEngine's GPU cache in two ways:
    :meth:`sync` (iteration boundary — the engine's admit/evict/prefetch
    verdicts become real async uploads/releases) and :meth:`ensure` (use
    time — a routed expert that is not resident is demand-uploaded, timed,
    and counted). Eviction victims for demand uploads come from the same
    cache policy object the simulator uses (Algorithm 2 by default), so the
    device cache never takes a replacement decision of its own.

    Double buffering: uploads land in :attr:`_staged` — per-slot dicts of
    standalone device arrays whose host→device copies start immediately —
    and become visible only when :meth:`commit` splices them into
    :attr:`bufs`. Bookkeeping (``slot_of``/``key_of``) updates at stage
    time, so `ensure`/`sync` treat staged experts as resident; the *math*
    only sees them once the consuming step's ``commit`` runs.
    """

    def __init__(self, store: HostExpertStore, n_slots: int, *,
                 fenced: bool = False, device=None):
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp
        self.store = store
        self.n_slots = int(n_slots)
        self.fenced = bool(fenced)
        # expert-parallel serving (DESIGN.md §8) runs one cache per mesh
        # device: pinning the buffers (and every staged upload) to ``device``
        # gives each shard its own independent host→device upload stream
        self.device = device
        self.bufs = {
            name: jnp.zeros((self.n_slots,) + store.wire_shapes[name],
                            store.wire_dtypes[name], device=device)
            for name in store.wire_names}
        self.slot_of = np.full((store.n_moe, store.n_experts), -1, np.int32)
        self.key_of: List[Optional[Key]] = [None] * self.n_slots
        self._free: List[int] = list(range(self.n_slots))
        # staged-but-uncommitted uploads: slot -> {name: device array}.
        # A plain dict (insertion-ordered); re-staging a reused slot
        # overwrites its pending rows, so commit never double-writes.
        self._staged: Dict[int, Dict[str, object]] = {}
        self._splice_fns = {
            name: jax.jit(slot_splice, donate_argnums=(0,))
            for name in store.wire_names}
        # stats (expert-granularity; the serving engine derives per-token
        # rates from these plus its token counters)
        self.hits = 0
        self.misses = 0
        self.demand_uploads = 0
        self.prefetch_uploads = 0
        self.evictions = 0
        self.upload_bytes = 0
        self.demand_stall_s = 0.0

    # -- residency ----------------------------------------------------------
    def __contains__(self, key: Key) -> bool:
        return self.slot_of[key[0], key[1]] >= 0

    @property
    def resident(self) -> List[Key]:
        return [k for k in self.key_of if k is not None]

    def table_row(self, li: int) -> np.ndarray:
        """(E,) expert→slot ids for one layer, clamped to valid slots.
        Non-resident experts point at slot 0: their gathered weights are
        garbage, which is safe — an expert is only *gathered into compute
        that matters* when a real token routes to it, and `ensure` makes
        exactly those experts resident before the expert GEMM runs."""
        return np.maximum(self.slot_of[li], 0).astype(np.int32)

    # -- movement -----------------------------------------------------------
    def _stage(self, key: Key) -> None:
        """Claim a free slot for ``key`` and start its host→device copies
        into the staging set (no mutation of ``bufs`` — the in-flight
        kernels keep the weights they were dispatched with). The copies'
        issue is the ``slots.upload`` span (metadata ``layer``, ``bytes``)."""
        slot = self._free.pop()
        with TraceAnnotation("slots.upload", layer=key[0],
                             bytes=self.store.wire_expert_bytes):
            w = self.store.wire_expert(*key)
            self._staged[slot] = {
                name: self._jax.device_put(arr, self.device)
                for name, arr in w.items()}
        self.slot_of[key[0], key[1]] = slot
        self.key_of[slot] = key
        self.upload_bytes += self.store.wire_expert_bytes

    def commit(self):
        """Splice every staged upload into the slot buffers (donated
        in-place updates) and return the new ``bufs``. The returned value
        is what the next consuming kernel must be dispatched with; any
        kernel still executing against the previous ``bufs`` value is
        untouched (functional no-alias guarantee)."""
        if self._staged:
            with TraceAnnotation("slots.commit", rows=len(self._staged)):
                for slot, rows in self._staged.items():
                    for name, arr in rows.items():
                        self.bufs[name] = self._splice_fns[name](
                            self.bufs[name], arr, slot)
                self._staged.clear()
        return self.bufs

    def evict(self, key: Key) -> None:
        slot = int(self.slot_of[key[0], key[1]])
        if slot < 0:
            return
        self.slot_of[key[0], key[1]] = -1
        self.key_of[slot] = None
        self._free.append(slot)
        self._staged.pop(slot, None)   # staged-then-evicted: drop the copy
        self.evictions += 1

    def fence(self) -> None:
        """Commit and block until every in-flight slot upload has landed."""
        self.commit()
        for buf in self.bufs.values():
            self._jax.block_until_ready(buf)

    # -- the two reconciliation paths ---------------------------------------
    def sync(self, target_keys: Iterable[Key]) -> int:
        """Reconcile device residency with the offload engine's GPU-cache
        verdicts (iteration boundary). Async: no fence — the uploads overlap
        in-flight compute and the next consuming step fences by data
        dependence. Returns the number of prefetch-class uploads issued."""
        target = set(target_keys)
        for key in self.resident:
            if key not in target:
                self.evict(key)
        return self.prefetch(sorted(target))

    def prefetch(self, keys: Iterable[Key]) -> int:
        """Stage prefetch-class uploads for every non-resident key that
        still has a free slot (never evicts — prefetches are advisory).
        Returns the number staged."""
        n = 0
        for key in keys:
            if key not in self and self._free:
                self._stage(key)
                self.prefetch_uploads += 1
                n += 1
        return n

    def ensure(self, keys: Sequence[Key], victim_fn=None) -> int:
        """Make ``keys`` (this layer's routed experts) resident *now*.
        Misses are demand uploads; victims — when the cache is full — come
        from ``victim_fn(resident, protected)``, the engine's cache-policy
        verdict. Returns the number of misses.

        Measurement note: in the default double-buffered mode the staged
        copies block the host only for the ``device_put`` issue cost —
        ``demand_stall_s`` counts that issue time, and the remaining
        transfer latency is absorbed by the data dependence of the post
        kernel that consumes the committed buffers. With ``fenced=True``
        (the PR-5 schedule) the miss additionally blocks through an
        explicit fence, so ``demand_stall_s`` is the full wall time the
        step stalled at the miss point — including any still-in-flight
        prefetch uploads the demand copy queued behind, like a demand read
        behind issued copies on a real link."""
        missing = [k for k in keys if k not in self]
        layer = keys[0][0] if keys else -1
        with TraceAnnotation("slots.ensure", layer=layer,
                             misses=len(missing)):
            self.hits += len(keys) - len(missing)
            self.misses += len(missing)
            if not missing:
                return 0
            t0 = time.perf_counter()
            protected = frozenset(keys)
            for key in missing:
                if not self._free:
                    victim = victim_fn(self.resident, protected) \
                        if victim_fn else next(
                            k for k in self.key_of if k not in protected)
                    if victim is None or victim in protected:
                        raise RuntimeError(
                            f"expert slot cache too small: {self.n_slots} "
                            f"slots cannot hold one layer's {len(keys)} "
                            "routed experts")
                    self.evict(victim)
                self._stage(key)
                self.demand_uploads += 1
            if self.fenced:
                self.fence()
            self.demand_stall_s += time.perf_counter() - t0
            return len(missing)

    def stats(self) -> dict:
        return {
            "weight_slots": self.n_slots,
            "slot_hits": self.hits,
            "slot_misses": self.misses,
            "demand_uploads": self.demand_uploads,
            "prefetch_uploads": self.prefetch_uploads,
            "slot_evictions": self.evictions,
            "upload_bytes": self.upload_bytes,
            "demand_stall_s": self.demand_stall_s,
            "transfer_dtype": self.store.transfer_dtype,
            "wire_expert_bytes": self.store.wire_expert_bytes,
        }
