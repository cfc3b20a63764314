"""Serving launcher: wires a (possibly sharded) model + the offload engine
into an open-loop request loop. On a CPU it runs ``--reduced`` configs end
to end; on a TPU the same entry point serves full-width configs
(``chip_smoke.py`` drives it through :func:`build`, :func:`run` and
:func:`report`, the three steps of :func:`main`).

Requests arrive per a Poisson process with per-request (ragged) prompt
lengths and token budgets; the slot-pool ``JaxModelServer`` admits them at
token boundaries through the continuous scheduler (``--policy`` selects
prefill-priority, decode-priority, or stall-aware admission) and recycles
batch slots on completion — no lockstep batching, no recompiles after
warmup.

The EAMC can be built three ways (DESIGN.md §4): offline from a warmup
dataset pass (the default), cold-start empty with online learning
(``--eamc-online``), or warm-restarted from a previous run's persisted
collection (``--eamc-path``; the file is rewritten at exit, so back-to-back
invocations keep learning across restarts).

Multi-tenant serving (DESIGN.md §11): ``--tenants spec.json`` loads a
TenantSpec list (or a full ServeSpec document) — each tenant may carry a
private predictor namespace with its own ``.npz`` persistence, an SLA
class consumed by the stall-policy admission tiers, a per-tenant stall
budget, and a GPU-slot quota. Requests are assigned to tenants by a
seeded draw weighted by each tenant's ``rps``; the report gains one line
per tenant and private predictor state is rewritten at exit.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-235b-a22b \
        --reduced --requests 8 --eamc-online --eamc-path /tmp/eamc
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass, field, replace

import jax
import numpy as np

from repro.configs import get_config
from repro.core.eam import EAMC
from repro.core.memsim import hw_for_device_kind
from repro.core.predictor import LearnedPredictor
from repro.core.tracer import build_eamc
from repro.models import Model
from repro.serving import EngineConfig, SchedulerConfig, TenantSpec
from repro.serving.spec import SLA_CLASSES, load_tenants
from repro.serving.engine import JaxModelServer
from repro.serving.guard import recompile_guard
from repro.serving.request import Request
from repro.serving.workload import poisson_arrivals
from repro.train.data import DataConfig, TokenStream


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    left to JAX; otherwise the cache lives at ``<repo>/.jax_cache``. The
    directory must not move between runs, or nothing is ever found again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-235b-a22b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--rps", type=float, default=2.0,
                    help="open-loop Poisson arrival rate (virtual-clock)")
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="max prompt length; requests draw ragged lengths "
                         "from [max(4, len//2), len]")
    ap.add_argument("--max-new", type=int, default=8,
                    help="max token budget; per-request budgets are ragged")
    ap.add_argument("--slots", type=int, default=4,
                    help="slot-pool capacity (fixed decode batch shape)")
    ap.add_argument("--policy", default="prefill",
                    choices=["prefill", "decode", "stall"],
                    help="continuous-admission policy")
    ap.add_argument("--gpu-cache", type=int, default=4)
    ap.add_argument("--dram-cache", type=int, default=8,
                    help="host-DRAM cache slots; experts beyond it are "
                         "SSD-resident and pay the NVMe hop on a miss")
    ap.add_argument("--resident-fraction", type=float, default=1.0,
                    help="fraction of the L×E expert set held in device "
                         "weight slots. 1.0 (default) keeps every expert "
                         "resident (fused step); < 1.0 streams real expert "
                         "weights through the slot cache, with the offload "
                         "engine's verdicts driving actual uploads")
    ap.add_argument("--weight-slots", type=int, default=None,
                    help="explicit device expert-slot count (overrides "
                         "--resident-fraction)")
    ap.add_argument("--transfer-dtype", default="fp32",
                    choices=["fp32", "fp16", "int8"],
                    help="expert wire dtype: what the slot cache ships and "
                         "the simulator charges per transfer (int8 adds "
                         "per-output-channel fp32 scales; dequant happens "
                         "on device in the consuming kernel)")
    ap.add_argument("--fenced-uploads", action="store_true",
                    help="restore the PR-5 slot-cache schedule: all "
                         "prefetch uploads at the iteration boundary and a "
                         "wall-clock fence on every demand miss (default "
                         "is the double-buffered overlap schedule)")
    ap.add_argument("--ssd-gbps", type=float, default=None,
                    help="SSD→DRAM bandwidth in GB/s (e.g. 3.5 for a "
                         "consumer NVMe; 'inf' disables the SSD tier)")
    ap.add_argument("--ssd-iops", type=float, default=0.0,
                    help="NVMe read IOPS: each SSD read pays 1/iops s "
                         "setup on top of the bandwidth term (0 = ideal)")
    ap.add_argument("--dram-gbps", type=float, default=None,
                    help="DRAM→device link bandwidth in GB/s (the paper's "
                         "PCIe sweep, Figure 10; default: the device "
                         "kind's simulator preset)")
    ap.add_argument("--gpu-links", type=int, default=1,
                    help="parallel DRAM→device upload links the simulator "
                         "charges transfers against (§7)")
    ap.add_argument("--record-drift", action="store_true",
                    help="record per-iteration router drift stats (adds "
                         "host-side bookkeeping; off on the measured path)")
    ap.add_argument("--eamc-capacity", type=int, default=8)
    ap.add_argument("--eamc-online", action="store_true",
                    help="learn the EAMC from served traffic instead of the "
                         "offline warmup pass; without --eamc-path the "
                         "collection starts empty (cold start)")
    ap.add_argument("--eamc-drift-threshold", type=float, default=0.6,
                    help="EWMA match-distance threshold that declares "
                         "workload drift and triggers an online EAMC "
                         "rebuild (only with --eamc-online)")
    ap.add_argument("--eamc-drift-min-seqs", type=int, default=8,
                    help="completed sequences required before (and "
                         "between) drift-triggered EAMC rebuilds")
    ap.add_argument("--eamc-path", default=None,
                    help="persisted EAMC (.npz): loaded at startup when the "
                         "file exists (warm restart) and rewritten at exit")
    ap.add_argument("--predictor", default="eamc",
                    choices=["eamc", "learned", "hybrid"],
                    help="prediction brain behind cache scoring, prefetch "
                         "priorities, stall admission, and placement "
                         "(DESIGN.md §10): the EAMC trace matcher "
                         "(default, the paper's behavior), the online "
                         "learned bigram/marginal model, or the hybrid "
                         "that trace-matches while the match is good")
    ap.add_argument("--predictor-path", default=None,
                    help="persisted learned-predictor state (.npz, "
                         "learned/hybrid only): loaded at startup when the "
                         "file exists (warm restart) and rewritten at exit "
                         "— the learned-brain counterpart of --eamc-path")
    ap.add_argument("--devices", type=int, default=1,
                    help="expert-parallel degree (DESIGN.md §8): shard "
                         "experts over D mesh devices with one slot cache "
                         "and upload link each, all-to-all MoE dispatch, "
                         "and EAMC-guided placement. On a CPU host, forced "
                         "host devices are configured automatically")
    ap.add_argument("--tenants", default=None,
                    help="multi-tenant spec JSON (a TenantSpec list or a "
                         "full ServeSpec document, DESIGN.md §11): "
                         "per-tenant predictor namespaces with their own "
                         ".npz persistence, SLA classes, stall budgets, "
                         "and GPU-slot quotas")
    ap.add_argument("--sla-class", default=None, choices=list(SLA_CLASSES),
                    help="override: tag every request (and every tenant "
                         "from --tenants) with this SLA class; the stall "
                         "policy admits interactive < standard < batch, "
                         "with aging so batch never starves")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


@dataclass
class Served:
    """One built server with its submitted requests (see :func:`build`)."""
    args: argparse.Namespace
    cfg: object
    model: Model
    srv: JaxModelServer
    reqs: list
    eamc: EAMC
    eamc_source: str
    predictor_source: str
    tenants: tuple
    tokens: dict = field(default_factory=dict)   # rid -> generated tokens
    drain_s: float = 0.0                         # host clock, see run()


def _build_eamc(args, model, params, dataset):
    """-> (EAMC, source): warm-restarted, cold, or built offline from a
    forward pass over the warmup dataset."""
    if args.eamc_path and os.path.exists(EAMC._resolve_path(args.eamc_path)):
        eamc = EAMC.load(args.eamc_path)
        eamc.capacity = max(eamc.capacity, args.eamc_capacity)
        return eamc, "load"
    if args.eamc_online:
        # cold start: no oracle-peek warmup pass — the engine learns the
        # collection from its own traffic
        return EAMC(capacity=args.eamc_capacity), "cold"
    fwd = jax.jit(lambda p, b: model.forward(p, b)[1]["counts"])

    def run_fn(seq):
        return np.asarray(fwd(params, {"tokens": seq[None]}))[:, 0, :]
    return build_eamc(run_fn, dataset, capacity=args.eamc_capacity), \
        "offline"


def build(args: argparse.Namespace) -> Served:
    """Initialize the model, build the EAMC and the server, and submit the
    open-loop requests. Nothing is served yet (see :func:`run`)."""
    # TenantSpec is rebuilt field-by-field here so every spec knob is
    # constructor-plumbed from launch code (config-drift R5) and the
    # --sla-class override applies uniformly
    tenants = ()
    if args.tenants:
        tenants = tuple(
            TenantSpec(tenant_id=t.tenant_id,
                       sla_class=args.sla_class or t.sla_class,
                       predictor=t.predictor,
                       stall_budget=t.stall_budget,
                       gpu_slot_quota=t.gpu_slot_quota,
                       shared_fallback=t.shared_fallback,
                       tasks=t.tasks,
                       rps=t.rps)
            for t in load_tenants(args.tenants))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.moe is None:
        raise SystemExit(f"{args.arch} has no routed MoE; expert offloading "
                         "degenerates to layer streaming (see DESIGN.md §5). "
                         "Pick an MoE arch for this launcher.")
    model = Model(cfg)
    # jitted, init writes each stacked leaf once; eagerly it holds every
    # per-layer piece beside the stack, twice the expert set at its peak
    params = jax.jit(model.init)(jax.random.PRNGKey(0))

    data = TokenStream(DataConfig(vocab=cfg.vocab,
                                  seq_len=args.prompt_len + 4, batch=1))
    dataset = [b["tokens"][0] for b in data.batches(max(10, args.requests))]
    eamc, eamc_source = _build_eamc(args, model, params, dataset)

    hw = hw_for_device_kind(jax.devices()[0].device_kind)
    if args.ssd_gbps is not None or args.ssd_iops:
        hw = replace(hw,
                     ssd_to_dram_gbps=(args.ssd_gbps if args.ssd_gbps
                                       is not None else hw.ssd_to_dram_gbps),
                     ssd_iops=args.ssd_iops)
    if args.dram_gbps is not None:
        hw = replace(hw, dram_to_dev_gbps=args.dram_gbps)
    srv = JaxModelServer(
        EngineConfig(arch=cfg, gpu_cache_experts=args.gpu_cache,
                     dram_cache_experts=args.dram_cache, hw=hw,
                     scheduler=SchedulerConfig(max_batch=args.slots,
                                               policy=args.policy),
                     keep_request_eams=False,
                     record_drift=args.record_drift,
                     n_gpu_links=args.gpu_links,
                     eamc_online=args.eamc_online,
                     eamc_drift_threshold=args.eamc_drift_threshold,
                     eamc_drift_min_seqs=args.eamc_drift_min_seqs,
                     resident_fraction=args.resident_fraction,
                     n_weight_slots=args.weight_slots,
                     transfer_dtype=args.transfer_dtype,
                     fenced_uploads=args.fenced_uploads,
                     n_devices=args.devices,
                     predictor=args.predictor,
                     tenants=tenants),
        model, params, eamc=eamc,
        cache_len=args.prompt_len + args.max_new)
    # streamed mode: the host store now holds the experts and the server
    # only the stripped tree, so dropping this last reference frees the
    # full tree's HBM — the slot buffers are the only experts on device
    del params

    # learned-predictor warm restart (the --eamc-path pattern): the engine
    # already constructed the brain from the config; persisted model state
    # streams into it in place
    # eamc brains inherit the collection's provenance; learned state is
    # cold unless --predictor-path warm-restarts it below
    predictor_source = eamc_source if args.predictor == "eamc" else "cold"
    if args.predictor_path and args.predictor in ("learned", "hybrid"):
        lp_path = LearnedPredictor._resolve_path(args.predictor_path)
        if os.path.exists(lp_path):
            srv.offload.predictor.load_state(args.predictor_path)
            predictor_source = "load"

    # open loop: every request is submitted up front with its Poisson
    # arrival timestamp; the engine's virtual clock drives admission
    rng = np.random.default_rng(args.seed)
    arrivals = poisson_arrivals(args.requests, rps=args.rps, seed=args.seed)
    # tenant assignment draws from a separate stream so prompts/budgets are
    # identical with and without --tenants (isolates the tenancy effect)
    trng = np.random.default_rng(args.seed + 1)
    weights = None
    if tenants:
        weights = np.array([max(float(t.rps), 0.0) for t in tenants])
        if weights.sum() <= 0:
            weights = np.ones(len(tenants))
        weights = weights / weights.sum()
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(max(4, args.prompt_len // 2),
                                args.prompt_len + 1))
        budget = int(rng.integers(max(2, args.max_new // 2),
                                  args.max_new + 1))
        prompt = np.asarray(dataset[i % len(dataset)][:plen], np.int32)
        r = Request(rid=i, arrival=float(arrivals[i]), prompt=prompt,
                    max_new_tokens=budget)
        if tenants:
            t = tenants[int(trng.choice(len(tenants), p=weights))]
            r.tenant_id = t.tenant_id
            r.sla_class = t.sla_class
        elif args.sla_class:
            r.sla_class = args.sla_class
        reqs.append(r)
        srv.submit(r)
    return Served(args=args, cfg=cfg, model=model, srv=srv, reqs=reqs,
                  eamc=eamc, eamc_source=eamc_source,
                  predictor_source=predictor_source, tenants=tenants)


def run(served: Served) -> None:
    """Serve every submitted request to completion. ``drain_s`` is host
    wall time up to the point where every device computation and upload
    has finished (compiles included)."""
    srv = served.srv
    t0 = time.perf_counter()
    # every jit entry (decode step, each prefill bucket, slot splices) may
    # trace exactly once across the whole run; a steady-state retrace
    # raises RecompileError instead of silently stalling the pipeline
    with recompile_guard(srv, max_traces_per_key=1):
        srv.drain()
    jax.block_until_ready(jax.live_arrays())
    served.drain_s = time.perf_counter() - t0
    served.tokens = {r.rid: srv.generated.pop(r.rid) for r in served.reqs}


def report(served: Served) -> None:
    """Print the run report. Latencies tagged ``sim-`` are modelled on the
    simulator's virtual clock, not measured; ``wall:`` is the host clock."""
    args, srv, reqs, cfg = served.args, served.srv, served.reqs, served.cfg
    tenants = served.tenants
    print(f"guard: zero-recompile ok (keys={len(srv.compile_counts)})")
    print(f"wall: drain={served.drain_s:.3f}s (host clock, compiles "
          "included)")
    stats = srv.stats()
    for r in reqs:
        toks = served.tokens[r.rid]
        print(f"req {r.rid}: prompt={r.prompt_len} new={len(toks)} "
              f"sim-slotwait={r.queue_delay*1e3:.1f}ms "
              f"sim-e2e={r.latency*1e3:.1f}ms "
              f"sim-tok-lat={r.per_token_latency*1e3:.2f}ms "
              f"toks={','.join(str(t) for t in toks)}")
    e2e = np.mean([r.latency for r in reqs])
    print(f"total: {args.requests} requests, policy={args.policy}, "
          f"hit={stats['gpu_hit_ratio']:.3f}, "
          f"sim-mean-tok-lat={stats['mean_token_latency']*1e3:.2f}ms, "
          f"sim-mean-e2e={e2e*1e3:.1f}ms, "
          f"compiles={dict(srv.compile_counts)}")
    print(f"tiers: demand dram={stats['demand_from_dram']} "
          f"ssd={stats['demand_from_ssd']} "
          f"staged={stats['staged_prefetches']}, "
          f"pcie={stats['pcie_bytes']/1e6:.1f}MB "
          f"(demand {stats['pcie_demand_bytes']/1e6:.1f}), "
          f"ssd={stats['ssd_bytes']/1e6:.1f}MB "
          f"(demand {stats['ssd_demand_bytes']/1e6:.1f}), "
          f"sim-miss-cost dram={stats['miss_cost_dram']*1e3:.2f}ms "
          f"ssd={stats['miss_cost_ssd']*1e3:.2f}ms")
    if srv.slot_runtime is not None:
        n_moe = len(served.model.moe_layers)
        total = n_moe * cfg.moe.n_experts
        # overlapped uploads block the host only to issue the device_put;
        # the fenced schedule waits for each demand upload to land
        demand = "demand-stall" if args.fenced_uploads else "demand-issue"
        print(f"slots: resident={stats['weight_slots']}/{total} "
              f"hit-ratio={stats['slot_hit_ratio']:.3f} "
              f"hits={stats['slot_hits']} misses={stats['slot_misses']} "
              f"demand-uploads={stats['demand_uploads']} "
              f"prefetch-uploads={stats['prefetch_uploads']} "
              f"evictions={stats['slot_evictions']} "
              f"uploaded={stats['upload_bytes']/1e6:.1f}MB "
              f"{demand}={stats['demand_stall_s']*1e3:.1f}ms "
              f"({stats['demand_stall_per_token_s']*1e3:.2f}ms/token) "
              f"wire={stats['transfer_dtype']} "
              f"({stats['wire_expert_bytes']}B/expert, "
              f"sim={stats['sim_expert_bytes']}B) "
              f"schedule={'fenced' if args.fenced_uploads else 'overlap'}")
    else:
        print("slots: all-resident (resident-fraction 1.0)")
    if args.devices > 1:
        links = stats["gpu_link_stats"]
        util = " ".join(f"{l['utilization']:.3f}" for l in links)
        busy = " ".join(f"{l['busy_s']*1e3:.1f}" for l in links)
        print(f"devices: D={args.devices} links={stats['n_gpu_links']} "
              f"sim-link-util=[{util}] sim-link-busy-ms=[{busy}] "
              f"rebalances={stats['placement_rebalances']} "
              f"migrations={stats['placement_migrations']} "
              f"replicated={stats['replicated_experts']}")
    learned = stats["eamc_online_inserts"] + stats["eamc_online_merges"]
    print(f"eamc: source={served.eamc_source} "
          f"entries={stats['eamc_entries']} "
          f"learned={learned} "
          f"(insert={stats['eamc_online_inserts']} "
          f"merge={stats['eamc_online_merges']}) "
          f"recon={stats['eamc_reconstructions']} "
          f"mean-dist={stats['eamc_mean_match_distance']:.3f}")
    print(f"predictor: kind={stats['predictor']} "
          f"source={served.predictor_source} "
          f"seqs={stats.get('predictor_seqs_trained', 0)}")
    if tenants:
        tstats = stats.get("tenants", {})
        by_tenant = {}
        for r in reqs:
            by_tenant.setdefault(r.tenant_id, []).append(r)
        defs = getattr(srv._sched, "deferrals_by_tenant", {})
        for t in tenants:
            ts = tstats.get(t.tenant_id, {})
            rs = by_tenant.get(t.tenant_id, [])
            p99 = (float(np.percentile([r.latency for r in rs], 99))
                   if rs else 0.0)
            print(f"tenant {t.tenant_id}: sla={t.sla_class} n={len(rs)} "
                  f"hit={ts.get('gpu_hit_ratio', 0.0):.3f} "
                  f"sim-p99={p99*1e3:.1f}ms "
                  f"deferrals={defs.get(t.tenant_id, 0)} "
                  f"slots={ts.get('gpu_slots_owned', 0)}"
                  f"{'/' + str(t.gpu_slot_quota) if t.gpu_slot_quota else ''} "
                  f"stall={ts.get('demand_stall_s', 0.0)*1e3:.1f}ms "
                  f"pred={ts.get('predictor_kind', 'shared')} "
                  f"src={ts.get('predictor_source', '-')} "
                  f"seqs={ts.get('predictor_seqs', 0)}")
        for tid, saved in srv.offload.save_tenant_state().items():
            print(f"tenant {tid}: saved predictor -> {saved}")
    if args.eamc_path:
        saved = served.eamc.save(args.eamc_path)
        print(f"eamc: saved {stats['eamc_entries']} entries -> {saved}")
    if args.predictor_path and args.predictor in ("learned", "hybrid"):
        saved = srv.offload.predictor.save(args.predictor_path)
        print(f"predictor: saved seqs="
              f"{stats.get('predictor_seqs_trained', 0)} -> {saved}")


def main(argv=None):
    args = parse_args(argv)
    if args.devices > 1:
        # must happen before the first jax device use: force enough host
        # devices for the expert mesh on a CPU host (the dryrun launcher's
        # pattern; accelerator backends ignore it). A user-supplied count
        # in XLA_FLAGS wins.
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
    init_compile_cache()
    served = build(args)
    run(served)
    report(served)


if __name__ == "__main__":
    main()
