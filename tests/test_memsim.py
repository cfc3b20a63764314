"""Multi-tier memory / link simulator semantics (§5.3)."""
import pytest

from repro.core.memsim import GPU, DRAM, HWConfig, Link, MemSim

HW = HWConfig(dram_to_dev_gbps=10.0, ssd_to_dram_gbps=1.0)
MB100 = 100_000_000  # 0.01 s on the 10 GB/s link, 0.1 s on the 1 GB/s link


def _sim(**kw):
    return MemSim(HW, expert_bytes=MB100, **kw)


def test_priority_order_and_resubmission():
    link = Link(10.0)
    link.submit("a", 0.1, 1)
    link.submit("b", 0.5, 1)
    link.submit("c", 0.3, 1)
    link.submit("a", 0.9, 1)   # resubmission updates priority
    order = [link._pop()[0] for _ in range(3)]
    assert order == ["a", "b", "c"]
    assert link._pop() is None


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reprioritised_queue_stays_bounded_and_pops_in_order(seed):
    """A server re-prioritises its prefetch queue every layer; the dead
    entries this leaves must not pile up, and the pops must be those of a
    plain (priority, submission order) sort of the live requests."""
    import random
    rng = random.Random(seed)
    link = Link(10.0)
    live = {}                                   # key -> (priority, order)
    for step in range(5000):
        key = rng.randrange(48)
        op = rng.random()
        if op < 0.85:
            p = rng.random()
            link.submit(key, p, 1)
            live[key] = (p, step)
        elif op < 0.97:
            link.cancel(key)
            live.pop(key, None)
        else:
            link.clear()
            live.clear()
        assert len(link._heap) <= 2 * link.queue_len() + 65
    want = [k for k, _ in sorted(live.items(),
                                 key=lambda kv: (-kv[1][0], kv[1][1]))]
    got = []
    while (nxt := link._pop()) is not None:
        got.append(nxt[0])
    assert got == want


def test_clear_empties_the_heap():
    link = Link(10.0)
    for e in range(10):
        link.submit(e, 0.1 * e, 1)
    link.clear()
    assert link._heap == [] and link._pop() is None


def test_demand_fetch_from_dram_takes_transfer_time():
    sim = _sim()
    sim.in_dram.add(("l", 0))
    stall = sim.demand_fetch(("l", 0))
    assert stall == pytest.approx(0.01, rel=1e-6)
    assert ("l", 0) in sim.on_gpu


def test_demand_fetch_from_ssd_pipelines_tiers():
    sim = _sim()
    stall = sim.demand_fetch(("l", 1))
    assert stall == pytest.approx(0.1 + 0.01, rel=1e-6)
    assert ("l", 1) in sim.in_dram and ("l", 1) in sim.on_gpu


def test_prefetch_overlaps_with_compute():
    sim = _sim()
    sim.in_dram.add(("l", 2))
    sim.submit_prefetch(("l", 2), 0.5)
    sim.advance(0.02)          # compute long enough to cover the transfer
    assert ("l", 2) in sim.on_gpu
    assert sim.demand_fetch(("l", 2)) == 0.0


def test_demand_jumps_prefetch_queue():
    sim = _sim()
    for e in range(8):
        sim.in_dram.add(("l", e))
        sim.submit_prefetch(("l", e), 0.1 + 0.01 * e)
    # queue holds 8 transfers = 80 ms; a demand for the LAST one must not
    # wait for the other 7 (only for any in-flight transfer)
    stall = sim.demand_fetch(("l", 0))
    assert stall <= 0.01 + 0.01 + 1e-9


def test_single_worker_serializes_one_link():
    sim = _sim()
    sim.in_dram.update({("l", 0), ("l", 1)})
    sim.submit_prefetch(("l", 0), 1.0)
    sim.submit_prefetch(("l", 1), 0.9)
    sim.advance(0.015)  # one and a half transfers
    assert (("l", 0) in sim.on_gpu) and (("l", 1) not in sim.on_gpu)
    sim.advance(0.01)
    assert ("l", 1) in sim.on_gpu


def test_ssd_and_pcie_links_work_in_parallel():
    sim = _sim()
    sim.in_dram.add(("a", 0))
    sim.submit_prefetch(("a", 0), 1.0)   # PCIe 10 ms
    sim.submit_prefetch(("b", 0), 0.9)   # SSD 100 ms then PCIe
    sim.advance(0.1 + 0.0101)
    assert ("a", 0) in sim.on_gpu
    assert ("b", 0) in sim.on_gpu        # pipelined through both tiers


def test_duplicate_prefetch_skipped():
    sim = _sim()
    sim.on_gpu.add(("l", 3))
    sim.submit_prefetch(("l", 3), 1.0)
    sim.advance(1.0)
    assert sim.gpu_link.n_transfers == 0


def test_clear_queues_keeps_inflight():
    sim = _sim()
    sim.in_dram.update({("a", 0), ("b", 0)})
    sim.submit_prefetch(("a", 0), 1.0)
    sim.submit_prefetch(("b", 0), 0.9)
    sim.advance(0.001)   # "a" goes in flight
    sim.clear_queues()
    sim.advance(0.05)
    assert ("a", 0) in sim.on_gpu      # in-flight completes
    assert ("b", 0) not in sim.on_gpu  # queued was dropped


def test_admission_veto_drops_prefetch_not_demand():
    vetoed = []

    def admit(key, tier, pr):
        vetoed.append(key)
        return False

    sim = MemSim(HW, expert_bytes=MB100, admit=admit)
    sim.in_dram.add(("x", 0))
    sim.submit_prefetch(("x", 0), 0.2)
    sim.advance(0.1)
    assert ("x", 0) not in sim.on_gpu and vetoed  # prefetch vetoed
    stall = sim.demand_fetch(("x", 0))            # demand bypasses admit
    assert ("x", 0) in sim.on_gpu and stall > 0


def test_stats_accumulate():
    sim = _sim()
    sim.in_dram.add(("l", 0))
    sim.demand_fetch(("l", 0))
    assert sim.demand_fetches == 1
    assert sim.stall_time > 0
    assert sim.gpu_link.bytes_moved == MB100
