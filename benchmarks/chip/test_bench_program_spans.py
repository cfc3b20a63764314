"""The readers of the program's own spans and named programs, on small
hand-made traces: the slot runtime's reads of device values
(``runtime.read.*``), the slot cache's host bookkeeping and the MoE
``post`` programs' roofline share. A trace of a program without these
spans or names (the benchmark over an older program) reads nothing."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chip import spec, work  # noqa: E402
from chip import tracefile as tf  # noqa: E402
from chip.tracefile import Event, Trace  # noqa: E402

CONFIG = {"arch": {"n_layers": 4, "d_model": 8, "n_heads": 2,
                   "n_kv_heads": 2, "head_dim": 4, "d_ff": 16, "vocab": 32,
                   "act": "gelu", "dtype": "bfloat16",
                   "moe": {"n_experts": 4, "top_k": 1, "d_expert": 16,
                           "moe_layer_period": 2, "moe_layer_offset": 1}}}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
NAMES = ("host_read_ms_per_token.b1", "read_idle_ms_per_token.b1",
         "host_reads_per_token.b1", "slot_host_ms_per_token.b1",
         "moe_post_roofline.b1")


def ev(name, t0, t1, **stats):
    return Event(name, float(t0), float(t1), tuple(stats.items()))


def program_trace():
    """Two decode steps of 100 ns and a prefill step. Host: the program's
    spans inside the step spans. Device: the programs those steps ran,
    with a sharded ``post`` that the roofline must not count."""
    host = [
        ev("bench.step.decode", 0, 100),
        ev("runtime.sync", 2, 8), ev("runtime.stage", 4, 6, layer=0),
        ev("runtime.read.route", 10, 20, layer=0),
        ev("slots.ensure", 21, 25, layer=0, misses=1),
        ev("slots.commit", 26, 28, rows=1),
        ev("runtime.read.counts", 30, 45, layer=0),
        ev("runtime.read.token", 80, 90),
        ev("bench.step.decode", 100, 200),
        ev("runtime.sync", 101, 103),
        ev("runtime.read.route", 110, 115, layer=0),
        ev("slots.ensure", 116, 120, layer=0, misses=0),
        ev("runtime.stage", 130, 134, layer=1),
        ev("runtime.read.counts", 140, 160, layer=0),
        ev("runtime.read.token", 185, 195),
        ev("bench.step.prefill", 200, 300),           # never counted
        ev("runtime.read.route", 210, 230, layer=0),
        ev("slots.ensure", 231, 240, layer=0, misses=3),
        ev("runtime.read.token", 280, 290),
    ]
    device = [
        ev("jit_slot_decode_pre(2)", 12, 18),
        ev("jit_slot_decode_post(3)", 29, 40),
        ev("jit_slot_tail(4)", 70, 85),
        ev("jit_slot_decode_post(3)", 120, 150),
        ev("jit_slot_decode_post_sharded(7)", 150, 158),
        ev("jit_slot_prefill_post(5)", 232, 260),
        ev("jit_slot_decode_post(3)", 300, 320),      # after the steps
    ]
    tr = Trace(device=device, host=host)
    for lst in (tr.device, tr.host):
        lst.sort(key=lambda e: (e.t0, -e.t1))
    return tr


def context(trace, config=CONFIG, peak=PEAK):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run
    return run.Context(config, peak, None, trace, tf.window(trace))


def reader(name):
    return spec.load_metric_reader(name)


def test_host_reads_per_decode_step():
    ctx = context(program_trace())
    # route, counts and token in each decode step; prefill's are left out
    assert reader("host_reads_per_token.b1")(ctx) == 3.0
    # (10 + 15 + 10) + (5 + 20 + 10) ns over two steps
    assert reader("host_read_ms_per_token.b1")(ctx) == pytest.approx(35e-6)


def test_device_idle_inside_reads():
    ctx = context(program_trace())
    # step 1: route 10 - 6, counts 15 - 10, token 10 - 5 (the tail ends
    # inside it); step 2: route 5, counts 20 - 18 (post, then the sharded
    # post: any program keeps the device busy), token 10
    assert reader("read_idle_ms_per_token.b1")(ctx) == pytest.approx(
        (4 + 5 + 5 + 5 + 2 + 10) / 2 * 1e-6)


def test_slot_host_time_is_the_union_of_nested_spans():
    ctx = context(program_trace())
    # step 1: sync 6 (stage inside it) + ensure 4 + commit 2; step 2: sync
    # 2 + ensure 4 + stage 4
    assert reader("slot_host_ms_per_token.b1")(ctx) == pytest.approx(
        (12 + 10) / 2 * 1e-6)


def test_moe_post_roofline_reads_only_the_decode_post_programs():
    ctx = context(program_trace())
    a = CONFIG["arch"]
    assert work.n_moe_layers(CONFIG) == 2 and work.ffn_mats(CONFIG) == 2
    weights = 2 * 1 * 2 * a["d_model"] * a["moe"]["d_expert"]
    least = 2 * weights * 2 / PEAK["hbm_bytes_per_s"]     # two steps, bf16
    # post [29,40] and [120,150] inside the steps: 41 ns; the sharded post,
    # the prefill post and the post after the steps are left out
    assert reader("moe_post_roofline.b1")(ctx) == pytest.approx(
        100 * least / 41e-9)


@pytest.mark.parametrize("name,match", [
    ("jit_slot_decode_post", True), ("jit_slot_decode_post(12)", True),
    ("jit_slot_decode_post.3", True), ("jit_slot_decode_post_sharded(1)",
                                       False),
    ("jit_slot_decode_pre(1)", False), ("jit_impl(2)", False)])
def test_roofline_finds_the_post_program_by_name(name, match):
    tr = Trace(host=[ev("bench.step.decode", 0, 100)],
               device=[ev(name, 10, 20)])
    got = reader("moe_post_roofline.b1")(context(tr))
    assert (got is not None) == match


def test_a_program_without_spans_or_names_reads_nothing():
    """The benchmark's own spans and ``jit_impl`` programs, as the trace of
    a program from before the spans has them."""
    tr = Trace(host=[ev("bench.step.decode", 0, 100),
                     ev("bench.readback", 10, 20),
                     ev("bench.ensure", 21, 25),
                     ev("np.asarray(jax.Array)", 30, 45)],
               device=[ev("jit_impl(1)", 12, 40)])
    ctx = context(tr)
    for name in NAMES:
        assert reader(name)(ctx) is None, name


def test_nothing_to_read_without_device_peak_or_decode():
    tr = program_trace()
    no_device = context(Trace(host=tr.host))
    assert reader("read_idle_ms_per_token.b1")(no_device) is None
    assert reader("moe_post_roofline.b1")(no_device) is None
    assert reader("host_reads_per_token.b1")(no_device) == 3.0
    assert reader("moe_post_roofline.b1")(context(tr, peak=None)) is None
    odd = {"arch": {**CONFIG["arch"], "dtype": "float8_e4m3fn"}}
    assert reader("moe_post_roofline.b1")(context(tr, config=odd)) is None
    prefill_only = Trace(host=[e for e in tr.host if e.t0 >= 200],
                         device=tr.device)
    for name in NAMES:
        assert reader(name)(context(prefill_only)) is None, name


def test_traced_cpu_run_reads_the_program_spans(tmp_path):
    """A whole traced run at the CPU size: two reads per MoE layer and the
    token in every decode step; the CPU trace has no device plane, so the
    device readers stay silent."""
    from chip.test_bench_cell import run_small, small_root
    root = small_root(str(tmp_path))
    res = run_small(root, trace=True, seconds=2.0)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    config = spec.load_config(spec.load(root), "small-switch", root)
    assert got["host_reads_per_token.b1"] == 2 * work.n_moe_layers(config) + 1
    assert got["host_read_ms_per_token.b1"] > 0
    assert got["slot_host_ms_per_token.b1"] > 0
    assert "read_idle_ms_per_token.b1" not in got
    assert "moe_post_roofline.b1" not in got
