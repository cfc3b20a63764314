"""Trace reduction on small hand-made traces, and on a recorded CPU trace."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chip import tracefile as tf  # noqa: E402
from chip.tracefile import Event, Trace  # noqa: E402


def ev(name, t0, t1, **stats):
    return Event(name, float(t0), float(t1), tuple(stats.items()))


def small_trace():
    """Two decode steps of 100 ns. Host: step spans with a policy span, a
    readback wait, and one execute event per program the host enqueued,
    some inside the spans of the runtime pieces that enqueued them. The
    device ran those programs in the same order."""
    host = [
        ev("bench.step.decode", 0, 100),
        ev("PJRT_LoadedExecutable_Execute", 2, 3),         # run 6
        ev("bench.policy", 80, 95),
        ev("bench.readback", 10, 35),
        ev("bench.jit.slot_decode_post", 35, 40),
        ev("PJRT_LoadedExecutable_Execute", 36, 39),
        ev("bench.step.decode", 100, 200),
        ev("bench.policy", 180, 190),
        ev("bench.jit.slot_decode_post", 135, 140),
        ev("PJRT_LoadedExecutable_Execute", 136, 139),
        ev("bench.jit.slot_decode_pre", 105, 108),
        ev("PJRT_LoadedExecutable_Execute", 106, 107),
        ev("bench.commit", 120, 130),
        ev("PJRT_LoadedExecutable_Execute", 121, 122),     # a splice
        ev("bench.jit.slot_decode_post", 150, 155),
        ev("PJRT_LoadedExecutable_Execute", 151, 152),
    ]
    ops = [                                    # program executions
        ev("jit_impl(1)", 5, 25),
        ev("jit_impl(2)", 40, 60),
        ev("jit_impl(3)", 110, 122),
        ev("jit__lambda(4)", 121, 126),        # overlaps the one before
        ev("jit_impl(2)", 140, 150),
        ev("jit_impl(2)", 160, 170),
    ]
    tr = Trace(device=ops, host=host)
    for lst in (tr.device, tr.host):
        lst.sort(key=lambda e: (e.t0, -e.t1))
    return tr


def test_interval_arithmetic():
    assert tf.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert tf.clip([[0, 10], [20, 30]], 5, 25) == [[5, 10], [20, 25]]
    assert tf.gaps([[2, 4], [6, 8]], 0, 10) == [[0, 2], [4, 6], [8, 10]]
    assert tf.total([[0, 2], [5, 6]]) == 3


def test_busy_window_and_idle():
    tr = small_trace()
    lo, hi = tf.window(tr)
    assert (lo, hi) == (0, 200)
    busy = tf.total(tf.busy(tr.device, lo, hi))
    assert busy == 20 + 20 + 16 + 10 + 10      # overlap counted once


def test_self_seconds_and_spans_inside_steps():
    tr = small_trace()
    steps = tf.spans(tr, "bench.step.decode")
    assert tf.self_seconds(tr, "bench.policy", steps) == pytest.approx(25e-9)
    assert tf.self_seconds(tr, "bench.readback", steps) == pytest.approx(25e-9)
    posts = [e for s in steps for e in tf.inside(tr.host, s)
             if e.name == "bench.jit.slot_decode_post"]
    assert [e.t0 for e in posts] == [35, 135, 150]


def test_self_time_excludes_nested_bench_spans():
    tr = Trace(host=[ev("bench.step.decode", 0, 100),
                     ev("bench.policy", 10, 60),
                     ev("bench.sync", 20, 30),
                     ev("np.asarray", 40, 45)])
    steps = tf.spans(tr, "bench.step.decode")
    assert tf.self_seconds(tr, "bench.policy", steps) == pytest.approx(40e-9)


def test_breakdown_attributes_gaps_to_innermost_host_event():
    tr = small_trace()
    tr.op_seconds = {"fusion.2": 3.0, "fusion.1": 1.0, "copy": 2.0}
    assert tf.top_device_ops(tr, n=2) == [["fusion.2", 3.0], ["copy", 2.0]]
    gaps = dict(tf.idle_gaps(tr, 0, 200))
    # idle [0,5], [25,40], [60,110], [126,140], [150,160], [170,200]
    assert sum(gaps.values()) == pytest.approx(124e-9)
    assert gaps["PJRT_LoadedExecutable_Execute"] == pytest.approx(5e-9)
    assert gaps["bench.readback"] == pytest.approx(15e-9)      # [25,40]
    assert gaps["bench.policy"] == pytest.approx(80e-9)   # [60,110] [170,200]
    assert gaps["bench.step.decode"] == pytest.approx(14e-9)  # [126,140]
    assert gaps["bench.jit.slot_decode_post"] == pytest.approx(10e-9)


def test_load_reads_host_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for kind in ("prefill", "decode", "decode"):
        with jax.profiler.TraceAnnotation(f"bench.step.{kind}"):
            with jax.profiler.TraceAnnotation("bench.policy"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tf.load(str(tmp_path))
    assert len(tf.spans(tr, "bench.step.decode")) == 2
    steps = tf.spans(tr, "bench.step.decode")
    assert 0 < tf.self_seconds(tr, "bench.policy", steps)
    lo, hi = tf.window(tr)
    assert hi > lo
    assert tr.device == []              # the CPU has no TPU device plane


def test_busy_within_clips_device_time_to_each_span():
    tr = small_trace()
    steps = tf.spans(tr, "bench.step.decode")
    # union [5,25] [40,60] | [110,126] [140,150] [160,170]
    assert tf.busy_within(tr.device, steps[:1]) == pytest.approx(40e-9)
    assert tf.busy_within(tr.device, steps) == pytest.approx(76e-9)
    assert tf.busy_within(tr.device, [ev("bench.step.decode", 26, 39)]) == 0


def test_decode_step_mfu_reads_device_time_inside_decode_spans():
    from types import SimpleNamespace

    from chip import spec, work
    read = spec.load_metric_reader("decode_step_mfu.b1")
    tr = small_trace()
    config = {"arch": {"n_layers": 2, "d_model": 8, "n_heads": 2,
                       "n_kv_heads": 2, "head_dim": 4, "d_ff": 16,
                       "vocab": 32, "act": "gelu",
                       "moe": {"n_experts": 4, "top_k": 1, "d_expert": 16,
                               "moe_layer_period": 2,
                               "moe_layer_offset": 1}}}
    steps = [SimpleNamespace(kind="prefill", pos=9),
             SimpleNamespace(kind="decode", pos=10),
             SimpleNamespace(kind="decode", pos=11),
             SimpleNamespace(kind="decode", pos=12)]   # after the trace
    peak = {"bf16_flops_per_s": 1e12}
    ctx = SimpleNamespace(config=config, peak=peak, trace=tr,
                          window=SimpleNamespace(steps=steps),
                          trace_steps=lambda k: tf.spans(tr, f"bench.step.{k}"))
    flops = (work.decode_token_flops(config, 11)
             + work.decode_token_flops(config, 12))
    assert read(ctx) == pytest.approx(100 * flops / 76e-9 / 1e12)
    # nothing to read: no peak, no device line, more spans than steps
    assert read(SimpleNamespace(**{**vars(ctx), "peak": None})) is None
    assert read(SimpleNamespace(**{**vars(ctx),
                                   "trace": Trace(host=tr.host)})) is None
    short = SimpleNamespace(steps=steps[:2])
    assert read(SimpleNamespace(**{**vars(ctx), "window": short})) is None
