"""The DeepSeek-V2 configuration through the benchmark, on the CPU: its file
loads into the program's config, a whole run at a small DeepSeek-shaped
size (MLA without query compression, YaRN, a dense layer 0, then MoE
layers of 16 experts top-4 beside a shared expert) is correct against
``references/deepseek_v2_decoder.py``, faults planted under the timed path
and the lower-precision control are not, and the MLA work counts and the
new metric readers read what they should.

The small configuration is float32, so the program matches the float32
reference to rounding and its control is bfloat16, as in
``test_bench_cell.py``."""
import json
import os
import shutil
import sys
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chip import cell, check, spec, work_mla  # noqa: E402
from chip import tracefile as tf  # noqa: E402
from chip.tracefile import Event, Trace  # noqa: E402

ROOT = spec.ROOT
CONFIG = "deepseek-v2-lite-l9"
CELL = "small-deepseek.b1-short"
SEED = 2 ** 31 + 23


def small_root(tmp: str) -> str:
    """A checkout-shaped directory with a small DeepSeek-shaped
    configuration and a traffic mix of shorter requests."""
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    os.path.join(tmp, "benchmarks", "chip"))
    here = os.path.join(tmp, "benchmarks", "chip")
    with open(os.path.join(here, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    a = cfg["arch"]
    a.update(name="small-deepseek", n_layers=3, d_model=256, n_heads=4,
             n_kv_heads=4, head_dim=64, d_ff=512, vocab=2048,
             dtype="float32")
    a["attn"]["mla"].update(kv_lora_rank=64, qk_nope_head_dim=32,
                            qk_rope_head_dim=16, v_head_dim=32)
    a["moe"].update(n_experts=16, top_k=4, d_expert=128, n_shared_experts=1,
                    d_shared=128)
    cfg["serve"]["resident_fraction"] = 0.5
    cfg["correct"]["max_logit_gap"] = 1e-4
    with open(os.path.join(here, "configs", "small-deepseek.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(here, "traffic", "b1-mixed.json")) as f:
        mix = json.load(f)
    mix.update(prompt_len=[16, 64], output_len=[8, 24])
    with open(os.path.join(here, "traffic", "b1-short.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "small-deepseek", "source": "test",
                         "file": "benchmarks/chip/configs/small-deepseek.json",
                         "reduced": [], "why": "CPU test size"})
    b["workloads"].append({"name": CELL, "config": "small-deepseek",
                           "traffic": "b1-short", "chips": 1,
                           "why": "CPU test size"})
    for m in b["per_layer"]:
        if CONFIG + ".b1-mixed" in m["workloads"]:
            m["workloads"].append(CELL)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return tmp


def run_small(root, hooks=(), seconds=3.0, trace=False):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
    import run
    run.setup_paths()
    return run.run_cell(spec.load(root), CELL, SEED, seconds, trace,
                        root=root, device_check=False, hooks=hooks)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(str(tmp_path_factory.mktemp("small")))


@pytest.fixture(scope="module")
def sound(root):
    recs = []

    def keep(session):
        window = session.window

        def spy(*a):
            w = window(*a)
            recs.extend(r for r in w.requests if r.finished)
            return w
        session.window = spy
    return run_small(root, hooks=[keep], seconds=10.0), recs


def test_config_file_loads_into_the_program():
    from repro.config import MLAConfig
    b = spec.load(ROOT)
    arch = cell.arch_from(spec.load_config(b, CONFIG))
    assert isinstance(arch.attn.mla, MLAConfig)
    assert not arch.attn.mla.q_lora_rank
    assert arch.attn.yarn_factor == 40.0
    assert [arch.is_moe_layer(i) for i in range(arch.n_layers)] == \
        [False] + [True] * 8
    from repro.configs import get_config
    lite = get_config("deepseek-v2-lite")
    assert arch.attn == lite.attn and arch.moe == lite.moe
    assert (arch.d_model, arch.d_ff, arch.vocab) == \
        (lite.d_model, lite.d_ff, lite.vocab)


# -- faults planted under the timed path ----------------------------------------

def yarn_left_out(session):
    """The served model computes RoPE and the softmax scale without YaRN."""
    import dataclasses
    model = session.srv.slot_runtime.model
    model.cfg = dataclasses.replace(
        model.cfg, attn=dataclasses.replace(model.cfg.attn, yarn_factor=1.0))


def shared_left_out(session):
    """Decode ``post`` runs the routed experts alone: the shared expert's
    weights it is given are zeros."""
    import jax
    import jax.numpy as jnp

    def wrap(kind, f):
        if kind != "slot_decode_post":
            return f

        def call(p, *rest):
            moe = dict(p["moe"])
            moe["shared"] = jax.tree.map(jnp.zeros_like, moe["shared"])
            return f({**p, "moe": moe}, *rest)
        return call
    cell.wrap_runtime_fns(session.srv.slot_runtime, wrap)


def test_sound_run_is_correct(sound):
    res, _ = sound
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 2
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", [yarn_left_out, shared_left_out],
                         ids=["yarn_left_out", "shared_left_out"])
def test_broken_timed_path_is_not_correct(root, fault):
    res = run_small(root, hooks=[fault])
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > 100 * gap["limit"]


def test_lower_precision_control_is_not_correct(root, sound):
    """The control on the program's own served tokens, over up to the first
    40 requests the window finished, in the check's batches of 8: at this
    size bfloat16 moves the reference's first token at about one position
    in fifty (14 of 645), too seldom for one sample of 8 requests to show
    it reliably."""
    import numpy as np
    _, recs = sound
    b = spec.load(root)
    config = spec.load_config(b, "small-deepseek", root)
    ref = spec.load_reference(config, os.path.join(root, "benchmarks",
                                                   "chip"))
    key = cell.weight_key(SEED)
    n = min(len(recs), 40)
    batches = [recs[i:i + check.N_SAMPLE]
               for i in range(0, n, check.N_SAMPLE)]
    program = np.concatenate([check.gaps(ref, config, key, r, 88)
                              for r in batches])
    control = np.concatenate([check.control_gaps(ref, config, key, r, 88)
                              for r in batches])
    limit = config["correct"]["max_logit_gap"]
    assert n >= 24 and len(program) == len(control) > 300
    assert check.verdict(program, limit)["correct"] is True
    assert check.verdict(control, limit)["correct"] is False
    assert control.max() > 3 * program.max()


def test_traced_run_reports_uploads(root):
    res = run_small(root, trace=True, seconds=2.0)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert "uploads_per_token.b1" in got
    # no device plane and no peaks on the CPU: those readers stay silent
    assert not got & {"mla_pre_roofline.b1", "shared_post_roofline.b1",
                      "mla_decode_step_mfu.b1"}


# -- work counts -------------------------------------------------------------------

def test_mla_work_by_hand():
    c = spec.load_config(spec.load(ROOT), CONFIG)
    d, H, r, V = 2048, 16, 512, 102400
    attn_w = (d * H * 192 + d * (r + 64) + r * H * (128 + 128)
              + H * 128 * d)
    assert work_mla.attn_weights(c) == attn_w == 13_762_560
    assert work_mla.n_moe_layers(c) == 8
    # 6 routed experts and the 2 x 1408 shared width, three matrices each
    assert work_mla.post_bytes(c) == 2 * 3 * d * (6 * 1408 + 2816) \
        == 138_412_032
    ctx = 300
    assert work_mla.pre_bytes(c, ctx) == (4 * (2 * d + r) + 4 * d * 64
                                          + 2 * attn_w + 2 * ctx * 576)
    attn = 2 * (d * H * 192 + d * 576 + H * 128 * r + H * ctx * 576
                + H * ctx * r + H * r * 128 + H * 128 * d)
    moe = 2 * d * 64 + 2 * 3 * d * (6 * 1408 + 2816)
    dense = 2 * 3 * d * 10944
    assert work_mla.decode_token_flops(c, ctx) == \
        9 * attn + 8 * moe + dense + 2 * d * V


def test_query_compression_counts_its_down_and_up_projection():
    c = spec.load_config(spec.load(ROOT), CONFIG)
    base = work_mla.attn_weights(c)
    c["arch"]["attn"]["mla"]["q_lora_rank"] = 1536
    assert work_mla.attn_weights(c) - base == \
        2048 * 1536 + 1536 * 16 * 192 - 2048 * 16 * 192


# -- the new metric readers on a synthetic trace --------------------------------

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}


def ev(name, t0, t1, **stats):
    return Event(name, float(t0), float(t1), tuple(stats.items()))


def synthetic(program=True):
    """Two traced decode steps of 100 us and a prefill, with the slot
    cache's uploads (one of them in the prefill) and the MoE layers'
    ``pre`` and ``post`` programs. ``program=False``: the same trace from
    a program without the upload span."""
    host = [ev("bench.step.decode", 0, 100e3),
            ev("slots.upload", 10e3, 11e3, layer=0),
            ev("slots.upload", 12e3, 13e3, layer=1),
            ev("bench.step.decode", 100e3, 200e3),
            ev("slots.upload", 110e3, 111e3, layer=0),
            ev("bench.step.prefill", 200e3, 300e3),
            ev("slots.upload", 210e3, 211e3, layer=0)]
    if not program:
        host = [e for e in host if e.name != "slots.upload"]
    device = [ev("jit_slot_decode_pre(2)", 20e3, 30e3),
              ev("jit_slot_decode_post(3)", 30e3, 60e3),
              ev("jit_slot_decode(1)", 60e3, 70e3),
              ev("jit_slot_decode_pre(2)", 120e3, 130e3),
              ev("jit_slot_decode_post(3)", 130e3, 160e3),
              ev("jit_slot_prefill_post(5)", 220e3, 290e3)]
    return Trace(device=device, host=host)


def context(trace, config):
    steps = [SimpleNamespace(kind="decode", pos=9),
             SimpleNamespace(kind="decode", pos=10),
             SimpleNamespace(kind="prefill", pos=0)]
    return SimpleNamespace(
        config=config, peak=PEAK, trace=trace,
        window=SimpleNamespace(steps=steps),
        trace_steps=lambda k: tf.spans(trace, f"bench.step.{k}"))


def expected(name, config):
    n = work_mla.n_moe_layers(config)
    if name == "uploads_per_token.b1":
        return 3 / 2
    if name == "mla_pre_roofline.b1":
        need = n * (work_mla.pre_bytes(config, 10)
                    + work_mla.pre_bytes(config, 11))
        return 100 * need / 1e12 / 20e-6
    if name == "shared_post_roofline.b1":
        return 100 * 2 * n * work_mla.post_bytes(config) / 1e12 / 60e-6
    flops = (work_mla.decode_token_flops(config, 10)
             + work_mla.decode_token_flops(config, 11))
    return 100 * flops / 90e-6 / 1e12           # busy 50 + 40 us


NEW = ("uploads_per_token.b1", "mla_pre_roofline.b1",
       "shared_post_roofline.b1", "mla_decode_step_mfu.b1")


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_a_synthetic_trace(name):
    config = spec.load_config(spec.load(ROOT), CONFIG)
    read = spec.load_metric_reader(name)
    assert read(context(synthetic(), config)) == \
        pytest.approx(expected(name, config))


def test_uploads_read_nothing_without_the_span():
    config = spec.load_config(spec.load(ROOT), CONFIG)
    read = spec.load_metric_reader("uploads_per_token.b1")
    assert read(context(synthetic(program=False), config)) is None
