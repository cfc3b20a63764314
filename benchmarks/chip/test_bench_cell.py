"""A whole run on the CPU at a small size: the harness's look for a chip is
skipped, everything else runs. A sound run is correct; a run whose timed
path is broken underneath is not; the lower-precision control is not; and
``run.py`` gives no result without a TPU or without the program beside it.

The small configuration is float32: on the CPU the program then matches
the float32 reference to rounding, and its control is bfloat16. (At
bfloat16 and this size, rare routing flips in the program read as wide as
the float8 control; the chip cells' limits come from chip runs.)"""
import json
import os
import shutil
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chip import cell, check, spec  # noqa: E402

ROOT = spec.ROOT
CELL = "small-switch.b1-short"
SEED = 2 ** 31 + 11


def small_root(tmp: str) -> str:
    """A checkout-shaped directory with one more configuration (every width
    cut, for the CPU) and one more traffic mix (shorter requests)."""
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    os.path.join(tmp, "benchmarks", "chip"))
    here = os.path.join(tmp, "benchmarks", "chip")
    with open(os.path.join(here, "configs", "switch-base-128.json")) as f:
        cfg = json.load(f)
    cfg["arch"].update(name="small-switch", n_layers=4, d_model=256,
                       n_heads=4, n_kv_heads=4, head_dim=64, d_ff=512,
                       vocab=2048, dtype="float32")
    cfg["arch"]["moe"].update(n_experts=32, d_expert=512)
    cfg["serve"]["resident_fraction"] = 0.5
    cfg["correct"]["max_logit_gap"] = 1e-4
    with open(os.path.join(here, "configs", "small-switch.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(here, "traffic", "b1-mixed.json")) as f:
        mix = json.load(f)
    mix.update(prompt_len=[16, 64], output_len=[8, 24])
    with open(os.path.join(here, "traffic", "b1-short.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "small-switch", "source": "test",
                         "file": "benchmarks/chip/configs/small-switch.json",
                         "reduced": [], "why": "CPU test size"})
    b["workloads"].append({"name": CELL, "config": "small-switch",
                           "traffic": "b1-short", "chips": 1,
                           "why": "CPU test size"})
    for m in b["per_layer"]:
        m["workloads"].append(CELL)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return tmp


def run_small(root, hooks=(), seconds=3.0, trace=False, seed=SEED):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
    import run
    run.setup_paths()
    return run.run_cell(spec.load(root), CELL, seed, seconds, trace,
                        root=root, device_check=False, hooks=hooks)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(str(tmp_path_factory.mktemp("small")))


def keep_sample(recs):
    """Hook: keep the sample of served requests the check compares."""
    def hook(session):
        window = session.window

        def spy(*a):
            w = window(*a)
            recs.extend(check.sample(w.requests, SEED))
            return w
        session.window = spy
    return hook


@pytest.fixture(scope="module")
def sound(root):
    recs = []
    return run_small(root, hooks=[keep_sample(recs)], seconds=10.0), recs


# -- faults planted under the timed path ----------------------------------------

def altered_token(session):
    """Every decoded token is changed where the decode tail produces it."""
    def wrap(kind, f):
        if kind != "slot_tail":
            return f
        return lambda *a: (f(*a) + 1) % 2048
    cell.wrap_runtime_fns(session.srv.slot_runtime, wrap)


def unchanged_state(session):
    """Decode returns the attention cache it was given: the step's new
    keys and values are never stored."""
    import jax
    import jax.numpy as jnp

    def wrap(kind, f):
        if kind not in ("slot_decode", "slot_decode_pre"):
            return f

        def call(p, bc, *rest):
            old = jax.tree.map(jnp.copy, bc)
            out = list(f(p, bc, *rest))
            out[1 if kind == "slot_decode" else 2] = old
            return tuple(out)
        return call
    cell.wrap_runtime_fns(session.srv.slot_runtime, wrap)


def test_sound_run_is_correct(sound):
    res, _ = sound
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"setup_s", "tpot_ms", "itl_p95_ms",
                                   "tok_s"}
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert list(res)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(root):
    res = run_small(root, trace=True, seconds=2.0)
    assert res["correct"] is True
    got = set(res["metrics"])
    # the CPU has no device plane and no peaks: the trace and peak readers
    # stay silent rather than report 0
    assert {"policy_ms_per_token.b1", "readback_ms_per_token.b1",
            "slot_hit_ratio.b1", "prefill_misses.b1", "prefill_ms.b1"} <= got
    assert not got & {"device_idle.b1", "moe_post_roofline.b1",
                      "decode_step_mfu.b1"}
    assert res["device"]["window_s"] > 0


@pytest.mark.parametrize("fault", [altered_token, unchanged_state],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(root, fault):
    res = run_small(root, hooks=[fault])
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_lower_precision_control_is_not_correct(root, sound):
    """The control on the program's own served tokens: the token that the
    reference in the next precision down (bfloat16, for this float32
    configuration) puts first lies further below the reference's best than
    the limit, which the program's served tokens stay under."""
    _, recs = sound
    b = spec.load(root)
    config = spec.load_config(b, "small-switch", root)
    ref = spec.load_reference(config, os.path.join(root, "benchmarks",
                                                   "chip"))
    key = cell.weight_key(SEED)
    program = check.gaps(ref, config, key, recs, 88)
    control = check.control_gaps(ref, config, key, recs, 88)
    limit = config["correct"]["max_logit_gap"]
    assert len(program) == len(control) > 30
    assert check.verdict(program, limit)["correct"] is True
    assert check.verdict(control, limit)["correct"] is False
    assert control.max() > 3 * program.max()


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "switch-base-128.b1-mixed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "switch-base-128.b1-mixed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
