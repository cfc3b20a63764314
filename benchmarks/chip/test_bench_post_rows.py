"""The reader of the MoE ``post`` programs' expert rows
(``post_expert_rows_per_token.b1``): the ``rows`` of the slot runtime's
``runtime.post`` spans inside the decode steps, nothing from a program
without them, and n_moe × top_k at batch one in a whole traced run."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chip import spec, work  # noqa: E402
from chip import tracefile as tf  # noqa: E402
from chip.tracefile import Event, Trace  # noqa: E402

NAME = "post_expert_rows_per_token.b1"


def ev(name, t0, t1, **stats):
    return Event(name, float(t0), float(t1), tuple(stats.items()))


def read(host):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run
    tr = Trace(host=sorted(host, key=lambda e: (e.t0, -e.t1)))
    ctx = run.Context({}, None, None, tr, tf.window(tr))
    return spec.load_metric_reader(NAME)(ctx)


def test_rows_of_the_decode_posts_per_step():
    host = [
        ev("bench.step.decode", 0, 100),
        ev("runtime.post", 10, 20, layer=0, rows=1),
        ev("runtime.post", 30, 40, layer=1, rows=1),
        ev("bench.step.decode", 100, 200),
        ev("runtime.post", 110, 120, layer=0, rows=1),
        ev("runtime.post", 130, 140, layer=1, rows=128),
        ev("bench.step.prefill", 200, 300),           # never counted
        ev("runtime.post", 210, 230, layer=0, rows=128),
    ]
    assert read(host) == pytest.approx((1 + 1 + 1 + 128) / 2)


@pytest.mark.parametrize("host", [
    [ev("bench.step.decode", 0, 100), ev("bench.readback", 10, 20),
     ev("runtime.read.counts", 30, 45, layer=0)],
    [ev("bench.step.prefill", 0, 100),
     ev("runtime.post", 10, 20, layer=0, rows=32)],
    [],
], ids=["parent", "prefill_only", "empty"])
def test_nothing_to_read_without_decode_post_spans(host):
    assert read(host) is None


def test_traced_cpu_run_reads_the_routed_rows(tmp_path):
    """A whole traced run at the CPU size (batch one, top-1, 32 experts):
    every MoE ``post`` of decode takes the routed-row path and reads one
    row; prefill's dispatch reads all E; each span carries its layer."""
    from chip.test_bench_cell import CELL, SEED, small_root
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run
    run.setup_paths()
    root = small_root(str(tmp_path / "root"))
    tdir = str(tmp_path / "trace")
    res = run.run_cell(spec.load(root), CELL, SEED, 2.0, True, root=root,
                       trace_dir=tdir, device_check=False)
    assert res["correct"] is True
    config = spec.load_config(spec.load(root), "small-switch", root)
    m = config["arch"]["moe"]
    n_moe = work.n_moe_layers(config)
    assert res["metrics"][NAME]["value"] == n_moe * m["top_k"]
    host = tf.load(tdir).host
    for kind, rows in (("decode", m["top_k"]), ("prefill", m["n_experts"])):
        steps = [e for e in host if e.name == f"bench.step.{kind}"]
        assert steps, kind
        for s in steps:
            posts = [e for e in tf.inside(host, s) if e.name == "runtime.post"]
            assert [e.stat("layer") for e in posts] == list(range(n_moe))
            assert {e.stat("rows") for e in posts} == {rows}, kind
