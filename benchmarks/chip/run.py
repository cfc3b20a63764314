#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1> [--trace-dir DIR]

Builds the cell's server through ``repro.launch.serve.build`` (slot
runtime, one chip), warms up every shape its traffic reaches, drives one
closed-loop user at batch one for ``--seconds`` on the host clock, checks
the served tokens against the plain reference, and prints one JSON line
last on stdout. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
Exits non-zero, printing no result, when no TPU is found, when the
device's peaks are unknown, or when anything compiles inside the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# the profiler records the first seconds of the window: some 70 decode
# steps, and a trace that exports and reads in well under a minute (the
# device's op line holds an event per op and loop iteration)
TRACE_SECONDS = 8.0


class NoResult(SystemExit):
    """The run cannot give a valid result (exit code 2)."""

    def __init__(self, why: str):
        print(f"no result: {why}", file=sys.stderr, flush=True)
        super().__init__(2)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    return ap.parse_args(argv)


def setup_paths() -> None:
    for p in (os.path.join(ROOT, "src"), os.path.dirname(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)


def configure_jax():
    """Persistent compilation cache at a fixed path inside the checkout."""
    os.makedirs(CACHE_DIR, exist_ok=True)      # JAX writes into, not creates
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def chip(chips: int, peaks: dict):
    """The device this run measures: a TPU with known peaks, or no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoResult(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoResult(f"cell needs {chips} chips, JAX found {len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoResult(f"no peaks for device kind {devs[0].device_kind!r} "
                       "in benchmarks/chip/peaks.json")
    return devs[0]


def end_to_end(w, setup_s: float) -> dict:
    """The cell's user-facing numbers over the whole window."""
    import numpy as np
    decode = [s for s in w.steps if s.kind == "decode"]
    itl = [b - a for r in w.requests
           for a, b in zip(r.token_times, r.token_times[1:])]
    out = {"setup_s": (setup_s, "s")}
    if decode:
        out["tpot_ms"] = (1e3 * sum(s.t1 - s.t0 for s in decode)
                          / len(decode), "ms")
    if itl:
        out["itl_p95_ms"] = (1e3 * float(np.percentile(itl, 95)), "ms")
    out["tok_s"] = (len(w.steps) / w.seconds, "tokens/s")
    return out


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool,
             *, root: str = ROOT, trace_dir=None, device_check: bool = True,
             hooks=()):
    """One run of one cell. Returns the result dict (the last stdout line).
    ``root`` is the checkout whose benchmark files are read. ``hooks`` are
    called with the built session before warm-up (tests use them to break
    the timed path underneath)."""
    import jax
    from chip import cell, check, spec as spec_mod, tracefile
    from chip.traffic import Traffic

    here = os.path.join(root, "benchmarks", "chip")
    w_entry = spec_mod.workload(spec, name)
    config = spec_mod.load_config(spec, w_entry["config"], root)
    traffic = Traffic(spec_mod.load_traffic(w_entry["traffic"], here),
                      config["arch"]["vocab"], seed)
    reference = spec_mod.load_reference(config, here)
    peaks = load_peaks()
    dev = chip(w_entry["chips"], peaks) if device_check else jax.devices()[0]
    peak = peaks.get(dev.device_kind)
    phases = cell.Phases()
    phases.add("start_and_imports", cell.clock() - T_START)
    compiles = cell.Compiles()

    served = cell.build(config, traffic.max_prompt(), traffic.max_new(),
                        seed, phases)
    session = cell.Session(served)
    for hook in hooks:
        hook(session)
    if trace:
        cell.instrument(session)
    c0 = compiles.seconds
    with phases.timed("warmup_and_cache_fill"):
        session.serve_all(traffic.warmup())
    print(f"setup: warm-up backend compile {compiles.seconds - c0:.3f} s, "
          f"set-up backend compile {compiles.seconds:.3f} s, "
          f"jit entries {len(session.srv.compile_counts)}", flush=True)
    setup_s = cell.clock() - T_START

    tdir = stop = None
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="chip_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        stop = (TRACE_SECONDS, jax.profiler.stop_trace)
    w = session.window(traffic.requests(), seconds, compiles, stop)
    if w.compiles:
        raise NoResult(f"{w.compiles} compiles inside the measured window")

    # the served state at the window's close (non-expert weights, slot
    # buffers, attention cache); the process's peak is set-up's jitted init
    # of the whole tree, which the window never holds again
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("bytes_in_use")
    print(f"memory: {memory_peak} bytes in use at the window's close, "
          f"set-up peak {stats.get('peak_bytes_in_use')} bytes", flush=True)
    e2e = end_to_end(w, setup_s)
    metrics = {m["name"]: e2e[m["name"]]
               for m in spec_mod.cell_metrics(spec, name, "end_to_end")
               if m["name"] in e2e}
    decode = [s for s in w.steps if s.kind == "decode"]
    up = sum(s.delta["upload_bytes"] for s in decode)
    firsts = [r.token_times[0] - r.t_submit for r in w.requests
              if r.token_times]
    print(f"window: {w.seconds:.3f} s, {len(w.requests)} requests, "
          f"{len(w.steps)} steps ({len(decode)} decode), upload "
          f"{up / max(1, len(decode)) / 1e6:.3f} MB per decode token, "
          f"{sum(s.delta['slot_misses'] for s in w.steps)} slot misses, "
          f"mean time to first token "
          f"{1e3 * sum(firsts) / max(1, len(firsts)):.3f} ms", flush=True)

    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": w_entry["chips"],
                     "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        tr = tracefile.load(tdir)
        lo_hi = tracefile.window(tr)
        ctx = Context(config, peak, w, tr, lo_hi)
        per_layer = {}
        for m in spec_mod.cell_metrics(spec, name, "per_layer"):
            v = spec_mod.load_metric_reader(m["name"], here)(ctx)
            if v is not None:
                per_layer[m["name"]] = (v, m["unit"])
        metrics = per_layer
        if lo_hi is not None:
            lo, hi = lo_hi
            busy = tracefile.total(tracefile.busy(tr.device, lo, hi))
            result_device["busy_s"] = busy * 1e-9
            result_device["window_s"] = (hi - lo) * 1e-9
            breakdown = {"device_ops": tracefile.top_device_ops(tr),
                         "idle_gaps": tracefile.idle_gaps(tr, lo, hi)}
        del tr, ctx
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)

    # the reference runs once the program's state is gone
    recs = check.sample(w.requests, seed)
    attempted = len(w.requests)
    failed = sum(1 for r in w.requests
                 if r.finished and len(r.tokens) != r.max_new)
    length = traffic.max_prompt() + traffic.max_new()
    del session, served, w
    gc.collect()
    t_ref = cell.clock()
    gaps = check.gaps(reference, config, cell.weight_key(seed), recs, length)
    v = check.verdict(gaps, config["correct"]["max_logit_gap"])
    print(f"check: {len(recs)} requests, {v['tokens']} served tokens, "
          f"median gap {v['median_gap']}, exact share {v['exact_share']}, "
          f"reference {cell.clock() - t_ref:.3f} s", flush=True)

    result = {
        "correct": v["correct"] and failed == 0,
        "attempted": attempted,
        "failed": failed,
        "metrics": {k: {"value": val, "unit": unit}
                    for k, (val, unit) in metrics.items()},
        "device": result_device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {"max_logit_gap": {"value": v["max_logit_gap"],
                                          "limit": v["limit"]}}
    return result


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, config, peak, window, trace, trace_window):
        self.config = config
        self.peak = peak
        self.window = window
        self.trace = trace
        self.trace_window = trace_window

    def trace_steps(self, kind: str) -> list:
        if self.trace is None:
            return []
        return [e for e in self.trace.host if e.name == f"bench.step.{kind}"]


def main(argv=None) -> int:
    args = parse_args(argv)
    setup_paths()
    configure_jax()
    from chip import spec as spec_mod
    spec = spec_mod.load(ROOT)
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), trace_dir=args.trace_dir)
    c = result["checks"]["max_logit_gap"]
    print(f"check max_logit_gap: {c['value']!r} (limit {c['limit']!r})",
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
