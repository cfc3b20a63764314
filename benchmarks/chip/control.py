#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's own
size: one run of the cell, then, on the same sample of served requests, the
gap of the token that the float8 reference puts first at each served
position. The control has to read above the limit that sound runs stay
under. Not part of the benchmark's runs.

    python3 benchmarks/chip/control.py --workload <name> --seed <n> \
        --seconds <s>

Prints one JSON line: the program's widest gap and the control's, with the
share of positions where each differs from the reference's best token,
and the control's ``correct`` as ``check.verdict`` decides it for a run.
Exits 0 when the control comes out as not correct, 1 when it passes.
"""
import json
import sys

import run  # sets T_START, the set-up clock's zero


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.setup_paths()
    run.configure_jax()
    import numpy as np
    from chip import cell, check, spec

    b = spec.load(run.ROOT)
    w = spec.workload(b, args.workload)
    config = spec.load_config(b, w["config"])
    recs = []

    def keep(session):
        window = session.window

        def spy(*a):
            out = window(*a)
            recs.extend(check.sample(out.requests, args.seed))
            return out
        session.window = spy

    res = run.run_cell(b, args.workload, args.seed, args.seconds, False,
                       hooks=[keep])
    mix = spec.load_traffic(w["traffic"])
    length = mix["prompt_len"][1] + mix["output_len"][1]
    ctl = check.control_gaps(spec.load_reference(config), config,
                             cell.weight_key(args.seed), recs, length)
    v = check.verdict(ctl, config["correct"]["max_logit_gap"])
    out = {"workload": args.workload, "seed": args.seed,
           "program_max_gap": res["checks"]["max_logit_gap"]["value"],
           "program_correct": res["correct"],
           "control_max_gap": v["max_logit_gap"],
           "control_correct": v["correct"],
           "control_inexact_share": float(np.mean(ctl > 0)),
           "tokens": v["tokens"],
           "limit": v["limit"]}
    print(f"control max_logit_gap: {v['max_logit_gap']!r} (limit "
          f"{v['limit']!r}), correct {v['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(out), flush=True)
    return 0 if not v["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
