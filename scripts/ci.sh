#!/usr/bin/env bash
# One-shot tier-1 verify: install dev deps (best effort — offline
# containers keep whatever is baked in) and run the test suite.
#
#   scripts/ci.sh            # quick: guard + install + pytest
#   SKIP_INSTALL=1 scripts/ci.sh
#   SMOKE=1 scripts/ci.sh    # additionally run the real-JAX serving path
#                            # end to end (slot-pool engine, ragged
#                            # requests, Poisson arrivals, expert slot
#                            # cache) under a timeout
#   BENCH=1 scripts/ci.sh    # additionally run reduced bench_rps,
#                            # bench_latency_cdf, bench_beyond (predictor
#                            # head-to-head), and bench_multitenant
#                            # (tenancy isolation + SLA tiers) points and
#                            # assert they emit valid JSON (bitrot guard)
#
# CI_LOG_DIR=<dir>           # tee serve/bench reports there (uploaded as
#                            # workflow artifacts)
set -euo pipefail
cd "$(dirname "$0")/.."

LOG_DIR="${CI_LOG_DIR:-}"
[ -n "$LOG_DIR" ] && mkdir -p "$LOG_DIR"

log_tee() {  # tee stdin to $LOG_DIR/$1 when CI_LOG_DIR is set
    if [ -n "$LOG_DIR" ]; then tee "$LOG_DIR/$1"; else cat; fi
}

# Tracked-artifact guard: compiled/binary artifacts must never be
# committed (PR 4 accidentally shipped 31 __pycache__ binaries).
if git ls-files | grep -E '\.(pyc|npz)$'; then
    echo "ci.sh: FAIL — tracked .pyc/.npz artifacts (see list above); " \
         "git rm them (the root .gitignore keeps them out)" >&2
    exit 1
fi

# Static invariant checks (repro.analysis, DESIGN.md §9): recompile
# hazards, donation/aliasing, host-sync discipline, Pallas purity, config
# drift. Fails on any finding not covered by analysis-baseline.json or an
# inline suppression-with-reason. The linter is stdlib-only, so it runs
# before the dependency install on purpose.
echo "ci.sh: lint — repro.analysis static invariant checks"
if [ -n "$LOG_DIR" ]; then
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis.lint \
        --json "$LOG_DIR/lint_report.json" \
        --jit-map "$LOG_DIR/jit_map.json" src benchmarks tests
else
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis.lint \
        src benchmarks tests
fi

if [ -z "${SKIP_INSTALL:-}" ]; then
    python -m pip install -q -r requirements-dev.txt || \
        echo "ci.sh: pip install failed (offline?); running with baked-in deps"
fi

# Single EXIT-trap cleanup for every scratch dir any tier allocates: a
# mid-tier failure (set -e) still removes them, and nothing double-frees.
TMPDIRS=()
cleanup() {
    local d
    for d in "${TMPDIRS[@]:-}"; do
        [ -n "$d" ] && rm -rf "$d"
    done
}
trap cleanup EXIT
scratch() {  # scratch VAR: mktemp -d into $VAR, registered for cleanup
    local d    # (no command substitution — a subshell would lose TMPDIRS)
    d=$(mktemp -d)
    TMPDIRS+=("$d")
    printf -v "$1" '%s' "$d"
}

if [ -n "${SMOKE:-}" ]; then
    echo "ci.sh: SMOKE tier — model-mode serve end to end"
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 \
        | log_tee serve_base.log
    echo "ci.sh: SMOKE tier — three-tier SSD→DRAM→GPU pipeline (NVMe 3.5 GB/s)"
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 --ssd-gbps 3.5 \
        | log_tee serve_ssd.log

    echo "ci.sh: SMOKE tier — expert slot cache (resident-fraction 0.5 vs 1.0)"
    scratch SLOT_TMP
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 \
        --resident-fraction 0.5 | tee "$SLOT_TMP/half.log" \
        | log_tee serve_rf05.log
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 \
        --resident-fraction 1.0 | tee "$SLOT_TMP/full.log" \
        | log_tee serve_rf10.log
    # double-buffered (default) vs PR-5 fenced schedule: same rf=0.5 fp32
    # run — the overlap schedule must not change a single token
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 \
        --resident-fraction 0.5 --fenced-uploads \
        | tee "$SLOT_TMP/fenced.log" | log_tee serve_rf05_fenced.log
    python - "$SLOT_TMP/half.log" "$SLOT_TMP/full.log" \
        "$SLOT_TMP/fenced.log" <<'PY'
import re, sys

half, full = open(sys.argv[1]).read(), open(sys.argv[2]).read()
fenced = open(sys.argv[3]).read()
toks_h = re.findall(r"toks=([\d,]+)", half)
toks_f = re.findall(r"toks=([\d,]+)", full)
toks_x = re.findall(r"toks=([\d,]+)", fenced)
assert toks_h and toks_h == toks_f, \
    f"slot-cache token output diverged from all-resident: {toks_h} vs {toks_f}"
assert toks_x == toks_h, \
    f"double-buffered schedule diverged from fenced: {toks_h} vs {toks_x}"
m = re.search(r"slots: resident=(\d+)/(\d+) hit-ratio=[0-9.]+ hits=(\d+) "
              r"misses=\d+ demand-uploads=(\d+)", half)
assert m, "no slot-cache report line in the rf=0.5 run"
res, total, hits, demand = map(int, m.groups())
assert res < total, f"rf=0.5 kept all {total} experts resident"
assert hits > 0, "slot cache reported zero hits"
assert demand > 0, "slot cache reported zero demand uploads"
assert "schedule=overlap" in half and "schedule=fenced" in fenced, \
    "serve report missing the upload-schedule tag"
for name, s in (("rf05", half), ("rf10", full), ("fenced", fenced)):
    assert "guard: zero-recompile ok" in s, \
        f"{name}: recompile_guard line missing — a jit entry retraced " \
        "during steady-state decode (or the guard was dropped from serve)"
print(f"ci.sh: slot cache OK (resident {res}/{total}, hits={hits}, "
      f"demand-uploads={demand}, overlap==fenced, tokens bit-identical, "
      "zero recompiles)")
PY

    # expert-parallel serving (DESIGN.md §8): the same rf=0.5 run sharded
    # over a forced-host 4-device mesh (serve bootstraps
    # --xla_force_host_platform_device_count itself) must not change a
    # single token vs the D=1 run above
    echo "ci.sh: SMOKE tier — expert-parallel D=4 vs D=1 token identity"
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 \
        --resident-fraction 0.5 --devices 4 | tee "$SLOT_TMP/d4.log" \
        | log_tee serve_rf05_d4.log
    python - "$SLOT_TMP/half.log" "$SLOT_TMP/d4.log" <<'PY'
import re, sys

half, d4 = open(sys.argv[1]).read(), open(sys.argv[2]).read()
toks_1 = re.findall(r"toks=([\d,]+)", half)
toks_4 = re.findall(r"toks=([\d,]+)", d4)
assert toks_1 and toks_4 == toks_1, \
    f"D=4 sharded serve diverged from D=1: {toks_1} vs {toks_4}"
m = re.search(r"devices: D=4 links=(\d+) sim-link-util=\[([^\]]*)\]", d4)
assert m, "D=4 run missing the devices/per-link report line"
assert int(m.group(1)) >= 4, f"D=4 run used only {m.group(1)} upload links"
r = re.search(r"rebalances=(\d+)", d4)
assert r and int(r.group(1)) > 0, "placement never rebalanced over 4 requests"
assert "guard: zero-recompile ok" in d4, \
    "D=4: recompile_guard line missing — a sharded jit entry retraced"
print(f"ci.sh: expert-parallel OK (D=4 tokens == D=1, links={m.group(1)}, "
      f"rebalances={r.group(1)})")
PY

    echo "ci.sh: SMOKE tier — online EAMC cold start + save/load warm restart"
    scratch EAMC_TMP
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 --eamc-online \
        --eamc-path "$EAMC_TMP/eamc" | tee "$EAMC_TMP/run1.log" \
        | log_tee serve_eamc_cold.log
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 --eamc-online \
        --eamc-path "$EAMC_TMP/eamc" | tee "$EAMC_TMP/run2.log" \
        | log_tee serve_eamc_warm.log
    python - "$EAMC_TMP/run1.log" "$EAMC_TMP/run2.log" <<'PY'
import re, sys

def parse(p):
    s = open(p).read()
    ent = int(re.search(r"eamc: source=\w+ entries=(\d+)", s).group(1))
    hit = float(re.search(r"hit=([0-9.]+)", s).group(1))
    src = re.search(r"eamc: source=(\w+)", s).group(1)
    return src, ent, hit

s1, e1, h1 = parse(sys.argv[1])
s2, e2, h2 = parse(sys.argv[2])
assert s1 == "cold" and s2 == "load", f"lifecycle sources wrong: {s1}/{s2}"
assert e1 > 0, "cold-start run learned no EAMC entries"
assert e2 > 0, "warm restart lost the persisted entries"
assert h2 + 1e-9 >= h1, f"warm-restart hit ratio regressed: {h2} < {h1}"
print(f"ci.sh: eamc lifecycle OK (entries {e1}->{e2}, hit {h1:.3f}->{h2:.3f})")
PY

    # learned predictor (DESIGN.md §10): cold start trains the per-layer
    # n-gram model online, the second run must resume from the persisted
    # .npz with nonzero learned state and keep training
    echo "ci.sh: SMOKE tier — learned predictor cold start + warm restart"
    scratch PRED_TMP
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 \
        --predictor learned --predictor-path "$PRED_TMP/pred" \
        | tee "$PRED_TMP/run1.log" | log_tee serve_pred_cold.log
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 4 \
        --predictor learned --predictor-path "$PRED_TMP/pred" \
        | tee "$PRED_TMP/run2.log" | log_tee serve_pred_warm.log
    python - "$PRED_TMP/run1.log" "$PRED_TMP/run2.log" <<'PY'
import re, sys

def parse(p):
    s = open(p).read()
    m = re.search(r"predictor: kind=(\w+) source=(\w+) seqs=(\d+)", s)
    assert m, f"{p}: no predictor report line"
    saved = re.search(r"predictor: saved seqs=(\d+)", s)
    assert saved, f"{p}: predictor state was not persisted"
    assert "guard: zero-recompile ok" in s, \
        f"{p}: recompile_guard line missing under the learned predictor"
    return m.group(1), m.group(2), int(m.group(3)), int(saved.group(1))

k1, s1, n1, v1 = parse(sys.argv[1])
k2, s2, n2, v2 = parse(sys.argv[2])
assert k1 == k2 == "learned", f"predictor kinds wrong: {k1}/{k2}"
assert s1 == "cold" and s2 == "load", f"lifecycle sources wrong: {s1}/{s2}"
assert v1 > 0, "cold-start run trained no sequences"
assert n2 >= v1 and v2 > v1, \
    f"warm restart lost learned state: loaded {n2}, saved {v1}->{v2}"
print(f"ci.sh: learned predictor OK (seqs {v1}->{v2}, warm source={s2})")
PY

    # multi-tenant serving (DESIGN.md §11): two tenants with private
    # predictor namespaces — each persists its own .npz and warm-restarts
    # from it; tokens are bit-identical across the restart and the decode
    # path stays zero-recompile
    echo "ci.sh: SMOKE tier — two-tenant serve: private predictor lifecycle"
    scratch MT_TMP
    cat > "$MT_TMP/tenants.json" <<JSON
[
  {"tenant_id": "acme", "sla_class": "interactive",
   "predictor": {"kind": "eamc", "online": true, "path": "$MT_TMP/acme"},
   "gpu_slot_quota": 3, "rps": 2.0},
  {"tenant_id": "globex", "sla_class": "batch", "stall_budget": 2,
   "predictor": {"kind": "eamc", "online": true, "path": "$MT_TMP/globex"},
   "rps": 1.0}
]
JSON
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 6 \
        --tenants "$MT_TMP/tenants.json" | tee "$MT_TMP/run1.log" \
        | log_tee serve_multitenant_cold.log
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${SMOKE_TIMEOUT:-300}" \
        python -m repro.launch.serve --reduced --requests 6 \
        --tenants "$MT_TMP/tenants.json" | tee "$MT_TMP/run2.log" \
        | log_tee serve_multitenant_warm.log
    python - "$MT_TMP/run1.log" "$MT_TMP/run2.log" <<'PY'
import os, re, sys

def parse(p):
    s = open(p).read()
    assert "guard: zero-recompile ok" in s, \
        f"{p}: recompile_guard line missing under multi-tenant serving"
    src = dict(re.findall(r"tenant (\w+): sla=.* src=(\w+)", s))
    saved = dict(re.findall(r"tenant (\w+): saved predictor -> (\S+)", s))
    assert set(src) == set(saved) == {"acme", "globex"}, \
        f"{p}: tenant report lines missing: src={src} saved={saved}"
    return re.findall(r"toks=([\d,]+)", s), src, saved

t1, src1, saved1 = parse(sys.argv[1])
t2, src2, saved2 = parse(sys.argv[2])
assert t1 and t1 == t2, \
    f"tenant warm restart changed token output: {t1} vs {t2}"
assert all(v == "cold" for v in src1.values()), f"run1 sources: {src1}"
assert all(v == "load" for v in src2.values()), \
    f"warm restart did not reload the private predictors: {src2}"
paths = set(saved2.values())
assert len(paths) == 2, f"tenants shared one predictor file: {paths}"
for p in paths:
    assert os.path.exists(p), f"persisted tenant predictor missing: {p}"
print(f"ci.sh: multi-tenant lifecycle OK (cold->load for {sorted(src2)}, "
      "distinct .npz per tenant, tokens bit-identical, zero recompiles)")
PY
fi

if [ -n "${BENCH:-}" ]; then
    echo "ci.sh: BENCH tier — reduced bench points must emit valid JSON"
    scratch BENCH_TMP
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${BENCH_TIMEOUT:-600}" \
        python -m benchmarks.bench_rps --resident-fraction 0.2 \
        --json "$BENCH_TMP/rps.json" | log_tee bench_rps.log
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${BENCH_TIMEOUT:-600}" \
        python -m benchmarks.bench_latency_cdf --scheduling continuous \
        --json "$BENCH_TMP/cdf.json" | log_tee bench_latency_cdf.log
    echo "ci.sh: BENCH tier — wire-dtype sweep (fp32/fp16/int8 transfers)"
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${BENCH_TIMEOUT:-600}" \
        python -m benchmarks.bench_rps --transfer-dtype fp32,fp16,int8 \
        --json "$BENCH_TMP/wire.json" | log_tee bench_wire_sweep.log
    echo "ci.sh: BENCH tier — expert-parallel device sweep (D=1,2,4)"
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${BENCH_TIMEOUT:-600}" \
        python -m benchmarks.bench_rps --devices 1,2,4 \
        --json "$BENCH_TMP/devices.json" | log_tee bench_device_sweep.log
    # the PR-7 trajectory point: the device-sweep emits, archived by name
    [ -n "$LOG_DIR" ] && cp "$BENCH_TMP/devices.json" "$LOG_DIR/BENCH_7.json"
    echo "ci.sh: BENCH tier — predictor head-to-head on the drift replay"
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${BENCH_TIMEOUT:-600}" \
        python -m benchmarks.bench_beyond --predictor \
        --json "$BENCH_TMP/beyond.json" | log_tee bench_predictor.log
    # the PR-9 trajectory point: the predictor head-to-head, archived by name
    [ -n "$LOG_DIR" ] && cp "$BENCH_TMP/beyond.json" "$LOG_DIR/BENCH_9.json"
    echo "ci.sh: BENCH tier — multi-tenant isolation + SLA admission tiers"
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "${BENCH_TIMEOUT:-600}" \
        python -m benchmarks.bench_multitenant --quick \
        --json "$BENCH_TMP/multitenant.json" | log_tee bench_multitenant.log
    # the PR-10 trajectory point: tenancy isolation + SLA, archived by name
    [ -n "$LOG_DIR" ] && cp "$BENCH_TMP/multitenant.json" \
        "$LOG_DIR/BENCH_10.json"
    python - "$BENCH_TMP/rps.json" "$BENCH_TMP/cdf.json" \
        "$BENCH_TMP/wire.json" "$BENCH_TMP/devices.json" \
        "$BENCH_TMP/beyond.json" "$BENCH_TMP/multitenant.json" <<'PY'
import json, sys

for p in sys.argv[1:]:
    with open(p) as f:
        doc = json.load(f)
    rows = doc["rows"]
    assert rows, f"{p}: bench emitted no rows"
    for r in rows:
        assert {"name", "value", "unit", "derived"} <= set(r), f"{p}: {r}"
    print(f"ci.sh: {p} OK ({len(rows)} rows)")

# wire sweep: narrower transfers must never ship MORE bytes on the same
# workload — upload bytes monotonically non-increasing along fp32→fp16→int8
# at every request rate
with open(sys.argv[3]) as f:
    rows = {r["name"]: r["value"] for r in json.load(f)["rows"]}
rates = sorted({n.split("rps=")[1].split("/")[0]
                for n in rows if "/upload-bytes" in n})
assert rates, "wire sweep emitted no upload-bytes rows"
for rps in rates:
    seq = [rows[n] for dt in ("fp32", "fp16", "int8")
           for n in (f"wire-sweep/switch-base-128/rf=0.5/{dt}"
                     f"/rps={rps}/upload-bytes",)]
    assert seq[0] >= seq[1] >= seq[2], \
        f"upload bytes not monotone at rps={rps}: {seq}"
    print(f"ci.sh: wire sweep rps={rps} upload-bytes {seq} monotone OK")

# device sweep: more devices -> more aggregate upload bandwidth -> less
# demand stall per token at rf<1; the bench emits its own monotonicity
# tally, asserted here to cover every request rate
with open(sys.argv[4]) as f:
    rows = {r["name"]: r for r in json.load(f)["rows"]}
mono = [r for n, r in rows.items() if n.endswith("/stall-monotone-rates")]
assert mono, "device sweep emitted no monotonicity row"
n_rates = int(mono[0]["derived"].split()[1])
assert mono[0]["value"] == n_rates, \
    f"device-sweep stall not monotone with D: {mono[0]}"
print(f"ci.sh: device sweep stall monotone at all {n_rates} rates OK")

# predictor head-to-head (DESIGN.md §10): on the post-drift phase the
# frozen EAMC degrades (stale collection) while the learned predictor
# keeps training through the shift — it must stay clearly ahead
with open(sys.argv[5]) as f:
    rows = {r["name"]: r["value"] for r in json.load(f)["rows"]}
frozen = rows["beyond/predictor/frozen-eamc/phase1/hit"]
learned = rows["beyond/predictor/learned/phase1/hit"]
assert learned >= 0.64, \
    f"learned predictor post-drift hit {learned} below the 0.64 floor"
assert learned > frozen, \
    f"learned predictor did not beat the frozen EAMC: {learned} <= {frozen}"
print(f"ci.sh: predictor head-to-head OK (post-drift hit: "
      f"learned={learned} > frozen={frozen})")

# multi-tenant (DESIGN.md §11): (1) private brains — the drifting tenant's
# post-drift hit must be at least the shared-collection run's; (2) the
# stable tenant must not feel its neighbour's drift (counterfactual-
# differenced, so workload-seed noise cancels); (3) SLA tiers must not
# worsen interactive p99 vs the tierless shared queue
with open(sys.argv[6]) as f:
    rows = {r["name"]: r["value"] for r in json.load(f)["rows"]}
per = rows["multitenant/isolation/per-tenant/drift/phase2/hit"]
shared = rows["multitenant/isolation/shared/drift/phase2/hit"]
assert per >= shared, \
    f"per-tenant brain lost to the shared one post-drift: {per} < {shared}"
shift = rows["multitenant/isolation/stable-shift"]
assert abs(shift) <= 0.01, \
    f"neighbour drift moved the stable tenant's hit ratio by {shift}"
p99_t = rows["multitenant/sla/tiered/interactive/p99-e2e"]
p99_0 = rows["multitenant/sla/tierless/interactive/p99-e2e"]
assert p99_t <= p99_0, \
    f"SLA tiers worsened interactive p99: {p99_t}ms > {p99_0}ms"
print(f"ci.sh: multi-tenant OK (drift hit {per} >= {shared}, "
      f"stable shift {shift:+.3f}, interactive p99 {p99_t} <= {p99_0}ms)")
PY
fi

# Tier-1 must be fully green: no allowed-failure list. The 6 seed-era
# hlo/dryrun failures are fixed; any pytest failure fails CI.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
