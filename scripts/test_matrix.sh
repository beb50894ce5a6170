#!/usr/bin/env bash
# Run the suite at mesh sizes 1/3/5/8 — the TPU-native analog of the
# reference CI's "mpirun -n 1,3,5,8 pytest heat/" matrix
# (reference Jenkinsfile:24-28; SURVEY.md §4) — with per-leg line coverage
# (the reference's codecov flags per world size, codecov.yml:1-20;
# Jenkinsfile:36-39) collected by scripts/heat_coverage.py and merged into
# one report at the end, plus a fusion-off leg that reruns the elementwise
# and eager-chain suites with HEAT_TPU_FUSION=0 so the deferred AND the
# eager engine paths both stay green.
set -e
cd "$(dirname "$0")/.."
COV_DIR=${HEAT_TPU_COV_DIR:-/tmp/heat_cov}
mkdir -p "$COV_DIR"
legs=()
for size in ${@:-1 3 5 8}; do
  echo "=== mesh size $size ==="
  HEAT_TPU_TEST_DEVICES=$size \
  HEAT_TPU_COVERAGE="$COV_DIR/cov_mesh$size.json" \
    python -m pytest tests/ -q -x -m "not slow"
  legs+=("$COV_DIR/cov_mesh$size.json")
done
# fusion leg: the eager engines (HEAT_TPU_FUSION=0 escape hatch) must match
# the recorded/fused default on the suites that exercise op chains
echo "=== fusion off (HEAT_TPU_FUSION=0) ==="
HEAT_TPU_FUSION=0 \
  python -m pytest tests/test_elementwise.py tests/test_eager_chain.py -q -x
# collective-fusion leg: HEAT_TPU_FUSION_COLLECTIVES=0 restores the
# force-at-collective behavior (resplit_/apply dispatch eagerly, no
# multi-root batching) — the escape hatch must keep the collective-spanning
# suites green and numerically identical
echo "=== collective fusion off (HEAT_TPU_FUSION_COLLECTIVES=0) ==="
HEAT_TPU_FUSION_COLLECTIVES=0 \
  python -m pytest tests/test_fused_collectives.py tests/test_eager_chain.py \
    tests/test_statistics.py tests/test_manipulations.py -q -x
# telemetry leg: the observability layer (HEAT_TPU_TELEMETRY=1) must change
# no results on the instrumented suites, and the overhead guard in
# tests/test_telemetry.py pins the enabled dispatch rate at >= 0.9x disabled
echo "=== telemetry on (HEAT_TPU_TELEMETRY=1) ==="
HEAT_TPU_TELEMETRY=1 \
  python -m pytest tests/test_telemetry.py tests/test_eager_chain.py tests/test_linalg_depth.py -q -x
# trace-timeline leg: the full verbose event log (timestamps, correlation
# ids, scoped sessions) stays green on the telemetry + trace suites, and an
# exported trace of a real reduction-chain run must parse as Chrome
# trace-event JSON (the CLI's validate-trace is the same check CI users run)
echo "=== telemetry verbose (HEAT_TPU_TELEMETRY=verbose) ==="
HEAT_TPU_TELEMETRY=verbose \
  python -m pytest tests/test_trace_timeline.py tests/test_telemetry.py -q -x
HEAT_TPU_TELEMETRY=verbose python - <<'PY'
import numpy as np, heat_tpu as ht
from heat_tpu.core import telemetry
a = ht.array(np.random.default_rng(0).standard_normal(
    (8 * ht.get_comm().size, 3)).astype(np.float32), split=0)
float(ht.mean(a)) + float(ht.std(a))  # dispatch + blocking sync on the timeline
telemetry.export_trace("/tmp/heat_tpu_matrix_trace.json")
PY
HEAT_TPU_TELEMETRY=verbose \
  python -m heat_tpu.telemetry validate-trace /tmp/heat_tpu_matrix_trace.json
# tracelens leg (ISSUE 13): the exported+validated trace runs through the
# post-hoc analyzer — the JSON output must parse with full attribution
# coverage (every bucket accounted, explicit unattributed remainder <= 5%)
# and ZERO findings on this clean workload; `analyze` itself exits nonzero
# on any warning/error finding, and the python step re-checks the shape
python -m heat_tpu.telemetry analyze /tmp/heat_tpu_matrix_trace.json --json \
  > /tmp/heat_tpu_matrix_analysis.json
python - <<'PY'
import json
doc = json.load(open("/tmp/heat_tpu_matrix_analysis.json"))
assert doc["attribution"]["overall"], "analyze produced no attribution buckets"
assert doc["attribution"]["unattributed_pct"] <= 5.0, \
    f"unattributed {doc['attribution']['unattributed_pct']}% > 5%"
assert doc["findings"] == [], f"clean workload produced findings: {doc['findings']}"
print("analyze OK:", {b: rec["pct"] for b, rec in doc["attribution"]["overall"].items()})
PY
# whole-algorithm fusion leg (ISSUE 20): the estimator suites run with
# collective fusion on (the default), then a real reduce-then-matmul
# iteration loop is traced and budget-checked — steady state must be ONE
# program dispatch and at most one blocking sync per iteration — and the
# trace goes through the post-hoc analyzer with ZERO findings; the same
# suites stay green under HEAT_TPU_FUSION_COLLECTIVES=0 and the ambient
# fault mix (deferral must not change results or swallow faults)
echo "=== whole-algorithm fusion (estimator suites + dispatch/sync budget) ==="
python -m pytest tests/test_whole_algorithm_fusion.py tests/test_lloyd_fused.py \
  tests/test_ml.py -q -x
HEAT_TPU_TELEMETRY=verbose python - <<'PY'
import numpy as np
import heat_tpu as ht
from heat_tpu.core import telemetry

p = ht.get_comm().size
rng = np.random.default_rng(0)
x = ht.array(rng.standard_normal((8 * p, 4 * p)).astype(np.float32), split=0)
w = ht.array(rng.standard_normal((4 * p, 2 * p)).astype(np.float32))

def step():
    mu = ht.mean(x)  # split-crossing psum node
    return float(ht.sum((x - mu) @ w))  # matmul node + reduction, ONE read

step(); step()  # warm: compiles land, steady state begins
telemetry.reset()
iters = 5
for _ in range(iters):
    step()
stats = telemetry.async_forcing()
assert stats["dispatches"] == iters, f"not 1 dispatch/iteration: {stats}"
assert stats["blocking_total"] <= iters, f">1 blocking sync/iteration: {stats}"
telemetry.export_trace("/tmp/heat_tpu_whole_algo_trace.json")
print("whole-algorithm budget OK:", {k: stats[k] for k in
      ("dispatches", "blocking_total", "multi_root_batches")})
PY
python -m heat_tpu.telemetry analyze /tmp/heat_tpu_whole_algo_trace.json --json \
  > /tmp/heat_tpu_whole_algo_analysis.json
python - <<'PY'
import json
doc = json.load(open("/tmp/heat_tpu_whole_algo_analysis.json"))
assert doc["findings"] == [], \
    f"whole-algorithm trace produced findings: {doc['findings']}"
print("whole-algorithm analyze OK")
PY
echo "=== whole-algorithm fusion: collectives-off + faults legs ==="
HEAT_TPU_FUSION_COLLECTIVES=0 \
  python -m pytest tests/test_whole_algorithm_fusion.py tests/test_lloyd_fused.py -q -x
HEAT_TPU_FAULTS=ci HEAT_TPU_TELEMETRY=1 \
  python -m pytest tests/test_whole_algorithm_fusion.py -q -x
# memory-observability leg: the headroom admission gate is ARMED (a generous
# fraction of host memory under the warn policy — every fused dispatch pays
# the live-ledger check without any policy actually firing) while the memory
# suite and the eager-chain suite run; the gate/ledger/forensics must change
# no results and the suite's own warn|raise|drain pins stay exact (tests
# re-arm their own budgets per test and restore the ambient one)
echo "=== memory observability (HEAT_TPU_MEMORY_BUDGET armed) ==="
HEAT_TPU_MEMORY_BUDGET=0.95 HEAT_TPU_MEMORY_POLICY=warn HEAT_TPU_TELEMETRY=1 \
  python -m pytest tests/test_memory_obs.py tests/test_eager_chain.py -q -x
# resilience leg: the suite runs under the deterministic ambient fault mix
# (core/resilience.py 'ci' preset: fused compiles/executes fail periodically
# and degrade to eager, transient io errors are retried, checkpoint
# write/commit/restore attempts absorb transient faults and gc deletions
# degrade to debris-for-the-next-sweep) — recovery is proven by the suite
# simply staying green while faults fire. Explicit inject() scopes suspend
# the ambient specs, so exact-count pins stay exact; the checkpoint suite's
# kill-mid-save resume loop runs here too (ISSUE 4 acceptance).
echo "=== faults injected (HEAT_TPU_FAULTS=ci) ==="
HEAT_TPU_FAULTS=ci HEAT_TPU_TELEMETRY=1 \
  python -m pytest tests/test_resilience.py tests/test_resilience_io.py tests/test_io_errors.py \
    tests/test_checkpoint_resilience.py tests/test_checkpoint_profiling.py \
    tests/test_fused_collectives.py tests/test_trace_timeline.py \
    tests/test_memory_obs.py tests/test_tracelens.py tests/test_numlens.py -q -x
# SDC-injection smoke: arm the numeric.sdc fault site on one device and prove
# the sentinel NAMES it — the true-positive path of the canary, end to end
# through the quarantine ledger (tests pin the MeshDegradedWarning escalation)
echo "=== SDC sentinel smoke (HEAT_TPU_FAULTS='numeric.sdc.0:every=1') ==="
HEAT_TPU_FAULTS='numeric.sdc.0:every=1' HEAT_TPU_NUMLENS=full python - <<'PY'
import numpy as np, heat_tpu as ht
from heat_tpu.core import numlens
float(ht.sum(ht.array(np.ones(8, np.float32), split=0)))  # bring the mesh up
r = numlens.run_canary()
assert r is not None and r["mismatches"], f"sentinel missed the sick device: {r}"
sdc = [f for f in numlens.findings() if f["rule"] == "numlens.sdc"]
assert sdc, "no numlens.sdc finding emitted"
print("SDC sentinel OK:", sdc[0]["device"])
PY
# numerics-lens leg: the numerics observability layer ARMED in sampling mode
# (every fused dispatch pays the hook check, sampled dispatches pay the jitted
# stats kernel + periodic shadow replay) while the numlens suite and the
# eager-chain suite run — the lens must change no results; the suite's own
# overhead/never-forces/never-initializes pins run armed too
echo "=== numerics lens (HEAT_TPU_NUMLENS=sample) ==="
HEAT_TPU_NUMLENS=sample HEAT_TPU_TELEMETRY=1 \
  python -m pytest tests/test_numlens.py tests/test_eager_chain.py -q -x
# runtime-health leg (core/health_runtime.py): flight recorder ARMED with a
# small ring and the stall watchdog live under the warn policy (every fused
# dispatch and blocking sync pays the guard arm/disarm and the ring append)
# while the health suite and the eager-chain suite run — the recorder,
# watchdog and latency histograms must change no results, and the suite's
# own trip/dump/percentile pins stay exact
echo "=== runtime health (HEAT_TPU_FLIGHT=1, watchdog armed) ==="
HEAT_TPU_FLIGHT=1 HEAT_TPU_FLIGHT_EVENTS=512 HEAT_TPU_WATCHDOG_POLICY=warn \
HEAT_TPU_TELEMETRY=1 \
  python -m pytest tests/test_health_runtime.py tests/test_eager_chain.py -q -x
# elasticity leg (core/elastic.py): the suite runs with the ambient
# elastic.preempt fault site firing periodically — every 7th poll of
# Supervisor.maybe_preempt() reports a preemption, so the drain → commit →
# reform → resume cycle executes for real while the elastic suite and the
# checkpoint-resilience suite run. Explicit inject()/suspended() scopes
# suspend the ambient spec, so the suites' exact-count pins stay exact; the
# kill-a-host DASO test (full-vs-shrunk trajectory match) runs here too.
echo "=== elasticity (HEAT_TPU_FAULTS='elastic.preempt:every=7') ==="
HEAT_TPU_FAULTS='elastic.preempt:every=7' HEAT_TPU_TELEMETRY=1 \
  python -m pytest tests/test_elastic.py tests/test_checkpoint_resilience.py -q -x
# multi-process runtime leg (core/multihost.py, ISSUE 19): REAL coordinated
# worker processes — a 2-process mesh over loopback gloo, supervised across
# reform generations. The slow-marked suite (excluded from the mesh loop
# above) runs under the ambient CI fault mix, which the launcher propagates
# into every worker's environment: cross-process collectives, world-size
# invariance, SIGKILL-mid-step reform with checkpoint-equality, the
# hung-peer drain watchdog. Then the launcher CLI drives one SIGKILL chaos
# run end to end: kill rank 1 mid-step, the survivor drains with
# REFORM_EXIT, and the reformed 1-process world restores from the newest
# verifying checkpoint and completes.
echo "=== multi-process runtime (2-proc gloo mesh, -m slow, HEAT_TPU_FAULTS=ci) ==="
HEAT_TPU_FAULTS=ci python -m pytest tests/test_multiproc.py -q -x -m slow
MP_SCRATCH=$(mktemp -d)
python scripts/launch_multiproc.py -n 2 --max-reforms 1 \
  --kill-rank 1 --kill-at-step 3 --quiet -- \
  python scripts/multiproc_trainer.py --steps 8 --checkpoint-every 2 \
    --ckpt-dir "$MP_SCRATCH/ckpt" --out "$MP_SCRATCH/out" \
  > "$MP_SCRATCH/result.json"
python - "$MP_SCRATCH" <<'PY'
import glob, json, os, sys
scratch = sys.argv[1]
r = json.load(open(os.path.join(scratch, "result.json")))
assert r["ok"] and r["reforms"] == 1, r
docs = [json.load(open(p))
        for p in glob.glob(os.path.join(scratch, "out", "result-*.json"))]
final = [d for d in docs if d["status"] == "done"]
assert final and final[0]["resumed_from"] is not None, docs
print("multiproc leg: reform OK, resumed from step", final[0]["resumed_from"])
PY
rm -rf "$MP_SCRATCH"
# serving leg (core/serving.py, ISSUE 15): the multi-tenant session layer —
# the suite drives N=8 threaded clients through session isolation, admission
# gates and cross-session batching (zero steady-state retraces);
# then the persistent program cache's cross-process contract runs for real:
# a COLD process populates HEAT_TPU_PROGRAM_CACHE_DIR, and a second WARM
# process replaying the same chain must record ZERO compiles (disk warm
# start — ROADMAP item 4's fresh-process acceptance)
echo "=== serving (sessions + admission + persistent cache) ==="
python -m pytest tests/test_serving.py -q -x
SERVING_CACHE_DIR=$(mktemp -d)
for leg_name in cold warm; do
  echo "--- $leg_name process ---"
  HEAT_TPU_PROGRAM_CACHE_DIR="$SERVING_CACHE_DIR" SERVING_LEG=$leg_name \
  python - <<'PY'
import json, os
import numpy as np
import heat_tpu as ht
from heat_tpu.core import serving

a = ht.array(np.arange(48, dtype=np.float32), split=0)
float(ht.sum(a * 5.0 + 2.0))
stats = serving.cache_stats()
leg = os.environ["SERVING_LEG"]
print(f"{leg}: compiles={stats['compiles']} disk_hits={stats['disk_hits']} "
      f"index_keys={stats['index_keys']}")
if leg == "cold":
    assert stats["compiles"] >= 1, f"cold process compiled nothing: {stats}"
    assert stats["index_keys"] >= 1, f"cold process banked no keys: {stats}"
else:
    assert stats["compiles"] == 0, f"warm process recompiled: {stats}"
    assert stats["disk_hits"] >= 1, f"warm process missed the index: {stats}"
PY
done
rm -rf "$SERVING_CACHE_DIR"
# ops-plane leg (core/opsplane.py, ISSUE 17): the live ops endpoint ARMED
# while N=8 threaded tenants drive real traffic — mid-traffic scrapes of
# /metrics + /healthz must be thread-safe and the exposition must pass the
# strict parser check (types, HELP lines, no duplicate samples, schema'd
# names only), exactly as a sidecar Prometheus would see it
echo "=== ops plane (HEAT_TPU_OPS_PORT armed during serving traffic) ==="
HEAT_TPU_OPS_PORT=0 python -m pytest tests/test_opsplane.py -q -x
HEAT_TPU_OPS_PORT=0 python - <<'PY'
import io, threading, urllib.request
import numpy as np
import heat_tpu as ht
from heat_tpu.core import opsplane, serving
import heat_tpu.telemetry as cli

port = opsplane.status()["port"]
assert port, "HEAT_TPU_OPS_PORT=0 did not arm the ops server"

def chain(arr, k):
    return ht.sum(arr * k + 1.0)

arrs = [
    ht.array(
        np.random.default_rng(i).normal(size=(256,)).astype(np.float32), split=0
    )
    for i in range(8)
]
# prebake every batch-size signature so steady state never retraces
for k in range(1, 9):
    outs = [chain(arrs[j], 1.0 + j * 0.25) for j in range(k)]
    for o in outs:
        float(o)

barrier = threading.Barrier(9)
errors = []

def client(i):
    try:
        with serving.Session(f"matrix{i}"):
            barrier.wait(timeout=30)
            for r in range(30):
                float(chain(arrs[i], 1.0 + r * 0.25))
    except Exception as exc:
        errors.append(exc)

threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
for t in threads:
    t.start()
barrier.wait(timeout=30)
# mid-traffic: the strict check (parser-valid /metrics + /healthz 200)
out = io.StringIO()
rc = cli.main(["ops", "check", "--port", str(port)], out=out)
print(out.getvalue().rstrip())
assert rc == 0, f"mid-traffic ops check failed:\n{out.getvalue()}"
with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
    text = r.read().decode()
assert 'tenant="matrix' in text, "no per-tenant counters on /metrics mid-traffic"
for t in threads:
    t.join(timeout=120)
assert not errors, errors
with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
    problems = opsplane.validate_exposition(r.read().decode())
assert not problems, problems
print("ops leg: mid-traffic scrape clean, per-tenant labels present")
PY
# autoscale leg (core/autoscale.py, ISSUE 18): the overload controller
# ARMED while 8 bursty mixed-tier tenants (4 interactive + 4 batch) drive
# traffic through an injected SLO burn — the loop must shed batch (typed
# ShedError, chains stay pending), hold every interactive request green,
# and walk shed -> cooldown -> recover with a bounded decision count
echo "=== autoscale (controller armed under bursty mixed-tier overload) ==="
python -m pytest tests/test_autoscale.py -q -x
python - <<'PY'
import threading, time
import numpy as np
import heat_tpu as ht
from heat_tpu.core import autoscale, health_runtime, opsplane, serving

warm = ht.array(np.arange(32, dtype=np.float32), split=0)
float(ht.sum(warm * 2.0))  # mesh + program warm
health_runtime.set_slo(dispatch_ms=1.0)
opsplane.set_burn(target=0.9, fast_s=1.0, slow_s=4.0, threshold=1.0,
                  min_samples=4)
ctl = autoscale.arm(interval_s=60.0, cooldown_s=0.3, shrink_after_s=3600.0)

# injected latency fault fires the burn alert; the controller sheds batch
for _ in range(16):
    health_runtime._slo_observe("dispatch", 0.05)
opsplane.sample()
assert autoscale.poll() == "shed_on", autoscale.stats()

interactive_errors, shed_hits = [], []
barrier = threading.Barrier(8)

def interactive(i):
    try:
        barrier.wait(timeout=30)
        with serving.Session(f"fg{i}", tier="interactive", deadline_ms=100.0):
            a = ht.array(np.random.default_rng(i).normal(
                size=(64,)).astype(np.float32), split=0)
            for r in range(8):
                float(ht.sum(a * (1.0 + r)))
    except Exception as exc:
        interactive_errors.append(exc)

def batch(i):
    barrier.wait(timeout=30)
    with serving.Session(f"bg{i}", tier="batch"):
        a = ht.array(np.random.default_rng(100 + i).normal(
            size=(64,)).astype(np.float32), split=0)
        for r in range(8):
            try:
                float(ht.sum(a * (1.0 + r)))
            except serving.ShedError:
                shed_hits.append(i)

threads = [threading.Thread(target=interactive, args=(i,)) for i in range(4)]
threads += [threading.Thread(target=batch, args=(i,)) for i in range(4)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=120)
assert not interactive_errors, f"interactive failed mid-overload: {interactive_errors}"
assert shed_hits, "no batch dispatch was shed under overload"

# burn clears + cooldown passes -> recovery; batch dispatches cleanly again
time.sleep(1.1)
opsplane.sample()
autoscale.poll()
time.sleep(0.35)
assert autoscale.poll() in ("shed_off", "recover"), autoscale.stats()
with serving.Session("bg-after", tier="batch"):
    float(ht.sum(warm * 3.0))
d = autoscale.stats()["decisions"]
assert d["shed_on"] == 1 and d["shed_off"] == 1 and d["errors"] == 0, d
autoscale.disarm()
health_runtime.set_slo(dispatch_ms=None)
print(f"autoscale leg: 0 interactive failures, {len(shed_hits)} batch "
      f"sheds, decisions={d}")
PY
# static-analysis leg (heat_tpu/analysis): the AST lint must be clean
# against the committed baseline (zero NEW findings — suppressions carry
# their justifications inline), the AOT program auditor over a cache
# warmed with the bench-shaped workloads at mesh 8 must report zero
# replication-blowup / collective-parity / budget findings, and the
# distribution-flow verifier (interprocedural split/sharding abstract
# interpretation, rules S101-S105) must verify the library + examples
# clean against the same (namespace-shared) baseline
echo "=== static analysis (heat-lint + program audit + heat-verify) ==="
python -m heat_tpu.analysis lint heat_tpu examples --baseline heat-lint-baseline.json
python -m heat_tpu.analysis audit --warm bench --devices 8
python -m heat_tpu.analysis verify heat_tpu examples --baseline heat-lint-baseline.json
# the coverage gate (reference codecov.yml target semantics): the merged
# matrix coverage must clear the floor or the matrix run fails. On runtimes
# without sys.monitoring (Python < 3.12) no cov_mesh*.json legs are produced
# — a green matrix must not then die on a FileNotFoundError, so the merge
# only runs over legs that actually exist and is skipped when there are none.
produced=()
for leg in "${legs[@]}"; do
  [ -f "$leg" ] && produced+=("$leg")
done
if [ "${#produced[@]}" -eq 0 ]; then
  if python -c 'import sys; sys.exit(0 if sys.version_info >= (3, 12) else 1)'; then
    # sys.monitoring IS available here — zero legs means the coverage
    # pipeline itself broke (e.g. the atexit dump failing); fail loudly
    echo "ERROR: no coverage legs produced although Python >= 3.12 supports sys.monitoring" >&2
    exit 1
  fi
  echo "coverage: no cov_mesh*.json legs produced (sys.monitoring needs Python >= 3.12); skipping merge/gate"
else
  python scripts/heat_coverage.py merge "$COV_DIR/coverage_merged.json" \
    --fail-under "${HEAT_TPU_COV_MIN:-60}" "${produced[@]}"
fi
