#!/usr/bin/env bash
# CI entry (the cibuild/*.sh analog): native build, test suite on the
# virtual 8-device CPU mesh, driver entry checks, CPU bench smoke.
#
# Test tiers (single-core box: compile time dominates):
#   cibuild/smoke.sh          — curated subset, quick green (~2.5 min)
#   pytest -q                 — everything but slow-marked (~10-15 min)
#   DEEPREC_FULL_TESTS=1 ...  — the full grid incl. multi-process launches
# This script runs the default tier; pass SMOKE=1 for the quick tier.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== native build =="
make -C deeprec_tpu/native

echo "== static analysis (fast fail: retrace/host-sync/layout/thread-safety lints, docs/analysis.md) =="
env JAX_PLATFORMS=cpu python -m deeprec_tpu.analysis --check

if [[ "${SMOKE:-0}" == "1" ]]; then
  echo "== tests (smoke tier) =="
  env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      bash cibuild/smoke.sh
else
  echo "== tests (virtual 8-device CPU mesh) =="
  env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m pytest tests/ -q
fi

echo "== driver entries =="
env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "== dedup engine microbench (CPU smoke: both paths compile) =="
env JAX_PLATFORMS=cpu python tools/bench_dedup.py --smoke

echo "== traffic-diet microbench (CPU smoke: diet + legacy-apply arms) =="
env JAX_PLATFORMS=cpu python tools/bench_lookup.py --traffic --smoke

echo "== fused sparse step (CPU smoke: interpret-mode parity + modeled HBM diet gate) =="
env JAX_PLATFORMS=cpu python tools/bench_lookup.py --fused-step \
    --smoke --dim 128 --out /tmp/deeprec_fused_smoke.json
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-fused /tmp/deeprec_fused_smoke.json

echo "== host input pipeline bench (CPU smoke: vectorized block parse vs serial line parser, N-worker stream parity, training-thread pop cost) =="
env JAX_PLATFORMS=cpu python tools/bench_input.py --smoke \
    --out /tmp/deeprec_input_smoke.json

echo "== input pipeline gate (block parse ≥2× serial, bit-identical batch stream at every worker count, zero training-thread regression) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-input /tmp/deeprec_input_smoke.json

echo "== checkpoint choreography microbench (CPU smoke: sync + async paths) =="
env JAX_PLATFORMS=cpu python tools/bench_ckpt.py --smoke

echo "== serving bench (CPU smoke: group dispatch + 2-process socket tier + int8 residency + grouped two-tower, delta updates mid-load, /v1/stats) =="
env JAX_PLATFORMS=cpu python tools/bench_serving.py --smoke \
    --out /tmp/deeprec_serving_smoke.json

echo "== fleet bench (CPU smoke: lease discovery, rolling restart of every backend via EXIT_RESCALE respawn, 2->4->2 autoscale, torn lease — zero failed requests) =="
env JAX_PLATFORMS=cpu python tools/bench_fleet.py --smoke \
    --out /tmp/deeprec_serving_smoke.json

echo "== serving scale-out / quantized residency / grouped / fleet gates (drift fails the smoke) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-serving /tmp/deeprec_serving_smoke.json

echo "== obs overhead gate, serving arm (telemetry plane ≤2% + /metrics parses) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-obs /tmp/deeprec_serving_smoke.json

echo "== compute-reuse gate (zipf arm ≥2× effective qps, hit-rate floor, bit-identity, publish dip+recovery, 0 steady compiles) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-reuse /tmp/deeprec_serving_smoke.json

echo "== retrieval bench (CPU smoke: 1M-item blocked top-k sweep, int8 + fp32 residency, recall vs exact scan, gather baseline, delta-fold freshness, trace guard) =="
env JAX_PLATFORMS=cpu python tools/bench_retrieval.py --smoke \
    --out /tmp/deeprec_retrieval_smoke.json

echo "== full-corpus retrieval gate (recall/speedup/freshness/residency/compile drift fails the smoke) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-retrieval /tmp/deeprec_retrieval_smoke.json

echo "== freshness bench (CPU smoke: online loop, trainer SIGKILL + supervised restart, zero failed requests) =="
env JAX_PLATFORMS=cpu python tools/bench_freshness.py --smoke

echo "== guard bench (CPU smoke: poison matrix — NaN/extreme/label-flip/replays + exploding-LR window; sentinel detects ≤1 dispatch, rollback+quarantine, canary gate, AUC floor, zero failed requests) =="
env JAX_PLATFORMS=cpu python tools/bench_guard.py --smoke \
    --out /tmp/deeprec_guard_smoke.json

echo "== model-quality firewall gate (drift fails the smoke) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-guard /tmp/deeprec_guard_smoke.json

echo "== bench (CPU smoke; real numbers come from TPU) =="
env JAX_PLATFORMS=cpu BENCH_SMOKE=1 \
    BENCH_PIPELINE=grid python bench.py --placement --mesh --tier-paging --smoke \
    | tee /tmp/deeprec_bench_smoke.out
tail -n 1 /tmp/deeprec_bench_smoke.out > /tmp/deeprec_bench_smoke.json

echo "== traffic model vs measured op counts (drift fails the smoke) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-traffic /tmp/deeprec_bench_smoke.json

echo "== in-step pipelining grid vs overlap model (regression fails the smoke) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-overlap /tmp/deeprec_bench_smoke.json

echo "== skew-aware placement vs uniform hash + drifting-skew replanning (imbalance/drift gates fail the smoke: auto replan, recovery, zero a2a overflow, per-dest budget diet) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-imbalance /tmp/deeprec_bench_smoke.json

echo "== pod-scale 2-D mesh gate (hier inter-tier wire diet vs flat a2a, bitwise loss parity, zero overflow/steady compiles, nested K-scan bound) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-hierarchy /tmp/deeprec_bench_smoke.json

echo "== overlapped tier paging gate (fresh-init loss ≥10× lower with paging on, 0 steady fold compiles, fold stall ≤ sync stall; step tol loose on single-core CI, --overlap-tol precedent) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-tier /tmp/deeprec_bench_smoke.json \
    --tier-step-tol 0.5

echo "== steady-state retrace gate (compiles inside timed windows fail the smoke) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-compiles /tmp/deeprec_bench_smoke.json

echo "== obs overhead gate, K-step scan arm (telemetry plane ≤2% + registry renders) =="
env JAX_PLATFORMS=cpu \
    python tools/roofline.py --assert-obs /tmp/deeprec_bench_smoke.json

echo "== bench (CPU smoke, budgets disabled: legacy dedup path compiles) =="
env JAX_PLATFORMS=cpu BENCH_SMOKE=1 \
    BENCH_TIMED_STEPS=4 BENCH_K=4 BENCH_PIPELINE=off \
    python bench.py --unique-budget off
