#!/usr/bin/env sh
# One-shot verification — the same tiers CI runs as separate named
# steps (.github/workflows/ci.yml), plus lint and the JSONL metrics
# contract guard:
#   1. tier-1 suite on the default (Pallas interpret) dispatch
#   2. kernel-adjacent tests again under REPRO_FORCE_REF=1 so BOTH
#      dispatch modes (pallas kernels and pure-jnp oracles) run
#   3. CPU end-to-end launcher smoke with gradient accumulation (K=4),
#      streaming metrics to experiments/bench/smoke_launcher.jsonl
#   4. diagnostics probe smoke (tiny MLP, 2 Lanczos iters, JSONL schema)
#   4b. kernel bench quick sweep — writes the machine-readable
#      experiments/bench/BENCH_kernels.json trajectory (per-precision
#      us/step, pallas_call counts, modeled HBM bytes/step)
#   4c. async overlap tier (-m overlap): delayed-metrics bit-parity,
#      BufferedSink byte-identity, PrefetchingStream switch-at-step-N
#      sample identity, adaptive probe cadence
#   4d. async launcher smoke (--prefetch 2 --async-metrics 4) + the
#      pipeline bench quick run — writes BENCH_pipeline.json (overlap
#      ratio, metric parity, bucketing pad waste)
#   4e. observability tier (-m obs): span tracer + trace-v1 schema,
#      layerwise telemetry oracle parity + 2-pallas_call invariant,
#      <=3% tracing overhead budget, render/report/bench-gate tools
#   4f. traced launcher smoke (--trace-out --layerwise-every) +
#      Perfetto render + trace-v1 schema validation + bench gate vs
#      the committed benchmarks/baselines/BENCH_kernels.json
#      (advisory: || true — wall-clock noise must not fail check)
#   4g. serving tier (-m serving): continuous-batching engine ==
#      per-request generate (greedy, staggered arrivals), batched
#      prefill == token-by-token oracle, zero decode recompiles,
#      paged KV reuse, mesh-restored weights, fused decode-kernel
#      parity (kernel == oracle == jnp on f32/bf16 pools, ring
#      wraparound) — the kernel tests re-run under REPRO_FORCE_REF=1
#      so the jnp oracle dispatch is exercised too — then the serve
#      launcher smokes (jnp, and --use-kernel --trace-out with span
#      validation)
#   5. multidevice: mesh-native numerics on 8 fabricated CPU devices
#      (shard_map train-step parity, DP controller (D,K) retargeting,
#      cross-mesh checkpoint round-trips; the GSPMD-parity subprocess
#      tests already ran in tier 1) + a mesh-native launcher smoke
#      (D=2 shard_map step)
# then ruff lint (skipped with a notice when ruff is not installed) and
# tools/validate_metrics.py over the smoke traces, so MetricsSink schema
# drift fails here and in CI, not in a downstream notebook.
# Run from the repo root:  make check
set -eu

cd "$(dirname "$0")/.."
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export PYTHONPATH

echo "== tier-1 (Pallas interpret kernels) =="
python -m pytest -x -q

echo "== kernel-oracle re-run (REPRO_FORCE_REF=1) =="
REPRO_FORCE_REF=1 python -m pytest -q \
    tests/test_kernels.py tests/test_segmented_parity.py \
    tests/test_optimizers.py tests/test_precision.py

echo "== e2e launcher smoke (gradient accumulation K=4) =="
python -m repro.launch.train --smoke --steps 2 --seq 64 \
    --global-batch 8 --microbatch 2 --log-every 1 \
    --metrics-out experiments/bench/smoke_launcher.jsonl

echo "== diagnostics probe smoke (tiny MLP, 2 Lanczos iters, JSONL schema) =="
python -m repro.diagnostics.smoke --out experiments/bench

echo "== kernel bench quick sweep (experiments/bench/BENCH_kernels.json) =="
PYTHONPATH="src:.:$PYTHONPATH" python benchmarks/bench_kernels.py --quick

echo "== async overlap tier (-m overlap: metric ring, buffered sink, prefetch, cadence) =="
python -m pytest -q -m overlap

echo "== async launcher smoke (prefetch + async metrics, JSONL parity-checked schema) =="
python -m repro.launch.train --smoke --steps 2 --seq 64 \
    --global-batch 8 --microbatch 2 --log-every 1 \
    --prefetch 2 --async-metrics 4 \
    --metrics-out experiments/bench/smoke_async_launcher.jsonl

echo "== pipeline bench quick run (experiments/bench/BENCH_pipeline.json) =="
PYTHONPATH="src:.:$PYTHONPATH" python benchmarks/bench_pipeline.py --quick

echo "== observability tier (-m obs: tracer, trace-v1 schema, layerwise telemetry, overhead budget) =="
python -m pytest -q -m obs

echo "== traced launcher smoke (--trace-out + --layerwise-every) =="
python -m repro.launch.train --smoke --steps 3 --seq 64 \
    --global-batch 8 --microbatch 2 --use-kernel fused --log-every 1 \
    --metrics-out experiments/bench/smoke_obs_launcher.jsonl \
    --trace-out experiments/bench/smoke_trace.jsonl \
    --layerwise-every 2
python tools/render_trace.py experiments/bench/smoke_trace.jsonl \
    -o experiments/bench/smoke_trace.perfetto.json
python tools/validate_metrics.py experiments/bench/smoke_trace.jsonl \
    --min-trace-records 9

echo "== bench regression gate (advisory: compares against committed baseline) =="
python tools/bench_compare.py benchmarks/baselines/BENCH_kernels.json \
    experiments/bench/BENCH_kernels.json || \
    echo "bench_compare: ADVISORY failure (wall-clock noise is expected off dedicated hardware)"

echo "== serving tier (-m serving: engine parity, paged KV reuse, compile-once decode, fused decode kernel) =="
python -m pytest -q -m serving

echo "== decode-kernel parity re-run (REPRO_FORCE_REF=1: jnp oracle dispatch) =="
REPRO_FORCE_REF=1 python -m pytest -q tests/test_serving.py \
    -k "kernel or bf16_cache"

echo "== serve launcher smoke (continuous-batching engine, mid-flight admission) =="
python -m repro.launch.serve --arch qwen2.5-3b --smoke --requests 6 \
    --prompt-len 12 --num-tokens 8 --slots 3

echo "== serve launcher kernel smoke (--use-kernel, traced engine phases) =="
python -m repro.launch.serve --arch gemma3-12b --smoke --requests 4 \
    --prompt-len 8 --num-tokens 8 --slots 3 --page-size 8 \
    --use-kernel --trace-out experiments/bench/smoke_serve_trace.jsonl
python tools/validate_metrics.py \
    experiments/bench/smoke_serve_trace.jsonl --min-trace-records 5

echo "== multidevice (8 fabricated CPU devices: shard_map parity, DP controller, sharded ckpts; GSPMD parity ran in tier 1) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest -q tests/test_mesh_train.py

echo "== mesh-native launcher smoke (D=2, K=2, shard_map step) =="
python -m repro.launch.train --smoke --steps 2 --seq 64 \
    --global-batch 8 --microbatch 2 --mesh-data 2 --log-every 1 \
    --metrics-out experiments/bench/smoke_mesh_launcher.jsonl

echo "== lint (ruff) =="
if command -v ruff >/dev/null 2>&1; then
    ruff check .
else
    echo "ruff not installed; skipping lint (CI runs it)"
fi

echo "== JSONL metrics contract (tools/validate_metrics.py) =="
python tools/validate_metrics.py \
    experiments/bench/smoke_launcher.jsonl \
    experiments/bench/smoke_async_launcher.jsonl \
    experiments/bench/smoke_mesh_launcher.jsonl \
    experiments/bench/smoke_obs_launcher.jsonl \
    experiments/bench/probe_smoke.jsonl \
    experiments/bench/trace_smoke.jsonl

echo "check: OK"
