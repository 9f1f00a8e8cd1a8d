.PHONY: check test fast bench bench-pipeline overlap obs serving \
	serve-kernel smoke lint multidevice

# tier-1 suite + REPRO_FORCE_REF=1 re-run (Pallas kernels, then their oracles)
# + e2e launcher smoke with gradient accumulation (K>1) + probe smoke
# + lint + JSONL metrics-contract guard — mirrors the CI full job
check:
	sh tools/check.sh

test:
	PYTHONPATH=src python -m pytest -x -q

# CI fast lane: everything not marked slow / diagnostics
fast:
	PYTHONPATH=src python -m pytest -q -m "not slow and not diagnostics"

# CI multidevice lane: distribution numerics on 8 fabricated CPU
# devices — shard_map train-step parity, DP controller (D,K)
# retargeting, cross-mesh checkpoint round-trips, GSPMD parity
multidevice:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
	    python -m pytest -q tests/test_mesh_train.py \
	    tests/test_sharding_multidevice.py

# ruff lint (config in pyproject.toml); CI fails on findings
lint:
	ruff check .

bench:
	PYTHONPATH=src:. python benchmarks/bench_kernels.py

# async host/device overlap bench: instrumented sync vs async step
# loop (MetricRing + BufferedSink + PrefetchingStream) with metric
# parity + 2-pallas_call assertions; writes BENCH_pipeline.json
bench-pipeline:
	PYTHONPATH=src:. python benchmarks/bench_pipeline.py

# the async overlap subsystem's test tier (also part of `make check`)
overlap:
	PYTHONPATH=src python -m pytest -q -m overlap

# observability tier: span tracer + trace-v1 schema + layerwise
# trust-ratio telemetry (oracle parity, 2-pallas_call invariant,
# <=3% tracing overhead budget) + render/report/bench-gate tools
obs:
	PYTHONPATH=src python -m pytest -q -m obs

# serving tier: continuous-batching engine == per-request generate
# (greedy, staggered arrivals), batched prefill == token-by-token
# oracle, zero decode recompiles across occupancy changes, paged KV
# reuse after eviction, mesh-restored weights serve identically
serving:
	PYTHONPATH=src python -m pytest -q -m serving

# fused decode-kernel parity slice of the serving tier, run under BOTH
# dispatch modes: Pallas (interpret on CPU) and the REPRO_FORCE_REF=1
# jnp oracle — kernel == oracle == jnp on f32/bf16 pools, ring
# wraparound, engine token parity, compile-once decode
serve-kernel:
	PYTHONPATH=src python -m pytest -q tests/test_serving.py \
	    -k "kernel or bf16_cache or wraparound"
	REPRO_FORCE_REF=1 PYTHONPATH=src python -m pytest -q \
	    tests/test_serving.py -k "kernel or bf16_cache"

# end-to-end CPU smoke of the launcher: global batch 8 = 4 accumulated
# microbatches of 2, optimizer applied once per global step — then the
# diagnostics probe smoke (tiny MLP, 2-iteration Lanczos, JSONL sink
# schema-validated in a tempdir)
smoke:
	PYTHONPATH=src python -m repro.launch.train --smoke --steps 2 \
	    --seq 64 --global-batch 8 --microbatch 2 --log-every 1
	PYTHONPATH=src python -m repro.diagnostics.smoke
