"""Serving launcher: continuous-batching engine on the host mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b \
        --smoke --requests 8 --prompt-len 16 --num-tokens 32

Builds a :class:`repro.serving.Engine` (fixed-slot decode batch, paged
KV cache, batched prefill admission), submits an open set of requests
— half up front, half injected mid-flight to exercise continuous
batching — and reports throughput plus the engine's compile/page
accounting. ``--restore DIR`` loads weights through the sharding-aware
checkpoint reader onto the requested mesh instead of initialising.

``--use-kernel`` routes decode attention through the fused Pallas
kernel, ``--cache-dtype bfloat16`` stores the KV pool in bf16, and
``--trace-out PATH`` exports per-phase engine spans
(admit/prefill/decode/sample/finish) as trace-v1 JSONL.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import serving
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.diagnostics import sink as diag_sink
from repro.launch import compile_cache, sharding
from repro.launch.mesh import make_host_mesh
from repro.models import extra_embed_shape, get_model
from repro.obs import trace as obs_trace


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2.5-3b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--num-tokens", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--restore", default=None,
                    help="checkpoint dir to restore params from")
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--use-kernel", action="store_true",
                    help="fused Pallas attention-decode kernel")
    ap.add_argument("--cache-dtype", default=None,
                    choices=("float32", "bfloat16"),
                    help="KV pool storage dtype (default: compute dtype)")
    ap.add_argument("--trace-out", default=None,
                    help="write engine phase spans (trace-v1 JSONL)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    mesh = make_host_mesh(args.data_parallel, args.model_parallel)
    max_len = args.prompt_len + args.num_tokens
    pages = -(-max_len // args.page_size)
    sc = serving.ServeConfig(
        slots=args.slots, max_len=pages * args.page_size,
        page_size=args.page_size, prefill_batch=args.slots,
        sampling=serving.SamplingParams(temperature=args.temperature),
        use_kernel=args.use_kernel, cache_dtype=args.cache_dtype)
    tracer = obs_trace.Tracer() if args.trace_out else obs_trace.NULL

    extra = None
    es = extra_embed_shape(cfg, sc.slots)
    if es is not None:
        extra = jnp.zeros(es, cfg.cdtype)  # stubbed modality frontend

    with mesh:
        if args.restore:
            eng = serving.Engine.from_checkpoint(
                args.restore, model, sc,
                mesh=mesh if mesh.size > 1 else None, extra=extra,
                tracer=tracer)
        else:
            params = model.init(jax.random.PRNGKey(0))
            if mesh.size > 1:
                params_sh = sharding.named(
                    mesh, sharding.state_pspecs(mesh, jax.eval_shape(
                        lambda: params)))
                params = jax.device_put(params, params_sh)
            eng = serving.Engine(model, params, sc, extra=extra,
                                 tracer=tracer)

        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, cfg.vocab_size, size=args.prompt_len)
                   for _ in range(args.requests)]
        head, tail = prompts[:len(prompts) // 2], prompts[len(prompts) // 2:]

        t0 = time.perf_counter()
        for p in head:
            eng.submit(p, max_new_tokens=args.num_tokens)
        results = []
        for _ in range(3):                    # in-flight injection
            results.extend(eng.step())
        for p in tail:
            eng.submit(p, max_new_tokens=args.num_tokens)
        results.extend(eng.drain())
        elapsed = time.perf_counter() - t0

    toks = sum(len(r.tokens) for r in results)
    stats = eng.stats()
    print(f"{args.arch}: {len(results)} requests, {toks} tokens in "
          f"{elapsed:.2f}s ({toks / elapsed:.1f} tok/s) — "
          f"slots={sc.slots} max_len={sc.max_len} "
          f"page_size={sc.page_size}")
    print(f"decode compiled {stats['decode_compilations']}x, prefill "
          f"{stats['prefill_compilations']}x; pages: "
          f"{stats['allocations']} allocs, {stats['reused_pages']} "
          f"reused")
    print("sample:", results[0].tokens[:16])
    if args.trace_out:
        summary = obs_trace.phase_summary(tracer.events())
        for name, row in summary.items():
            print(f"  span {name}: n={row['count']} "
                  f"total={row['total_ms']:.1f}ms "
                  f"mean={row['mean_us']:.0f}us")
        with diag_sink.JsonlSink(args.trace_out) as tsink:
            n_trace = tracer.export(tsink)
        print(f"trace -> {args.trace_out} ({n_trace} records)")


if __name__ == "__main__":
    main()
