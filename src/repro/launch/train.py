"""Training launcher.

Builds the mesh from the available devices, shards TrainState + batches
with the production rules, and runs the jit'd train_step on synthetic LM
data.

Distributed execution (``--mesh-data D`` / ``--mesh-model M``): an
EXPLICIT ``--mesh-data D`` with ``M == 1`` and ``D > 1`` selects the
MESH-NATIVE data-parallel path — loss + accumulation under
``shard_map`` over the ``data`` axis, params/optimizer state
replicated, grads psum-averaged in f32, the fused optimizer still
exactly two ``pallas_call``s per device — and the global batch is
``K × D × microbatch`` (``--microbatch`` is PER-DEVICE there).  With
``M > 1``, or via the legacy ``--data-parallel`` spelling, the GSPMD
path (fsdp + TP in_shardings, ``--microbatch`` global) runs
instead.  On CPU, ``D×M > 1`` fabricates host devices automatically
by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=D*M`` before the
first jax device access (the flag only affects the host platform, so
it is inert on real TPU/GPU runs).

Large-batch execution: ``--global-batch`` is the total samples per
optimizer step and ``--microbatch`` the per-device-pass batch; when they
differ the step scan-accumulates K = global/(micro·D) microbatches in
f32 and applies the optimizer once per global step (two
``pallas_call``s under ``--use-kernel fused``, regardless of K).
``--precision bf16_master[_sr]`` additionally stores the fused
substrate's momentum/Adam state in bf16 (f32 master params, strictly
f32 norm/table accumulation — see ``repro.core.layerwise``), halving
optimizer-state bytes per step. The
optimizer/schedule are built from the *global* batch size — that is
what the paper's batch-size LR scaling (§5.2.2) and TVLARS's γ_min
(§5.2.1) key off.

Sharpness probes (``repro.diagnostics``): ``--probe-every N`` runs an
m-step Lanczos λ_max(H) probe on a held batch every N steps (a
separate jitted computation — the train step and its 2-``pallas_call``
invariant are untouched); ``--metrics-out`` streams every step's
metrics plus the probe trace to JSONL.

Adaptive batch size (``--adaptive-batch``): a gradient-noise-scale
probe closes the loop — every ``--controller-every`` steps the
McCandlish B_noise estimate retargets the global batch by changing K
at fixed ``--microbatch`` (peak memory never moves), clamped to
``[--batch-min, --batch-max]``, with the LR re-scaled to the current
batch; decisions stream as ``controller/*`` metrics.

Observability (``repro.obs``): ``--trace-out trace.jsonl`` records
host-side spans (data_wait / dispatch / resolve / probe / controller /
produce) into a bounded ring and exports them as trace-v1 JSONL —
render with ``tools/render_trace.py``, summarize with
``tools/obs_report.py``.  ``--layerwise-every N`` streams the paper's
per-layer ``(w_norm, g_norm, trust_ratio)`` triples as
``layerwise/{param}/{metric}`` metrics every N steps, read straight
off the trust table the optimizer already computes (zero extra
``pallas_call``s).  ``--profile-dir`` captures a ``jax.profiler``
trace over a ``--profile-start``/``--profile-steps`` window.

Usage:
  python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --optimizer tvlars --steps 20 --global-batch 8 --microbatch 2 \
      --probe-every 5 --metrics-out /tmp/run.jsonl \
      --trace-out /tmp/trace.jsonl --layerwise-every 5
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import build_optimizer
from repro.core import labels as labels_lib
from repro.core.layerwise import PRECISIONS
from repro.data import pipeline
from repro.data.synthetic import lm_batch, lm_sample_source
from repro.diagnostics import probes
from repro.diagnostics import sink as diag_sink
from repro.launch import compile_cache, sharding
from repro.launch.mesh import make_host_mesh
from repro.models import extra_embed_shape, get_model
from repro.models import layers as layers_lib
from repro.obs import layerwise as obs_layerwise
from repro.obs import profiler as obs_profiler
from repro.obs import trace as obs_trace
from repro.training import tasks
from repro.training.controller import (AdaptiveBatchController,
                                       ControllerConfig)
from repro.training.train_state import TrainState, replicate
from repro.training.trainer import MetricRing, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2.5-3b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-trainable)")
    ap.add_argument("--optimizer", default="tvlars")
    ap.add_argument("--learning-rate", type=float, default=2.0)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8,
                    help="alias for --global-batch (kept for back-compat)")
    ap.add_argument("--global-batch", type=int, default=None,
                    help="total samples per optimizer step "
                         "(default: --batch)")
    ap.add_argument("--microbatch", type=int, default=None,
                    help="per-device-pass batch; K = global/micro grads "
                         "are accumulated (default: --global-batch)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--use-kernel", default="off",
                    choices=("off", "per_tensor", "fused"),
                    help="optimizer dispatch path: 'fused' runs the "
                         "whole update as two segmented pallas_calls "
                         "(see repro.core.layerwise)")
    ap.add_argument("--precision", default="f32", choices=PRECISIONS,
                    help="fused-substrate storage policy: 'bf16_master' "
                         "stores momentum/Adam state in bf16 with f32 "
                         "master params + f32 norm accumulation (half "
                         "the optimizer-state bytes); '_sr' adds "
                         "stochastic rounding on the state write-back. "
                         "Non-f32 requires --use-kernel fused")
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--mesh-data", type=int, default=None,
                    help="data axis of the device mesh (alias of "
                         "--data-parallel); D > 1 with --mesh-model 1 "
                         "runs the shard_map data-parallel step with "
                         "the batch sharded over D devices "
                         "(--microbatch is PER DEVICE). On CPU, "
                         "missing devices are fabricated via "
                         "XLA_FLAGS=--xla_force_host_platform_"
                         "device_count automatically")
    ap.add_argument("--mesh-model", type=int, default=None,
                    help="model axis of the device mesh (alias of "
                         "--model-parallel); M > 1 uses the legacy "
                         "GSPMD fsdp+TP path")
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--probe-every", type=int, default=0,
                    help="run the Lanczos sharpness probe every N steps "
                         "(0 = off); probes are separate jitted "
                         "computations on a held batch — the train "
                         "step is untouched")
    ap.add_argument("--probe-topk", type=int, default=1,
                    help="how many top Hessian eigenvalues to report")
    ap.add_argument("--probe-iters", type=int, default=8,
                    help="Lanczos iterations per probe")
    ap.add_argument("--probe-no-reorth", action="store_true",
                    help="skip full reorthogonalization; the stored "
                         "Krylov basis is iters x params floats, so "
                         "disable it for full-size (non --smoke) archs")
    ap.add_argument("--metrics-out", default=None,
                    help="stream per-step metrics + probe results to "
                         "this JSONL file (see repro.diagnostics.sink)")
    ap.add_argument("--adaptive-batch", action="store_true",
                    help="close the loop: a gradient-noise-scale probe "
                         "retargets the global batch (accum_steps K at "
                         "fixed --microbatch) every --controller-every "
                         "steps, with the LR re-scaled to the current "
                         "batch (see repro.training.controller)")
    ap.add_argument("--batch-min", type=int, default=None,
                    help="adaptive-batch lower clamp on the global "
                         "batch (default: --microbatch)")
    ap.add_argument("--batch-max", type=int, default=None,
                    help="adaptive-batch upper clamp on the global "
                         "batch (default: 4x the starting global batch)")
    ap.add_argument("--controller-every", type=int, default=5,
                    help="adaptive-batch decision cadence in steps")
    ap.add_argument("--prefetch", type=int, default=0, metavar="N",
                    help="prefetch N batches on a background producer "
                         "thread (0 = off; 2 = double buffering): batch "
                         "generation + host->device transfer of step "
                         "i+1 overlap the compute of step i (see "
                         "data.pipeline.PrefetchingStream; composes "
                         "with --adaptive-batch via its drain/refill "
                         "retarget contract)")
    ap.add_argument("--async-metrics", type=int, default=0, metavar="W",
                    help="resolve per-step metrics W steps late through "
                         "a bounded in-flight ring instead of blocking "
                         "on every step's device values (0 = off; "
                         "exact same numbers, delayed materialization), "
                         "and buffer JSONL writes onto a writer thread "
                         "(diagnostics.BufferedSink)")
    ap.add_argument("--layerwise-every", type=int, default=0, metavar="N",
                    help="emit the per-layer (w_norm, g_norm, "
                         "trust_ratio) stream every N steps (0 = off) "
                         "as layerwise/{param}/{metric} metrics — read "
                         "straight off the fused step's host trust "
                         "table, zero extra pallas_calls (see "
                         "repro.obs.layerwise)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record host-side spans (data_wait / dispatch "
                         "/ resolve / probe / controller / produce) and "
                         "write them as trace-v1 JSONL here; render "
                         "with tools/render_trace.py, summarize with "
                         "tools/obs_report.py")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace into DIR over "
                         "the [--profile-start, +--profile-steps) "
                         "step window")
    ap.add_argument("--profile-start", type=int, default=1,
                    help="first step of the profiler window (default 1 "
                         "— skips the compile step)")
    ap.add_argument("--profile-steps", type=int, default=3,
                    help="length of the profiler window in steps")
    args = ap.parse_args()
    if args.layerwise_every < 0:
        raise SystemExit(f"--layerwise-every {args.layerwise_every} "
                         f"must be >= 0")
    if args.prefetch < 0 or args.async_metrics < 0:
        raise SystemExit(f"--prefetch {args.prefetch} and "
                         f"--async-metrics {args.async_metrics} must "
                         f"be >= 0")

    mesh_data = args.mesh_data if args.mesh_data is not None \
        else args.data_parallel
    mesh_model = args.mesh_model if args.mesh_model is not None \
        else args.model_parallel
    if mesh_data < 1 or mesh_model < 1:
        raise SystemExit(f"--mesh-data {mesh_data} and --mesh-model "
                         f"{mesh_model} must be >= 1")
    need = mesh_data * mesh_model
    flags = os.environ.get("XLA_FLAGS", "")
    if need > 1 and "xla_force_host_platform_device_count" not in flags:
        # fabricate host devices BEFORE the first jax device access;
        # the flag only affects the host (CPU) platform, so it is
        # inert on real TPU/GPU backends
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={need}"
        ).strip()
    # the shard_map DP path (batch over devices, params replicated) is
    # opted into by the EXPLICIT --mesh-data flag; legacy
    # --data-parallel keeps its GSPMD semantics (--microbatch stays a
    # global per-pass size there, vs per-device under mesh-native)
    mesh_native = args.mesh_data is not None and mesh_model == 1 \
        and mesh_data > 1
    compile_cache.enable()

    global_batch = args.global_batch if args.global_batch is not None \
        else args.batch
    microbatch = args.microbatch if args.microbatch is not None \
        else global_batch
    if global_batch < 1 or microbatch < 1:
        raise SystemExit(f"--global-batch {global_batch} and --microbatch "
                         f"{microbatch} must be >= 1")
    # adaptive runs start at D=1 (the controller grows D itself), so
    # only the FIXED mesh-native path divides the pull by the data
    # width up front
    per_pull = microbatch * (
        mesh_data if mesh_native and not args.adaptive_batch else 1)
    if global_batch % per_pull:
        raise SystemExit(
            f"--global-batch {global_batch} must be divisible by "
            f"--microbatch x data width = {microbatch} x "
            f"{per_pull // microbatch} = {per_pull} (global batch is "
            f"K x D x per-device microbatch)")
    accum_steps = global_batch // per_pull

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "ssm" or cfg.family == "hybrid":
        assert args.seq % cfg.ssm_chunk == 0, \
            f"--seq must divide ssm_chunk={cfg.ssm_chunk}"
    model = get_model(cfg)
    try:
        mesh = make_host_mesh(mesh_data, mesh_model)
    except ValueError as e:
        raise SystemExit(str(e)) from e

    use_kernel = False if args.use_kernel == "off" else args.use_kernel
    if args.precision != "f32" and args.use_kernel != "fused":
        raise SystemExit(
            f"--precision {args.precision} requires --use-kernel fused "
            f"(the mixed-precision substrate IS the fused flat buffer)")

    # observability: host-span tracer (NULL when off — call sites never
    # branch), jax.profiler step window, layerwise telemetry switch
    tracer = obs_trace.Tracer() if args.trace_out else obs_trace.NULL
    profiler = obs_profiler.StepProfiler(
        args.profile_dir, start=args.profile_start,
        steps=args.profile_steps) if args.profile_dir else None
    layerwise = args.layerwise_every > 0

    def optimizer_for(batch_size: int):
        # schedules/γ_min see the TRUE global batch (samples per
        # optimizer step), not a token-count heuristic
        return build_optimizer(args.optimizer, total_steps=args.steps,
                               learning_rate=args.learning_rate,
                               batch_size=batch_size,
                               use_kernel=use_kernel,
                               precision=args.precision)

    controller = None
    if args.adaptive_batch:
        if need > 1 and not mesh_native:
            raise SystemExit(
                "--adaptive-batch composes with the shard_map data "
                "axis only: pass --mesh-data (with --mesh-model 1); "
                "the GSPMD fsdp+TP path has no re-stack boundary")
        if mesh_data & (mesh_data - 1):
            raise SystemExit(
                f"--adaptive-batch: --mesh-data {mesh_data} must be a "
                f"power of two (the controller snaps D to powers of "
                f"two)")
        batch_min = args.batch_min if args.batch_min is not None \
            else microbatch
        batch_max = args.batch_max if args.batch_max is not None \
            else 4 * global_batch
        try:
            ccfg = ControllerConfig(microbatch=microbatch,
                                    batch_min=batch_min,
                                    batch_max=batch_max,
                                    every=args.controller_every,
                                    data_max=mesh_data)
        except ValueError as e:
            raise SystemExit(f"--adaptive-batch: {e}") from e
        if global_batch % microbatch:
            raise SystemExit(
                f"--adaptive-batch: --global-batch {global_batch} must "
                f"be a multiple of --microbatch {microbatch}")
        # held GNS probe batch: stacked at K >= 2 (the estimator
        # contrasts per-microbatch vs accumulated gradient norms)
        k_probe = max(2, global_batch // microbatch)
        ptoks, plabels = lm_batch(jax.random.PRNGKey(998),
                                  k_probe * microbatch, args.seq,
                                  cfg.vocab_size)
        gns_batch = {"tokens": ptoks, "labels": plabels}
        es_probe = extra_embed_shape(cfg, k_probe * microbatch)
        if es_probe is not None:
            gns_batch["extra_embeds"] = jnp.zeros(es_probe, cfg.cdtype)
        gns_batch = pipeline.stack_microbatches(gns_batch, k_probe)
        if ccfg.data_max > 1:
            def make_step(opt_, k, mesh_):
                return make_train_step(model, opt_, accum_steps=k,
                                       mesh=mesh_, layerwise=layerwise)
        else:
            def make_step(opt_, k):
                return make_train_step(model, opt_, accum_steps=k,
                                       layerwise=layerwise)
        try:
            controller = AdaptiveBatchController(
                make_step,
                optimizer_for,
                probes.GradNoiseProbe(tasks.lm_task(model), gns_batch,
                                      accum_steps=k_probe,
                                      every=args.controller_every),
                # init_data_parallel=None: the controller fills the
                # data axis from step 0 (fill-data-first policy)
                ccfg, init_batch=global_batch,
                base_lr=args.learning_rate,
                # same donation policy as the fixed path / trainer.fit
                donate=jax.default_backend() in ("tpu", "gpu"))
        except ValueError as e:
            raise SystemExit(f"--adaptive-batch: {e}") from e

    opt = controller.optimizer() if controller is not None \
        else optimizer_for(global_batch)
    rng = jax.random.PRNGKey(0)

    with mesh:
        if mesh.size > 1 and not mesh_native:
            layers_lib.set_batch_sharding(
                ("data",) if microbatch % mesh_data == 0 else None,
                model_size=mesh_model, mesh=mesh)
        state = TrainState.create(model.init(rng), opt)
        if mesh_native:
            # shard_map DP: params + flat substrate replicated over
            # the data axis; the step psums grads internally
            state = replicate(state, mesh) if controller is None \
                else state
        else:
            state_sh = sharding.named(
                mesh, sharding.state_pspecs(
                    mesh, jax.eval_shape(lambda: state), fsdp=True))
            state = jax.device_put(state, state_sh)
        stream = None
        if controller is not None:
            # sample-level source: position-preserving across K switches
            base_src = lm_sample_source(args.seq, cfg.vocab_size)

            def sample_src(start, count):
                b = base_src(start, count)
                es_b = extra_embed_shape(cfg, count)
                if es_b is not None:
                    b["extra_embeds"] = jnp.zeros(es_b, cfg.cdtype)
                return b

            stream = pipeline.MicrobatchedStream(sample_src, microbatch,
                                                 accum_steps=accum_steps)
            if args.prefetch > 0:
                # batch generation moves to the producer thread; the
                # controller's retargets drain/refill the buffer so
                # switch-at-step-N stays sample-identical (placement is
                # left to the controller's run step, which shards per
                # current D)
                stream = pipeline.PrefetchingStream(stream,
                                                    size=args.prefetch,
                                                    tracer=tracer)
            controller.attach(stream)
            step_fn = None
        elif mesh_native:
            step_fn = jax.jit(make_train_step(model, opt,
                                              accum_steps=accum_steps,
                                              mesh=mesh,
                                              layerwise=layerwise),
                              donate_argnums=(0,))
        else:
            step_fn = jax.jit(make_train_step(model, opt,
                                              accum_steps=accum_steps,
                                              layerwise=layerwise),
                              in_shardings=(state_sh, None),
                              donate_argnums=(0,))

        es = extra_embed_shape(cfg, global_batch)
        batch_dim = 1 if accum_steps > 1 else 0
        fixed_iter = None
        if controller is None:
            def fixed_batches():
                for j in range(args.steps):
                    toks, labels = lm_batch(jax.random.fold_in(rng, j),
                                            global_batch, args.seq,
                                            cfg.vocab_size)
                    b = {"tokens": toks, "labels": labels}
                    if es is not None:
                        b["extra_embeds"] = jnp.zeros(es, cfg.cdtype)
                    if accum_steps > 1:
                        b = pipeline.stack_microbatches(b, accum_steps)
                    yield b

            if args.prefetch > 0:
                place = (lambda b: pipeline.shard_batch(
                    mesh, b, batch_dim=batch_dim)) if mesh.size > 1 \
                    else pipeline.device_put_batch
                fixed_iter = pipeline.PrefetchingStream(
                    fixed_batches(), size=args.prefetch, place=place,
                    tracer=tracer)
            else:
                def _placed():
                    for b in fixed_batches():
                        if mesh.size > 1:
                            b = pipeline.shard_batch(mesh, b,
                                                     batch_dim=batch_dim)
                        yield b
                fixed_iter = _placed()
        print(f"global_batch={global_batch} microbatch={microbatch} "
              f"accum_steps={accum_steps} "
              f"data_parallel={mesh_data if mesh_native else 1} "
              f"mesh={tuple(mesh.shape.items())} "
              f"use_kernel={args.use_kernel} precision={args.precision}")

        static = {"arch": args.arch, "optimizer": args.optimizer}
        if controller is None:
            # adaptive runs carry the CURRENT batch per record instead
            static["global_batch"] = global_batch
        sink = diag_sink.JsonlSink(args.metrics_out, static=static) \
            if args.metrics_out else None
        if sink is not None and args.async_metrics > 0:
            # JSONL formatting + fsync move off the step loop too
            sink = diag_sink.BufferedSink(sink)
        probe = None
        if args.probe_every > 0:
            # held probe batch: fixed key, same [K, B/K, ...] stacking
            # (and therefore the same scan memory envelope) as training
            ptoks, plabels = lm_batch(jax.random.PRNGKey(997),
                                      global_batch, args.seq,
                                      cfg.vocab_size)
            pbatch = {"tokens": ptoks, "labels": plabels}
            if es is not None:
                pbatch["extra_embeds"] = jnp.zeros(es, cfg.cdtype)
            if accum_steps > 1:
                pbatch = pipeline.stack_microbatches(pbatch, accum_steps)
            probe = probes.LanczosProbe(
                tasks.lm_task(model), pbatch, every=args.probe_every,
                num_iters=args.probe_iters, top_k=args.probe_topk,
                accum_steps=accum_steps,
                # mesh-native runs probe data-parallel too: per-shard
                # HVPs, psum'd contractions, replicated Krylov basis
                mesh=mesh if mesh_native and controller is None else None,
                reorth=not args.probe_no_reorth)

        ring = MetricRing(args.async_metrics, tracer=tracer) \
            if args.async_metrics > 0 else None
        # segment names for the layerwise stream, in tree-flatten
        # order — identical to the fused substrate's packing order
        lw_names = labels_lib.leaf_names(state.params) if layerwise \
            else None

        t0 = time.time()

        def emit_train(i, values, last, step_bs=None):
            rest, lw = obs_layerwise.split_record(dict(values))
            host = {k: float(v) for k, v in rest.items()
                    if np.ndim(v) == 0}
            if step_bs is not None:
                host["global_batch"] = float(step_bs)
            if lw and (args.layerwise_every <= 1
                       or i % args.layerwise_every == 0):
                host.update(obs_layerwise.expand(lw, lw_names))
            if sink is not None:
                sink.write(i, host, last=last)
            if i % args.log_every == 0 or last:
                print(f"step {i:4d} loss={host['loss']:.4f} "
                      f"ce={host['ce']:.4f} "
                      f"gnorm={host['grad_norm']:.3f} "
                      f"({time.time()-t0:.1f}s)")

        def emit_probe(i, out, _last):
            if sink is not None:
                sink.write(i, {f"{probe.name}/{k}": v
                               for k, v in out.items()}, last=True)
            print(f"step {i:4d} probe lambda_max="
                  f"{out['lambda_max']:.4f}")

        def emit_ctrl(i, out, _last):
            if sink is not None:
                sink.write(i, {f"{controller.name}/{k}": v
                               for k, v in out.items()}, last=True)
            print(f"step {i:4d} controller "
                  f"B_noise={out['b_noise']:.1f} "
                  f"global_batch={int(out['global_batch'])} "
                  f"D={int(out.get('data_parallel', 1))} "
                  f"K={int(out['accum_steps'])} "
                  f"lr={out['lr']:.4f}"
                  + (" [switched]" if out["changed"] else ""))

        for i in range(args.steps):
            if profiler is not None:
                profiler.step(i)
            if controller is not None:
                # the batch pulled now trains at the CURRENT target;
                # retargets only land after this step's probe boundary
                step_batch_size = controller.global_batch
                with tracer.span("data_wait", step=i):
                    batch = next(stream)
                with tracer.span("dispatch", step=i):
                    state, metrics = controller.step_fn()(state, batch)
            else:
                step_batch_size = None
                with tracer.span("data_wait", step=i):
                    batch = next(fixed_iter)
                with tracer.span("dispatch", step=i):
                    state, metrics = step_fn(state, batch)
            last = i == args.steps - 1
            if ring is None:
                with tracer.span("resolve", step=i):
                    host_metrics = jax.device_get(metrics)
                emit_train(i, host_metrics, last, step_batch_size)
            else:
                # leave the values on device; the ring materializes
                # them `async_metrics` steps later (exact same numbers)
                ring.append(i, metrics,
                            lambda s, v, l, _b=step_batch_size:
                            emit_train(s, v, l, _b), last=last)
            if probe is not None and probes.probe_due(probe, i):
                if ring is None:
                    with tracer.span("probe", step=i, probe=probe.name):
                        out = probe(i, state)
                    emit_probe(i, out, True)
                else:
                    with tracer.span("probe", step=i, probe=probe.name,
                                     mode="dispatch"):
                        raw = probe.dispatch(i, state)
                    ring.append(i, raw,
                                lambda s, v, l:
                                emit_probe(s, probe.resolve(v), l))
            if controller is not None and probes.probe_due(controller, i):
                # the decision must land before the next pull, so the
                # controller call itself stays synchronous; its output
                # rides the ring only to keep sink records ordered
                with tracer.span("controller", step=i):
                    out = controller(i, state)
                if ring is None:
                    emit_ctrl(i, out, True)
                else:
                    ring.append(i, out,
                                lambda s, v, l: emit_ctrl(s, v, l))
        if ring is not None:
            ring.drain()
        if profiler is not None:
            profiler.close()
            print(f"profile -> {args.profile_dir}")
        if isinstance(stream, pipeline.PrefetchingStream):
            stream.close()
        if isinstance(fixed_iter, pipeline.PrefetchingStream):
            fixed_iter.close()
        if sink is not None:
            sink.close()
            print(f"metrics -> {args.metrics_out}")
        if args.trace_out:
            with diag_sink.JsonlSink(args.trace_out) as tsink:
                n_trace = tracer.export(tsink)
            print(f"trace -> {args.trace_out} ({n_trace} records)")
        print(f"done: {args.steps} steps in {time.time()-t0:.1f}s, "
              f"final loss {float(metrics['loss']):.4f}")
        assert np.isfinite(float(metrics["loss"])), "NaN/inf loss"


if __name__ == "__main__":
    main()
