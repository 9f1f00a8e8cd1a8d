"""Bring-up smoke of the training and serving paths on a TPU v5e.

    python chip_smoke.py               # one chip: phase 1 and phase 2
    python chip_smoke.py --four-chips  # four chips: D=4 vs D=1, nothing else

Phase 1 (train) runs qwen2.5-3b at its published widths (d_model 2048,
16 heads over 2 KV heads, d_ff 11008, vocab 151,936), cut in depth only,
to the most layers whose jitted step the TPU compiler fits in the chip's
HBM (read from ``compiled.memory_analysis()``). It goes through the
library's own entry points: ``build_optimizer("tvlars",
use_kernel="fused")``, ``TrainState.create`` and ``jax.jit(
make_train_step(model, opt, accum_steps=K), donate_argnums=(0,))`` on
seeded synthetic ``lm_batch`` data. It checks that the loss is finite
and falls, that the step holds exactly two ``pallas_call``s and that the
compiled program holds ``tpu_custom_call``, and compares one fused
optimizer step with ``kernels.ref.ref_segmented_update`` on the same
packed buffers within ``ref.parity_tolerance("f32")``.

Phase 2 (serve) runs ``serving.Engine`` on the full 36-layer model in
bf16 with a bf16 KV pool, once with the fused decode kernel and once on
the jnp decode path, submitting half of the requests mid-flight. It
checks one decode compilation per engine, that every request finishes,
that the kernel's greedy tokens match the jnp path's, every one, and
that one real-width kernel call matches ``ref.ref_attention_decode``
within ``ref.decode_parity_tolerance``.

``--four-chips`` runs the shard_map data-parallel step (D=4) at the
phase-1 model against a D=1 run of the same global batch in this
process, compares losses and parameter updates within bf16 rounding
bounds, and checks that every device holds its own shard of each
batch.

Everything runs in this one process: a chip belongs to one process, so
nothing here starts a child that needs it. There is no CPU fallback:
without a TPU, or with ``REPRO_FORCE_REF`` set, the script exits
non-zero before any model work. Any failed check exits non-zero. The
numbers printed on the way are bring-up facts (layers, memory, compile
seconds, parity errors), also written to ``experiments/chip/``; the last
line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARTIFACT_DIR = os.path.join(ROOT, "experiments", "chip")

ARCH = "qwen2.5-3b"
SEED = 0                          # of the random weights and data
LEARNING_RATE = 2.0               # the training launcher's default

# phase 1: 4 microbatches of one 2048-token row per optimizer step
TRAIN_SEQ = 2048
TRAIN_MICROBATCH = 1
TRAIN_ACCUM = 4
TRAIN_STEPS = 4
# depth the one-chip search starts from, and the four-chip run uses: an
# ahead-of-time v5e compile of this step fits 3 layers (13.6 GiB) and
# refuses 4 (16.16G of 15.75G HBM)
TRAIN_LAYERS = 3

# phase 2: prompts in one pow2 prefill bucket (64) and admissions of
# four, so each engine compiles one prefill and one decode program
SERVE_SLOTS = 8
SERVE_MAX_LEN = 2048
SERVE_PAGE = 16
SERVE_ADMIT = 4
SERVE_REQUESTS = 8
PROMPT_LEN = (40, 64)
NEW_TOKENS = (12, 32)

# four chips: global batch 8 rows = D=1 x K=8 = D=4 x K=2, microbatch 1
FOUR_WIDTH = 4
FOUR_GLOBAL_BATCH = 8
FOUR_STEPS = 3


class SmokeError(RuntimeError):
    """A bring-up check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def note(facts: dict, phase: str, **kw) -> None:
    """Print one line of bring-up facts and keep them for the artifact."""
    facts.setdefault(phase, {}).update(kw)
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in kw.items()),
          flush=True)


def require_tpu():
    """Device 0, which must be a TPU; exits non-zero otherwise."""
    if "REPRO_FORCE_REF" in os.environ:
        raise SystemExit("chip_smoke: REPRO_FORCE_REF is set; this run "
                         "must execute the Pallas kernels, not the oracles")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (device 0 is "
                         f"{dev.platform!r}); there is no CPU fallback")
    return dev


def hbm_peak(compiled) -> int:
    """Bytes the compiled program holds at its peak on one device."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def peak_in_use() -> str:
    import jax
    return gib(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def gib(n: float) -> str:
    return f"{n / 2**30:.2f}GiB"


def excess(a, b, rtol: float):
    """``max(|a - b| - rtol·|b|)``: within tolerance iff <= atol."""
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.max(jnp.abs(a - b) - rtol * jnp.abs(b))


# --------------------------------------------------------------------------
# phase 1: fused-TVLARS training step
# --------------------------------------------------------------------------

def train_optimizer(steps: int, batch_size: int):
    from repro.core import build_optimizer
    return build_optimizer("tvlars", total_steps=steps,
                           learning_rate=LEARNING_RATE,
                           batch_size=batch_size, use_kernel="fused")


def train_shapes(model, opt, accum: int, microbatch: int, seq: int):
    import jax
    import jax.numpy as jnp
    from repro.training.train_state import TrainState
    state = jax.eval_shape(
        lambda: TrainState.create(model.init(jax.random.PRNGKey(0)), opt))
    row = jax.ShapeDtypeStruct((accum, microbatch, seq), jnp.int32)
    return state, {"tokens": row, "labels": row}


def fit_depth(compile_at, start: int, most: int, limit: int, facts: dict):
    """The most layers whose step compiles within ``limit`` bytes.

    ``compile_at(n)`` returns the compiled step, or None when the
    compiler refuses it for memory. Walks up from ``start`` while the
    next depth fits, or down until one does."""
    tried: dict = {}

    def fits(n: int) -> bool:
        if n not in tried:
            tried[n] = compile_at(n)
        return tried[n] is not None and hbm_peak(tried[n]) <= limit

    n = min(start, most)
    if fits(n):
        while n < most and fits(n + 1):
            n += 1
    else:
        while n > 1 and not fits(n):
            n -= 1
        check(fits(n), f"no depth of {ARCH} fits {gib(limit)}")
    for k in sorted(tried):
        c = tried[k]
        note(facts, "train", **{f"peak_at_{k}_layers":
                                "refused" if c is None
                                else gib(hbm_peak(c))})
    return n, tried[n]


def phase_train(facts: dict, *, cfg, limit: int, seq: int = TRAIN_SEQ,
                start_layers: int = TRAIN_LAYERS) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import flatten
    from repro.data import pipeline
    from repro.data.synthetic import lm_batch
    from repro.kernels import ops, ref
    from repro.models import get_model
    from repro.training.train_state import TrainState
    from repro.training.trainer import make_train_step

    microbatch, accum, steps = TRAIN_MICROBATCH, TRAIN_ACCUM, TRAIN_STEPS
    opt = train_optimizer(steps, accum * microbatch)

    def compile_at(n: int):
        model = get_model(cfg.replace(num_layers=n))
        state, batch = train_shapes(model, opt, accum, microbatch, seq)
        step = jax.jit(make_train_step(model, opt, accum_steps=accum),
                       donate_argnums=(0,))
        t0 = time.perf_counter()
        try:
            compiled = step.lower(state, batch).compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            note(facts, "train", **{f"refused_{n}_layers":
                                    repr(str(e).splitlines()[0][:160])})
            return None
        note(facts, "train", **{f"compile_s_{n}_layers":
                                round(time.perf_counter() - t0, 1)})
        return compiled

    layers, compiled = fit_depth(compile_at, start_layers, cfg.num_layers,
                                 limit, facts)
    model = get_model(cfg.replace(num_layers=layers))
    note(facts, "train", layers=layers, hbm_limit=gib(limit),
         reason=f"most layers whose step fits {gib(limit)} "
                f"({gib(hbm_peak(compiled))} at {layers})",
         seq=seq, microbatch=microbatch, accum_steps=accum)

    state_shape, batch_shape = train_shapes(model, opt, accum, microbatch,
                                            seq)
    jaxpr = jax.make_jaxpr(make_train_step(model, opt, accum_steps=accum))(
        state_shape, batch_shape)
    n_pallas = ops.count_pallas_calls(jaxpr.jaxpr)
    native = "tpu_custom_call" in compiled.as_text()
    note(facts, "train", pallas_calls=n_pallas, tpu_custom_call=native)
    check(n_pallas == 2, f"fused step holds {n_pallas} pallas_calls, not 2")
    check(native, "compiled step holds no tpu_custom_call")

    key = jax.random.PRNGKey(SEED)
    state = jax.jit(lambda k: TrainState.create(model.init(k), opt))(key)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    # one held batch: the loss must fall on it, free of batch-to-batch
    # noise
    toks, labels = lm_batch(jax.random.fold_in(key, 1), accum * microbatch,
                            seq, cfg.vocab_size)
    batch = pipeline.stack_microbatches({"tokens": toks, "labels": labels},
                                        accum)
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
        secs.append(round(time.perf_counter() - t0, 3))
    note(facts, "train", params=n_params, losses=losses, step_s=secs,
         peak_bytes_in_use=peak_in_use())
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    # one fused optimizer step against the oracle, same packed buffers
    spec = flatten.build_spec(state.params)

    @jax.jit
    def packed(params, k):
        leaves = jax.tree_util.tree_leaves(params)
        keys = jax.random.split(k, len(leaves))
        grads = [1e-3 * jax.random.normal(kk, x.shape, jnp.float32)
                 for kk, x in zip(keys, leaves)]
        return flatten.pack_tree(params, spec), flatten.pack(grads, spec)

    w, g = packed(state.params, jax.random.fold_in(key, 2))
    m = state.opt_state.momentum
    del state, metrics, compiled
    tol = ref.parity_tolerance("f32")
    hyper = dict(mode="paper", eta=1e-3, weight_decay=5e-4, momentum=0.9,
                 b1=0.9, b2=0.999, eps=1e-9)

    @jax.jit
    def parity(w, g, m, seg_ids, adapt_mask, lr):
        kw = dict(seg_ids=seg_ids, adapt_mask=adapt_mask, base_lr=lr,
                  **hyper)
        (km,), kd = ops.segmented_update(w, g, (m,), **kw)
        (rm,), rd = ref.ref_segmented_update(w, g, (m,), **kw)
        return (jnp.max(jnp.abs(km - rm)), excess(km, rm, tol["rtol"]),
                jnp.max(jnp.abs(kd - rd)), excess(kd, rd, tol["rtol"]))

    m_err, m_exc, d_err, d_exc = (float(x) for x in parity(
        w, g, m, spec.segment_ids(), spec.adapt_mask(),
        jnp.float32(LEARNING_RATE)))
    note(facts, "train", opt_rows=spec.num_rows, segments=spec.num_segments,
         momentum_max_err=m_err, delta_max_err=d_err, tolerance=tol)
    check(m_exc <= tol["atol"] and d_exc <= tol["atol"],
          f"fused step vs ref_segmented_update beyond {tol}: momentum "
          f"{m_err}, delta {d_err}")


# --------------------------------------------------------------------------
# phase 2: continuous-batching engine
# --------------------------------------------------------------------------

def run_engine(model, params, requests, *, use_kernel: bool,
               max_len: int):
    """Submit half the requests, step three times, submit the rest
    mid-flight, drain. Returns (tokens by request id, stats, seconds)."""
    from repro import serving
    sc = serving.ServeConfig(slots=SERVE_SLOTS, max_len=max_len,
                             page_size=SERVE_PAGE,
                             prefill_batch=SERVE_ADMIT,
                             use_kernel=use_kernel, cache_dtype="bfloat16")
    eng = serving.Engine(model, params, sc)
    half = len(requests) // 2
    t0 = time.perf_counter()
    for prompt, n in requests[:half]:
        eng.submit(prompt, max_new_tokens=n)
    results = []
    for _ in range(3):
        results.extend(eng.step())
    for prompt, n in requests[half:]:
        eng.submit(prompt, max_new_tokens=n)
    results.extend(eng.drain())
    secs = time.perf_counter() - t0
    check(len(results) == len(requests),
          f"{len(results)} of {len(requests)} requests came back")
    for r in results:
        want = requests[r.id][1]
        check(r.finished and len(r.tokens) == want,
              f"request {r.id}: finished={r.finished}, "
              f"{len(r.tokens)} of {want} tokens")
    return {r.id: r.tokens for r in results}, eng.stats(), secs


def phase_serve(facts: dict, *, cfg, max_len: int = SERVE_MAX_LEN) -> None:
    import jax
    import numpy as np
    from repro.models import get_model

    model = get_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    note(facts, "serve", layers=cfg.num_layers, params=n_params,
         param_dtype=cfg.param_dtype, init_s=round(time.perf_counter() - t0,
                                                   1))
    rng = np.random.RandomState(SEED)
    reqs = [(rng.randint(1, cfg.vocab_size,
                         size=rng.randint(PROMPT_LEN[0], PROMPT_LEN[1] + 1)),
             int(rng.randint(NEW_TOKENS[0], NEW_TOKENS[1] + 1)))
            for _ in range(SERVE_REQUESTS)]

    tokens = {}
    for use_kernel in (True, False):
        name = "kernel" if use_kernel else "jnp"
        tokens[name], stats, secs = run_engine(
            model, params, reqs, use_kernel=use_kernel, max_len=max_len)
        note(facts, "serve", **{
            f"{name}_wall_s_incl_compile": round(secs, 1),
            f"{name}_decode_compilations": stats["decode_compilations"],
            f"{name}_prefill_compilations": stats["prefill_compilations"],
            f"{name}_tokens": stats["tokens_generated"]})
        check(stats["decode_compilations"] == 1,
              f"{name} engine compiled decode "
              f"{stats['decode_compilations']} times")
        gc.collect()

    # every generated token must agree. With random tied embeddings
    # each greedy step picks the input token itself by a margin of tens
    # of logits (the untrained loss is ~32 nats), far beyond any
    # rounding difference between the kernel and the jnp path; the
    # numerics themselves are held to the oracle in phase_decode_kernel
    prefix = []
    for rid in range(len(reqs)):
        a, b = tokens["kernel"][rid], tokens["jnp"][rid]
        prefix.append(next((i for i, (x, y) in enumerate(zip(a, b))
                            if x != y), min(len(a), len(b))))
    generated = [len(tokens["kernel"][r]) for r in range(len(reqs))]
    note(facts, "serve", greedy_common_prefix=prefix, generated=generated)
    check(prefix == generated,
          f"kernel and jnp greedy tokens part: common prefixes {prefix} "
          f"of {generated}")
    note(facts, "serve", peak_bytes_in_use=peak_in_use())


def phase_decode_kernel(facts: dict, *, cfg,
                        max_len: int = SERVE_MAX_LEN) -> None:
    """One decode-kernel call at the serving pool's real width against
    the f32 oracle."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    b, h, hkv, dh = SERVE_SLOTS, cfg.num_heads, cfg.num_kv_heads, \
        cfg.head_dim_
    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 6)
    bf16 = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, 1, h, dh)).astype(bf16)
    nk = jax.random.normal(ks[1], (b, 1, hkv, dh)).astype(bf16)
    nv = jax.random.normal(ks[2], (b, 1, hkv, dh)).astype(bf16)
    kc = jax.random.normal(ks[3], (b, max_len, hkv * dh)).astype(bf16)
    vc = jax.random.normal(ks[4], (b, max_len, hkv * dh)).astype(bf16)
    pos = jax.random.randint(ks[5], (b,), 0, max_len)
    out, kc_k, vc_k = jax.jit(ops.attention_decode_fused)(q, nk, nv, kc, vc,
                                                          pos)
    with jax.default_matmul_precision("highest"):
        want, kc_r, vc_r = jax.jit(ref.ref_attention_decode)(q, nk, nv, kc,
                                                             vc, pos)
    tol = ref.decode_parity_tolerance(bf16)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    over = float(excess(out, want, tol["rtol"]))
    caches_equal = bool(jnp.array_equal(kc_k, kc_r)
                        & jnp.array_equal(vc_k, vc_r))
    note(facts, "decode_kernel", max_err=err, tolerance=tol,
         kv_append_exact=caches_equal)
    check(over <= tol["atol"],
          f"decode kernel vs ref_attention_decode beyond {tol}: {err}")
    check(caches_equal, "decode kernel KV append differs from the oracle")


# --------------------------------------------------------------------------
# four chips: shard_map data parallelism against one device
# --------------------------------------------------------------------------

def check_batch_shards(placed: dict, host: dict, mesh, batch_dim: int):
    """Each device of ``mesh`` holds its own slice of every leaf."""
    import numpy as np
    want = set(mesh.devices.flat)
    for name, leaf in placed.items():
        shards = leaf.addressable_shards
        check({s.device for s in shards} == want,
              f"{name}: shards on {[s.device for s in shards]}")
        starts = set()
        for s in shards:
            check(s.data.shape[batch_dim]
                  == host[name].shape[batch_dim] // len(want),
                  f"{name}: shard {s.data.shape} on {s.device} is not "
                  f"one device's slice")
            check(np.array_equal(np.asarray(s.data), host[name][s.index]),
                  f"{name}: shard on {s.device} holds other rows")
            starts.add(s.index[batch_dim].start)
        check(len(starts) == len(want), f"{name}: devices share slices")


def phase_four_chips(facts: dict, *, cfg, seq: int = TRAIN_SEQ,
                     layers: int = TRAIN_LAYERS) -> None:
    import jax
    import numpy as np
    from repro.core import labels as labels_lib
    from repro.data import pipeline
    from repro.data.synthetic import lm_batch
    from repro.kernels import ops, ref
    from repro.launch.mesh import make_data_mesh
    from repro.models import get_model
    from repro.training.train_state import TrainState, replicate
    from repro.training.trainer import make_train_step

    width, global_batch, steps = FOUR_WIDTH, FOUR_GLOBAL_BATCH, FOUR_STEPS
    check(jax.device_count() >= width,
          f"--four-chips needs {width} devices, JAX found "
          f"{jax.device_count()}")
    model = get_model(cfg.replace(num_layers=layers))
    opt = train_optimizer(steps, global_batch)
    key = jax.random.PRNGKey(SEED)
    init = jax.jit(lambda k: TrainState.create(model.init(k), opt))
    host = []
    for i in range(steps):
        toks, labels = lm_batch(jax.random.fold_in(key, 100 + i),
                                global_batch, seq, cfg.vocab_size)
        host.append(jax.device_get({"tokens": toks, "labels": labels}))

    def run(step, state, place):
        losses = []
        t0 = time.perf_counter()
        for b in host:
            state, metrics = step(state, place(b))
            losses.append(float(metrics["loss"]))
        return state, losses, round(time.perf_counter() - t0, 1)

    params0 = jax.device_get(init(key).params)
    k1 = global_batch
    step1 = jax.jit(make_train_step(model, opt, accum_steps=k1),
                    donate_argnums=(0,))
    state, losses1, secs1 = run(
        step1, init(key), lambda b: pipeline.stack_microbatches(b, k1))
    params1 = jax.device_get(state.params)
    del state
    gc.collect()

    mesh = make_data_mesh(width)
    kd = global_batch // width
    stepd = make_train_step(model, opt, accum_steps=kd, mesh=mesh)
    state_shape, batch_shape = train_shapes(model, opt, kd, width, seq)
    n_pallas = ops.count_pallas_calls(
        jax.make_jaxpr(stepd)(state_shape, batch_shape).jaxpr)
    check(n_pallas == 2, f"D={width} step holds {n_pallas} pallas_calls")

    def place(b):
        stacked = jax.tree_util.tree_map(
            np.asarray, pipeline.stack_microbatches(b, kd))
        placed = pipeline.shard_batch(mesh, stacked, batch_dim=1)
        check_batch_shards(placed, stacked, mesh, batch_dim=1)
        return placed

    state, losses_d, secs_d = run(
        jax.jit(stepd, donate_argnums=(0,)), replicate(init(key), mesh),
        place)
    params_d = jax.device_get(state.params)
    del state

    # The model computes in bf16, and the one-device scan and the
    # per-device shard_map program are separate compilations that may
    # round activations at different points: per-token values and
    # gradients differ at bf16 rounding level, not only by f32
    # reassociation. Each step's update then carries a few-bf16-ulp
    # relative error that compounds over the steps, the model of
    # ``ref.parity_tolerance`` for bf16 operands; the loss, a mean over
    # the step's tokens, averages the per-token rounding down by
    # sqrt(tokens).
    bf16 = ref.parity_tolerance("bf16_master", steps)["rtol"]
    loss_rtol = ref.parity_tolerance("bf16_master")["rtol"] \
        / math.sqrt(global_batch * seq)
    l_err = max(abs(a - b) / abs(a) for a, b in zip(losses1, losses_d))
    names = labels_lib.leaf_names(params0)
    upd_err, worst, p_err = 0.0, names[0], 0.0
    for name, p0, a, b in zip(names, *(jax.tree_util.tree_leaves(t) for t
                                       in (params0, params1, params_d))):
        d1, dd = a - p0, b - p0
        gap = float(np.max(np.abs(dd - d1)))
        scale = float(np.max(np.abs(d1)))
        rel = gap / scale if scale > 0 else (0.0 if gap == 0 else np.inf)
        p_err = max(p_err, gap)
        if rel > upd_err:
            upd_err, worst = rel, name
    note(facts, "four_chips", layers=layers, global_batch=global_batch,
         d1_accum=k1, d4_accum=kd, losses_d1=losses1, losses_d4=losses_d,
         loss_max_rel_err=l_err, loss_rtol=loss_rtol, param_max_err=p_err,
         update_max_rel_err=upd_err, update_worst_leaf=worst,
         update_rtol=bf16, d1_wall_s=secs1, d4_wall_s=secs_d,
         pallas_calls=n_pallas, batch_shards="own")
    check(all(np.isfinite(losses_d)), f"non-finite D={width} loss")
    check(l_err <= loss_rtol,
          f"D={width} losses {losses_d} vs D=1 {losses1}")
    check(upd_err <= bf16,
          f"D={width} update of {worst} differs from D=1 by {upd_err} of "
          f"its largest element (bound {bf16})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the D=4 shard_map step against D=1")
    args = ap.parse_args(argv)
    dev = require_tpu()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.configs import get_config
    from repro.launch import compile_cache

    facts: dict = {}
    note(facts, "device", kind=dev.device_kind, count=jax.device_count(),
         jax=jax.__version__, compile_cache=compile_cache.enable())
    cfg = get_config(ARCH)
    # training keeps f32 master params (the trainer's contract); the
    # published bf16 weights are what the serving phase loads
    train_cfg = cfg.replace(param_dtype="float32")
    if args.four_chips:
        name = "four_chips"
        phases = [("four_chips", lambda: phase_four_chips(
            facts, cfg=train_cfg))]
    else:
        name = "chip_smoke"
        phases = [
            ("train", lambda: phase_train(
                facts, cfg=train_cfg,
                limit=dev.memory_stats()["bytes_limit"])),
            ("decode_kernel", lambda: phase_decode_kernel(facts, cfg=cfg)),
            ("serve", lambda: phase_serve(facts, cfg=cfg)),
        ]
    # a failed check ends its phase; the later phases still run so one
    # chip call reports every fact, and the run then exits non-zero
    failed = []
    for phase, run in phases:
        try:
            run()
        except SmokeError as e:
            failed.append(f"{phase}: {e}")
            print(f"{phase}: FAILED {e}", flush=True)
        gc.collect()
        note(facts, phase,
             live_bytes_after=sum(x.nbytes for x in jax.live_arrays()))
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    with open(os.path.join(ARTIFACT_DIR, f"{name}.json"), "w") as f:
        json.dump(facts, f, indent=1, default=str)
    if failed:
        raise SystemExit("chip_smoke failed: " + "; ".join(failed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
