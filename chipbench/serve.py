"""Serving cells: open-loop traffic through ``serving.Engine``.

The traffic file fixes the rate and the length distributions; a fixed
``shape_seed`` draws the inter-arrival gaps and the (prompt, output)
lengths in their order, so every run serves the same work on the same
schedule, and ``--seed`` draws the token ids. (Ordering them by the
seed changed the work: in a window not much longer than a request, the
order decides how many tokens are due before the close.) Set-up makes
the weights on the device from the seed in one call, builds the engine
and compiles every prefill bucket the lengths can reach plus the one
decode program, by serving short requests of those shapes.

The engine serves the traffic for ``preload_seconds`` before the window
opens (counted as set-up), so the window finds it as a long-running
server would: slots, queue and K/V pool filled to the rate's steady
state. The window submits each request when it is due and steps the
engine whenever it holds work. Token times are taken from outside,
through the public API: admission is first-in first-out, so the drop in
``queue_depth`` over a step says which requests were admitted (each
gains its prefill token and its first decoded token in that step) and
every request already active gains one token per step; the results a
step returns must agree with that count.

Once the window has closed, the engine is freed and the plain reference
runs over a sample, drawn from the seed, of the requests that finished
inside the window, the longest among them: at every served position,
how far the reference's logit of the served token lies below its best.
The widest such gap is compared with its limit.
"""
from __future__ import annotations

import collections
import gc
import math
import os
import shutil
import time

import numpy as np

from chipbench import common, flops, program, reference, xplane


def schedule(traffic: dict, seed: int, seconds: float, vocab: int):
    """(due times from the window's start, prompts, output lengths)."""
    rate = traffic["rate_per_s"]
    n = max(1, math.ceil(rate * seconds))
    shape = np.random.default_rng(traffic["shape_seed"])
    gaps = shape.exponential(1.0 / rate, n)

    def lengths(spec):
        x = shape.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)

    plens, olens = lengths(traffic["prompt_len"]), \
        lengths(traffic["output_len"])
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, int(p), dtype=np.int32)
               for p in plens]
    return np.cumsum(gaps), prompts, [int(o) for o in olens]


class Req:
    __slots__ = ("due", "prompt", "out", "submit", "first", "last",
                 "count", "tokens")

    def __init__(self, due, prompt, out):
        self.due, self.prompt, self.out = due, prompt, out
        self.submit = self.first = self.last = None
        self.count = 0
        self.tokens = None


def make_engine(config: dict, seed: int, tracer=None) -> tuple:
    """(weights, engine): the weights made on the device from the seed
    in one call, the engine as the configuration states it."""
    import jax
    import jax.numpy as jnp
    from repro import serving

    model = program.model_of(config)
    params = jax.jit(reference.weights_fn(
        config, jnp.dtype(config["program"]["param_dtype"])))(
            reference.base_key(seed))
    sc = config["serve"]
    eng = serving.Engine(model, params, serving.ServeConfig(
        slots=sc["slots"], max_len=sc["max_len"], page_size=sc["page_size"],
        prefill_batch=sc["prefill_batch"], use_kernel=sc["use_kernel"],
        cache_dtype=sc["cache_dtype"]), tracer=tracer)
    return params, eng


def warm_up(eng, traffic: dict, vocab: int) -> None:
    """Compile every prefill bucket the traffic can reach and the decode
    step, on the engine the window uses."""
    rng = np.random.default_rng(0)
    for lb in traffic["warm_prefill_lengths"]:
        for nb in traffic["warm_prefill_batches"]:
            for _ in range(nb):
                eng.submit(rng.integers(0, vocab, lb, dtype=np.int32),
                           max_new_tokens=2)
            eng.drain()


def serve_window(eng, reqs, t0: float, close: float, on_trace=None,
                 trace_at=None) -> dict:
    """Drive the engine open-loop until ``close``; returns the window's
    token times and per-step work."""
    fifo: collections.deque = collections.deque()
    active: dict[int, Req] = {}
    by_id: dict[int, Req] = {}
    gaps: list[float] = []
    steps: list[dict] = []
    finished: list[Req] = []
    faults: list[str] = []
    i, n = 0, len(reqs)
    traced = False
    while True:
        now = time.perf_counter()
        if now >= close:
            break
        if trace_at is not None and not traced and now >= trace_at:
            on_trace()
            traced = True
            continue
        while i < n and t0 + reqs[i].due <= now:
            r = reqs[i]
            rid = eng.submit(r.prompt, max_new_tokens=r.out)
            r.submit = time.perf_counter()
            by_id[rid] = r
            fifo.append(rid)
            i += 1
        if not fifo and not active:
            nxt = t0 + reqs[i].due if i < n else close
            wake = trace_at if trace_at is not None and not traced \
                else close
            time.sleep(max(0.0, min(nxt, close, wake)
                           - time.perf_counter()))
            continue
        waiting = eng.queue_depth
        contexts = [len(r.prompt) + r.count for r in active.values()]
        results = eng.step()
        t = time.perf_counter()
        new = [fifo.popleft() for _ in range(waiting - eng.queue_depth)]
        for r in active.values():
            gaps.append((t, t - r.last))
            r.count += 1
            r.last = t
        for rid in new:
            r = by_id[rid]
            active[rid] = r
            r.first = r.last = t
            r.count = 2
            gaps.append((t, 0.0))
            contexts.append(len(r.prompt) + 1)
        steps.append({"end": t, "contexts": contexts,
                      "prompts": [len(by_id[rid].prompt) for rid in new],
                      "tokens": len(active) + len(new), "traced": traced})
        for res in results:
            r = active.pop(res.id)
            if len(res.tokens) != r.count or r.count != r.out:
                faults.append(f"request {res.id}: {len(res.tokens)} tokens, "
                              f"counted {r.count}, asked {r.out}")
            r.tokens = list(res.tokens)
            finished.append(r)
    return {"gaps": gaps, "steps": steps, "finished": finished,
            "faults": faults, "open": list(active) + list(fifo)}


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, devices, t_start: float, engine_hook=None,
        after=None) -> tuple:
    """One run; returns (result fields, Check, reader inputs or None).

    ``engine_hook(engine)`` may alter the engine before the window
    (tests plant faults through it); ``after(params, sampled)`` runs
    once the check has read the sampled requests (the control reads
    them too)."""
    import jax
    from repro.obs.trace import Tracer

    vocab = config["vocab_size"]
    clock = tracer = None
    if trace:
        clock = program.SpanClock()
        tracer = Tracer()
    params, eng = make_engine(config, seed, tracer)
    warm_up(eng, traffic, vocab)
    compiled = (eng.prefill_compilations, eng.decode_compilations)
    if engine_hook is not None:
        engine_hook(eng)

    preload = traffic["preload_seconds"]
    length = traffic["trace_seconds"] if trace else seconds
    due, prompts, outs = schedule(traffic, seed, preload + length, vocab)
    reqs = [Req(d, p, o) for d, p, o in zip(due, prompts, outs)]
    logdir = os.path.join(common.TRACE_DIR, "serve")
    marks = {}

    def start_trace():
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(logdir)
        marks["ann"] = jax.profiler.TraceAnnotation(xplane.WINDOW)
        marks["ann"].__enter__()
        clock.mark_window()
        marks["t0"] = time.perf_counter()

    t0 = time.perf_counter()
    opened, close = t0 + preload, t0 + preload + length
    setup_s = opened - t_start
    out = serve_window(eng, reqs, t0, close, start_trace,
                       opened if trace else None)
    if trace:
        marks["ann"].__exit__(None, None, None)
        marks["t1"] = time.perf_counter()
        jax.profiler.stop_trace()
    recompiled = (eng.prefill_compilations,
                  eng.decode_compilations) != compiled
    peak = common.memory_peak(devices)
    records = tracer.events() if trace else []
    del eng
    gc.collect()

    due_in = [r for r in reqs if opened <= t0 + r.due < close]
    metrics, reader = {}, None
    if not trace:
        ttft = [((r.first if r.first is not None and r.first <= close
                  else close) - (t0 + r.due)) * 1e3 for r in due_in]
        itl = [g * 1e3 for t, g in out["gaps"] if opened < t <= close]
        toks = sum(s["tokens"] for s in out["steps"]
                   if opened < s["end"] <= close)
        metrics = {
            "serve_ttft_p95_ms": {"value": common.percentile(ttft, 95),
                                  "unit": "ms"},
            "serve_itl_p95_ms": {"value": common.percentile(itl, 95)
                                 if itl else math.inf, "unit": "ms"},
            "serve_output_tokens_per_s": {"value": toks / length,
                                          "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        lo, hi = marks["t0"], marks["t1"]
        traced = [s for s in out["steps"] if s["traced"]]
        fl = sum(flops.prefill_flops(config, p) for s in traced
                 for p in s["prompts"])
        fl += sum(flops.decode_flops(config, c) for s in traced
                  for c in s["contexts"])
        reader = {"kind": "serve", "logdir": logdir, "clock": clock,
                  "records": records, "steps": len(traced), "flops": fl,
                  "decode_bytes": sum(flops.decode_attention_bytes(
                      config, s["contexts"]) for s in traced),
                  "decode_steps": len(traced),
                  "decode_step_bytes": sum(flops.decode_step_bytes(
                      config, s["contexts"]) for s in traced),
                  "arrival_lag_s": [r.submit - (t0 + r.due) for r in reqs
                                    if r.submit is not None
                                    and lo <= r.submit <= hi],
                  "chips": len(devices)}

    check = common.Check()
    for f in out["faults"]:
        check.require(False, f)
    check.require(not recompiled, "the engine compiled inside the window")
    finished = [r for r in out["finished"] if r.last > opened]
    check.require(bool(finished), "no request finished in the window")
    failed = 0
    if finished:
        sampled = sample(finished, traffic, seed)
        gaps = logit_gaps(config, traffic, params, sampled)
        failed = sum(g > traffic["limits"]["logit_gap"] for g in gaps)
        check.number("logit_gap", max(gaps), traffic["limits"]["logit_gap"])
        if after is not None:
            after(params, sampled)
    result = {"attempted": len(due_in), "failed": failed,
              "metrics": metrics,
              "device": {**common.device_info(devices),
                         "memory_peak_bytes": peak}}
    return result, check, reader


def sample(finished: list, traffic: dict, seed: int) -> list:
    """The requests the check reads: the longest finished one and a
    draw from the seed among the rest."""
    longest = max(range(len(finished)),
                  key=lambda j: len(finished[j].prompt) + finished[j].out)
    rest = [j for j in range(len(finished)) if j != longest]
    rng = np.random.default_rng(seed + 1)
    k = min(traffic["sample_requests"] - 1, len(rest))
    pick = [longest] + [rest[j] for j in sorted(
        rng.choice(len(rest), k, replace=False))] if k else [longest]
    return [finished[j] for j in pick]


def padded(traffic: dict, r) -> tuple:
    """(ids, next ids, first served position) padded to one length."""
    n = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    seq = np.zeros(n + 1, np.int32)
    full = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
    seq[:len(full)] = full
    return seq[:-1], seq[1:], len(r.prompt) - 1


def logit_gaps(config, traffic, params, reqs,
               precision: str = "f32") -> list[float]:
    """Per request, the widest gap of a served token below the
    reference's best logit at its position."""
    fn = reference.gap_fn(config, precision)
    out = []
    for r in reqs:
        ids, nxt, first = padded(traffic, r)
        gap, _ = fn(params, ids, nxt)
        gap = np.asarray(gap)[first:first + len(r.tokens)]
        out.append(float(gap.max()))
    return out


def control_gaps(config, traffic, params, reqs) -> list[float]:
    """Per request, the widest gap below the float32 reference's best
    logit of the tokens the float8 reference puts first at the served
    positions of the same prompts and tokens."""
    exact = reference.gap_fn(config, "f32")
    low = reference.gap_fn(config, "fp8")
    out = []
    for r in reqs:
        ids, nxt, first = padded(traffic, r)
        _, top = low(params, ids, nxt)
        gap, _ = exact(params, ids, top)
        out.append(float(np.asarray(gap)[first:first + len(r.tokens)].max()))
    return out
