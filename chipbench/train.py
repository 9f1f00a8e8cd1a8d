"""Training cells: the fused-TVLARS step driven through ``trainer.fit``.

Set-up builds one object, the jitted step with its state (weights made
on the device from the seed in one call), drives it through the first
``checked_steps`` optimizer steps with the window's own call and feed,
and hands the same state to the window. The window runs
``ceil(seconds / step)`` further steps through one ``trainer.fit`` call;
every step's rows are distinct token ids drawn from the seed.

Once the window has closed and the state is freed, the plain reference
(``reference.py``) repeats the checked steps from the same weights and
rows. Compared, each by its own limit: the loss of every checked step,
the first gradient as the optimizer got it (worked out from the weights
after one step) and the change of the weights over the checked steps,
each of the last two per stored tensor against the larger of that
tensor's reference norm and the median tensor's. Tensors whose reference
gradient is under a thousandth of the median tensor's are left out of
those two (a key bias, which softmax ignores, is one). A tensor that
the architecture marks ``held`` (moved only by its ``after_step``) has
no first gradient to read back; its change is compared.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import time

from chipbench import arch, common, flops, program, reference, xplane

# largest |g| / (wd |w|) at which a first-gradient norm is read back
MAX_AMPLIFICATION = 100.0


def hyper(config: dict, traffic: dict) -> reference.Hyper:
    o = config["optimizer"]
    rows = traffic["accum_steps"] * traffic["microbatch"] \
        * traffic["data_parallel"]
    lr = o["learning_rate"] * math.sqrt(rows / o["base_batch_size"])
    gamma_min = min(rows / o["base_batch_size"] * o["gamma_min_per_base"],
                    0.5)
    return reference.Hyper(lr, o["lam"], float(o["delay_steps"]), o["alpha"],
                           gamma_min, o["eta"], o["momentum"],
                           o["weight_decay"], o["eps"])


def build_optimizer(config: dict, hp: reference.Hyper):
    from repro.core import build_optimizer as build
    o = config["optimizer"]
    return build("tvlars", total_steps=o["total_steps"], learning_rate=hp.lr,
                 delay_steps=int(hp.delay), lam=hp.lam, alpha=hp.alpha,
                 gamma_min=hp.gamma_min, eta=hp.eta, momentum=hp.momentum,
                 weight_decay=hp.wd, use_kernel=o["use_kernel"],
                 precision=o["precision"])


class Feed:
    """The batches of the run, step ``i`` made on the device from the
    seed by one compiled program."""

    def __init__(self, make, key):
        self._make, self._key, self.i = make, key, 0

    def __iter__(self):
        return self

    def __next__(self):
        import jax.numpy as jnp
        b = self._make(self._key, jnp.int32(self.i))
        self.i += 1
        return b


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, devices, t_start: float, step_factory=None) -> tuple:
    """One run; returns (result fields, Check, reader inputs or None).

    ``step_factory(model, opt, accum_steps=, mesh=)`` replaces
    ``trainer.make_train_step`` (tests plant faults through it)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from repro.launch.mesh import make_data_mesh
    from repro.obs.trace import Tracer
    from repro.training import trainer
    from repro.training.train_state import TrainState

    K, mb, D = (traffic["accum_steps"], traffic["microbatch"],
                traffic["data_parallel"])
    S, checked = traffic["seq_len"], traffic["checked_steps"]
    rows = K * mb * D
    hp = hyper(config, traffic)
    model = program.model_of(config)
    opt = build_optimizer(config, hp)
    if D > 1:
        mesh = make_data_mesh(D)
        rep = NamedSharding(mesh, P())
        bsh = NamedSharding(mesh, P(None, "data") if K > 1 else P("data"))
    else:
        mesh = None
        rep = bsh = SingleDeviceSharding(devices[0])
    key = reference.base_key(seed)
    init = reference.weights_fn(config, jnp.float32)
    state = jax.jit(lambda k: TrainState.create(init(k), opt),
                    out_shardings=rep)(key)

    def batch(k, i):
        ids = reference.step_tokens(k, i, rows, S, config["vocab_size"])
        b = {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
        if K > 1:
            b = {n: x.reshape(K, rows // K, S) for n, x in b.items()}
        return b

    feed = Feed(jax.jit(batch, out_shardings=bsh), key)
    factory = step_factory or trainer.make_train_step
    train_step = factory(model, opt, accum_steps=K, mesh=mesh)

    def first_stats(p, k):
        return reference.first_update_stats(config, hp, p, init(k))

    def change(p, k):
        return [jnp.linalg.norm(w - w0) for w, w0 in
                zip(reference.flat(p, config),
                    reference.flat(init(k), config))]

    first_stats, change = jax.jit(first_stats), jax.jit(change)
    losses, times, first = [], [], None
    for i in range(checked):
        t = time.perf_counter()
        state, hist = trainer.fit(train_step, state, feed, 1)
        times.append(time.perf_counter() - t)
        losses.append(float(hist[0]["loss"]))
        if i == 0:
            first = [[float(v) for v in x]
                     for x in jax.device_get(first_stats(state.params, key))]
    moved = [float(x) for x in jax.device_get(change(state.params, key))]
    step_s = min(times[1:]) if len(times) > 1 else times[0]

    tokens_per_step = rows * S
    reader = None
    if not trace:
        n = max(2, math.ceil(seconds / step_s))
        setup_s = time.perf_counter() - t_start
        t0 = time.perf_counter()
        state, hist = trainer.fit(train_step, state, feed, n)
        jax.block_until_ready(state)
        window = time.perf_counter() - t0
        metrics = {
            "train_tokens_per_s": {"value": n * tokens_per_step / window,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        n = max(2, math.ceil(traffic["trace_seconds"] / step_s))
        logdir = os.path.join(common.TRACE_DIR, "train")
        shutil.rmtree(logdir, ignore_errors=True)
        clock = program.SpanClock()
        tracer = Tracer()
        jax.profiler.start_trace(logdir)
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            clock.mark_window()
            t0 = time.perf_counter()
            state, hist = trainer.fit(
                train_step, state, feed, n,
                options=trainer.FitOptions(tracer=tracer))
            jax.block_until_ready(state)
            window = time.perf_counter() - t0
        jax.profiler.stop_trace()
        metrics = {}
        reader = {"kind": "train", "logdir": logdir, "clock": clock,
                  "records": tracer.events(), "steps": n,
                  "tokens": n * tokens_per_step,
                  "flops": n * tokens_per_step
                  * flops.train_flops_per_token(config, S),
                  "update_bytes": flops.update_bytes(config),
                  "chips": len(devices)}
    bad = sum(1 for h in hist if not math.isfinite(float(h["loss"])))
    peak = common.memory_peak(devices)
    del state, hist, feed, train_step
    gc.collect()

    check = compare(config, traffic, hp, key, losses, first, moved)
    check.require(bad == 0, f"{bad} window steps gave a non-finite loss")
    result = {"attempted": n, "failed": bad, "metrics": metrics,
              "device": {**common.device_info(devices),
                         "memory_peak_bytes": peak}}
    return result, check, reader


def reference_rows(config: dict, key, rows: int, seq: int):
    import jax
    import jax.numpy as jnp
    make = jax.jit(lambda k, i: reference.step_tokens(
        k, i, rows, seq, config["vocab_size"]))
    return lambda i: make(key, jnp.int32(i))


def program_grad_norms(config, hp, first) -> list[float]:
    """The first gradient's norm of each leaf the optimizer updates, read
    back from its first update (NaN for a ``held`` leaf)."""
    return [math.nan if role == "held" else
            reference.first_grad_norm(hp, role == "adapt", *stats)
            for role, stats in zip(arch.roles(config), first)]


def kept_leaves(roles, ref_grads) -> list[bool]:
    """Tensors whose change is compared: every ``held`` one, and those
    the optimizer updates whose reference gradient is not nought to
    rounding (under a thousandth of the median such tensor's)."""
    moved = sorted(g for g, r in zip(ref_grads, roles) if r != "held")
    med = moved[len(moved) // 2]
    return [r == "held" or g >= 1e-3 * med for r, g in zip(roles, ref_grads)]


def resolvable(roles, hp, ref_grads, first) -> list[bool]:
    """Tensors whose first gradient norm the stored weights resolve.

    A trust-ratio update ``gamma (g + wd w)`` has a length fixed by the
    ratio; only its weight-decay share tells ``|g|``, so the inversion
    amplifies the float32 rounding of the weights by about
    ``|g| / (wd |w|)``. Where that exceeds ``MAX_AMPLIFICATION`` (on the
    reference's gradient) the norm is not read; a plain tensor's update
    is ``base * g`` and is always read; a held tensor has no update."""
    out = []
    for role, g, (_, _, w2) in zip(roles, ref_grads, first):
        wd_w = hp.wd * w2 ** 0.5
        out.append(role == "plain" or
                   (role == "adapt" and g <= MAX_AMPLIFICATION * wd_w))
    return out


def compare(config, traffic, hp, key, losses, first, moved) -> common.Check:
    rows = traffic["accum_steps"] * traffic["microbatch"] \
        * traffic["data_parallel"]
    S = traffic["seq_len"]
    ref = reference.train_readings(
        config, hp, key, reference_rows(config, key, rows, S), S,
        len(losses))
    return numbers(traffic, config, hp, losses, first, moved, ref)


def numbers(traffic, config, hp, losses, first, moved, ref) -> common.Check:
    lim = traffic["limits"]
    check = common.Check()
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                       ref["losses"]))
    roles = arch.roles(config)
    keep = kept_leaves(roles, ref["grad_norms"])
    readable = [k and r for k, r in zip(
        keep, resolvable(roles, hp, ref["grad_norms"], first))]
    grad_gap, _ = reference.worst_gap(ref["grad_norms"],
                                      program_grad_norms(config, hp, first),
                                      readable)
    move_gap, _ = reference.worst_gap(ref["delta_norms"], moved, keep)
    check.number("loss_gap", loss_gap, lim["loss_gap"])
    check.number("grad_norm_gap", grad_gap, lim["grad_norm_gap"])
    check.number("update_norm_gap", move_gap, lim["update_norm_gap"])
    return check
