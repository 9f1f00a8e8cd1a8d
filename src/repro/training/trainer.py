"""Unified training step + gradient-accumulation engine + host fit loop.

``make_train_step(task, optimizer, accum_steps=K)`` returns the pure
function ``(state, batch) -> (state, metrics)`` used everywhere: jit'd
directly for CPU experiments, or pjit'd with shardings by the launcher —
the function body is identical (GSPMD handles distribution).

``make_train_step(..., mesh=mesh)`` is the mesh-native data-parallel
path: the task loss + accumulation scan run under ``shard_map`` over
the mesh's data axes (batch leaves sharded on the microbatch dim — the
``pipeline.microbatch_pspec`` layout), per-device mean gradients are
``psum``-averaged in f32 across the data axis, and everything
downstream of the all-reduce — the optimizer application, grad_norm,
and the LWN/LGN/LNR traces — sees the replicated GLOBAL-batch
gradients. The fused optimizer therefore still runs exactly two
``pallas_call``s per device per global step, on the replicated flat
``(rows, 128)`` substrate, at any (data_parallel, accum_steps): the
global batch is ``K × D × microbatch`` and scaling D moves samples
onto more devices instead of more scan steps.

``task`` is a :class:`repro.training.tasks.Task` (LM / classifier / SSL
all share one step body); passing a :class:`repro.models.registry.Model`
is accepted as shorthand for ``tasks.lm_task(model)``.

Gradient accumulation (``accum_steps=K > 1``) decouples the global batch
from device memory: ``batch`` leaves carry a leading ``[K, B/K, ...]``
microbatch axis (see ``data.pipeline.stack_microbatches``) and a
``jax.lax.scan`` over K accumulates grads — and the task's mean-reduced
loss/metrics — in f32 at fixed peak memory (one microbatch of
activations + one f32 grad buffer), then applies the optimizer exactly
once per global step. Under ``use_kernel="fused"`` that single
application is still exactly two ``pallas_call``s regardless of K.

Precision: grads are accumulated and averaged in f32 and ``params``
stay f32 regardless of the optimizer's ``precision`` policy — under
``"bf16_master"`` only the fused substrate's state buffers (inside
``opt_state``) are bf16, and the optimizer hands back an f32 delta
that ``apply_updates`` adds to the f32 master params. Nothing in this
module branches on the policy.

Metrics include mean LWN/LGN/LNR so the paper's Fig. 2 telemetry is free
at every step; with accumulation those norms are computed on the
*accumulated* (global-batch) gradients, so the traces reflect the true
global batch. ``fit`` optionally records the full per-layer traces.
"""
from __future__ import annotations

import collections
import dataclasses
import warnings
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import apply_updates, instrumentation
from repro.core.base import GradientTransform
from repro.data import pipeline
from repro.diagnostics import hvp as hvp_lib
from repro.diagnostics import probes as probes_lib
from repro.diagnostics import sink as sinks
from repro.models.registry import Model
from repro.obs import layerwise as obs_layerwise
from repro.obs import scopes
from repro.obs import trace as obs_trace
from repro.training import tasks
from repro.training.losses import WeightedMean
from repro.training.train_state import TrainState


def _accumulate(grad_fn: Callable, params, batch, accum_steps: int):
    """Scan K microbatches: f32 grad sum + weighted-mean loss/metrics.

    ``batch`` leaves are ``[K, B/K, ...]``; peak memory is one
    microbatch of activations plus one f32 grad accumulator, independent
    of K (and therefore of the global batch size).
    """
    with jax.named_scope(scopes.GRAD_ACCUM):
        hvp_lib.check_stacked(batch, accum_steps)

        # shapes only — establishes the metrics-dict structure for the
        # carry
        mb0 = jax.tree_util.tree_map(lambda x: x[0], batch)
        (_, metrics_shape), _ = jax.eval_shape(grad_fn, params, mb0)

        def body(carry, microbatch):
            grad_acc, loss_acc, metric_acc = carry
            (loss, metrics), grads = grad_fn(params, microbatch)
            grad_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), grad_acc, grads)
            loss_acc = loss_acc.add(loss)
            metric_acc = jax.tree_util.tree_map(
                lambda a, v: a.add(v), metric_acc, metrics,
                is_leaf=lambda x: isinstance(x, WeightedMean))
            return (grad_acc, loss_acc, metric_acc), None

        carry0 = (
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params),
            WeightedMean.zero(),
            # metric accumulators take the metric's own shape (metrics
            # need not be scalars — e.g. per-class error vectors)
            jax.tree_util.tree_map(
                lambda s: WeightedMean(jnp.zeros(s.shape, jnp.float32),
                                       jnp.zeros((), jnp.float32)),
                metrics_shape),
        )
        (grad_sum, loss_acc, metric_acc), _ = jax.lax.scan(body, carry0,
                                                           batch)
        grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grad_sum)
        metrics = jax.tree_util.tree_map(
            lambda a: a.result(), metric_acc,
            is_leaf=lambda x: isinstance(x, WeightedMean))
        return loss_acc.result(), metrics, grads


def _check_divisible(batch, accum_steps: int, dp: int, axes) -> None:
    """Trace-time guard: every microbatch dim must split over the data
    axes. Raises naming the offending sizes (shapes are static)."""
    dim = 1 if accum_steps > 1 else 0
    for leaf in jax.tree_util.tree_leaves(batch):
        if leaf.ndim <= dim or leaf.shape[dim] % dp:
            raise ValueError(
                f"mesh train step: batch leaf {leaf.shape} has "
                f"microbatch dim {dim} of size "
                f"{leaf.shape[dim] if leaf.ndim > dim else '<missing>'} "
                f"which does not split over the data-parallel width "
                f"{dp} (axes {axes}); global batch must be "
                f"K x D x per-device-microbatch")


def _sharded_grad_fn(task, mesh: Mesh, axes, accum_steps: int):
    """``(params, batch) -> (loss, metrics, grads)`` under ``shard_map``
    over the data axes: per-shard loss/grads (with the K-scan inside),
    then one f32 ``pmean`` — the all-reduce that makes every device see
    the global-batch mean. Params are replicated (in_spec ``P()``);
    outputs are replicated, so the caller's optimizer/telemetry code is
    identical to the single-device path."""
    grad_fn = jax.value_and_grad(task.loss_fn, has_aux=True)

    def local(params, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
        else:
            loss, metrics, grads = _accumulate(
                grad_fn, params, batch, accum_steps)

        def pm(x):
            return jax.lax.pmean(jnp.asarray(x, jnp.float32), axes)

        with jax.named_scope(scopes.GRAD_PMEAN):
            return (pm(loss), jax.tree_util.tree_map(pm, metrics),
                    jax.tree_util.tree_map(pm, grads))

    bspec = pipeline.batch_axes_pspec(axes, accum_steps)
    return jax.shard_map(local, mesh=mesh, in_specs=(P(), bspec),
                         out_specs=P(), check_vma=False)


def _optimizer_fn(optimizer: GradientTransform, layerwise: bool,
                  mesh: Optional[Mesh]) -> Callable:
    """``(grads, opt_state, params) -> (updates, opt_state, telemetry)``.

    ``telemetry`` is what the ``repro.obs.layerwise`` tap caught
    (empty unless ``layerwise``). With a ``mesh`` every operand is
    replicated, and the update runs under ``shard_map`` so that each
    device applies it to its own copy: the TPU compiler cannot
    partition a Pallas (Mosaic) call, even over replicated operands."""
    def apply(grads, opt_state, params):
        if not layerwise:
            return (*optimizer.update(grads, opt_state, params), {})
        with obs_layerwise.capture() as tap:
            updates, opt_state = optimizer.update(grads, opt_state, params)
        return updates, opt_state, dict(tap)

    if mesh is None:
        return apply
    return jax.shard_map(apply, mesh=mesh, in_specs=(P(), P(), P()),
                         out_specs=P(), check_vma=False)


def make_train_step(task: Union[tasks.Task, Model],
                    optimizer: GradientTransform, *,
                    accum_steps: int = 1,
                    mesh: Optional[Mesh] = None,
                    data_axes: Optional[tuple] = None,
                    lb_coef: float = 1e-2, z_coef: float = 1e-3,
                    record_norms: bool = False,
                    layerwise: bool = False) -> Callable:
    """The one step factory: ``(state, batch) -> (state, metrics)``.

    ``task``: a :class:`~repro.training.tasks.Task`; a ``Model`` is
    wrapped via ``tasks.lm_task(model, lb_coef=..., z_coef=...)`` for
    backward compatibility with the LM call sites.
    ``accum_steps=K>1``: batch leaves are ``[K, B/K, ...]`` stacked
    microbatches; grads/metrics accumulate in f32 over a scan and the
    optimizer applies once per global step.
    ``mesh=``: run the loss + accumulation under ``shard_map`` over the
    mesh's data axes (default ``data_axes``: the ``("pod", "data")``
    subset present in the mesh). The microbatch dim of every batch leaf
    is sharded over those axes (``pipeline.shard_batch`` /
    ``microbatch_pspec`` layout); params and optimizer state must be
    replicated over them. Gradients are psum-averaged in f32 inside the
    region, so grad_norm / LWN / LGN / LNR and the optimizer all see
    the global-batch gradients, and the fused path keeps its exact
    2-``pallas_call``-per-device invariant. A mesh whose data width is
    1 falls back to the identical single-device body.

    ``layerwise=True`` activates the ``repro.obs.layerwise`` tap around
    ``optimizer.update`` at trace time: the per-segment ``(w_norm,
    g_norm, trust_ratio)`` triples the layer-wise optimizers already
    materialize become extra jitted-step outputs under
    ``layerwise/{metric}`` (each a ``(nseg,)`` f32 array) — zero extra
    ``pallas_call``s, no sync points; under ``fit(...,
    async_metrics=N)`` they ride the MetricRing like every metric.
    Host-side naming/decimation is ``fit``'s ``layerwise_every`` /
    ``layerwise_names`` / ``layerwise_history``.

    The returned step also accepts the batch splatted as positional args
    (``step(state, images, labels)``), matching the legacy per-workload
    factories' signatures.
    """
    if isinstance(task, Model):
        task = tasks.lm_task(task, lb_coef=lb_coef, z_coef=z_coef)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    grad_fn = jax.value_and_grad(task.loss_fn, has_aux=True)

    dp = pipeline.resolve_dp_size(mesh, data_axes)
    if dp > 1:
        data_axes = pipeline.resolve_data_axes(mesh, data_axes)
        sharded = _sharded_grad_fn(task, mesh, data_axes, accum_steps)
    else:
        sharded = None
    apply_optimizer = _optimizer_fn(optimizer, layerwise,
                                    mesh if dp > 1 else None)

    def train_step(state: TrainState, *batch_args):
        batch = batch_args[0] if len(batch_args) == 1 else batch_args
        if sharded is not None:
            _check_divisible(batch, accum_steps, dp, data_axes)
            loss, task_metrics, grads = sharded(state.params, batch)
        elif accum_steps == 1:
            (loss, task_metrics), grads = grad_fn(state.params, batch)
        else:
            loss, task_metrics, grads = _accumulate(
                grad_fn, state.params, batch, accum_steps)
        clash = {"loss", "grad_norm", "layer_norms"} & set(task_metrics)
        if clash:
            raise ValueError(
                f"task {task.name!r} metrics {sorted(clash)} collide with "
                f"trainer-reserved metric names")
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state, tap = apply_optimizer(
                grads, state.opt_state, state.params)
            with jax.named_scope(scopes.APPLY_UPDATES):
                params = apply_updates(state.params, updates)
        with jax.named_scope(scopes.STEP_METRICS):
            metrics = {"loss": loss, **task_metrics,
                       "grad_norm": instrumentation.global_norm(grads)}
            for k, v in tap.items():
                metrics[f"{obs_layerwise.PREFIX}{k}"] = v
            if record_norms:
                # on the accumulated grads: Fig. 2 traces see the
                # global batch
                metrics["layer_norms"] = instrumentation.layer_norms(
                    state.params, grads)
        return TrainState(state.step + 1, params, opt_state), metrics

    return train_step


def make_classifier_step(apply_fn: Callable,
                         optimizer: GradientTransform, *,
                         accum_steps: int = 1,
                         mesh: Optional[Mesh] = None,
                         record_norms: bool = False) -> Callable:
    """Back-compat shim: ``make_train_step(tasks.classifier_task(...))``."""
    return make_train_step(tasks.classifier_task(apply_fn), optimizer,
                           accum_steps=accum_steps, mesh=mesh,
                           record_norms=record_norms)


def make_ssl_step(embed_fn: Callable, optimizer: GradientTransform, *,
                  lambda_offdiag: float = 5e-3,
                  accum_steps: int = 1,
                  mesh: Optional[Mesh] = None,
                  record_norms: bool = False) -> Callable:
    """Back-compat shim: ``make_train_step(tasks.ssl_task(...))``."""
    return make_train_step(
        tasks.ssl_task(embed_fn, lambda_offdiag=lambda_offdiag), optimizer,
        accum_steps=accum_steps, mesh=mesh, record_norms=record_norms)


class MetricRing:
    """Bounded ring of in-flight device metric futures.

    The host/device overlap primitive behind ``fit(...,
    async_metrics=N)`` (and the launcher's ``--async-metrics``): the
    dispatch loop ``append``s each step's *unmaterialized* device
    metrics (jax dispatch is asynchronous — holding the arrays costs
    nothing), and only once more than ``window`` entries are in flight
    is the oldest resolved — one ``jax.device_get``, the single point
    that waits on the device — and handed to its ``emit(step, host,
    last)`` callback.  The loop therefore runs up to ``window`` steps
    ahead of materialization, while the ring still bounds in-flight
    depth (an unbounded run-ahead would queue arbitrarily many device
    computations and buffers).

    Values are EXACT: the same arrays the synchronous path would have
    converted, materialized late.  Emission order is exactly append
    order, so interleaved train/probe/recorder records resolve in the
    same sequence the synchronous loop would have produced.  ``drain``
    resolves everything still in flight (end of run).

    ``tracer=`` records a ``resolve`` span around each entry's
    ``device_get`` — the single point the host waits on the device, and
    the number that shows how far ahead the dispatch loop runs.
    """

    def __init__(self, window: int, *,
                 tracer: Optional["obs_trace.Tracer"] = None):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.window = int(window)
        self._tracer = obs_trace.NULL if tracer is None else tracer
        self._ring: collections.deque = collections.deque()

    def __len__(self) -> int:
        return len(self._ring)

    def append(self, step: int, values, emit: Callable, *,
               last: bool = False) -> None:
        """Enqueue device ``values``; resolves the oldest entries down
        to ``window`` in flight (FIFO, so order is preserved)."""
        self._ring.append((step, values, emit, last))
        while len(self._ring) > self.window:
            self._pop()

    def _pop(self) -> None:
        step, values, emit, last = self._ring.popleft()
        with self._tracer.span("resolve", step=step,
                               in_flight=len(self._ring) + 1):
            host = jax.device_get(values)
        emit(step, host, last)

    def drain(self) -> None:
        """Resolve every in-flight entry (the end-of-run barrier)."""
        while self._ring:
            self._pop()


def _to_host_scalars(metrics) -> dict:
    """Materialized metrics tree -> {key: float|array} exactly as the
    synchronous path converts them (floats for 0-d, arrays verbatim)."""
    return {k: float(v) if np.ndim(v) == 0 else v
            for k, v in metrics.items()}


@dataclasses.dataclass(frozen=True)
class FitOptions:
    """Every ``fit`` knob in one value: ``fit(step, state, batches, n,
    options=FitOptions(...))``.

    Fields group into: **logging** (``log_every``, ``log_fn``,
    ``sink``, ``close_sink``, ``callbacks``, ``recorder``), **control**
    (``controller``, ``async_metrics``, ``donate``) and
    **observability** (``tracer``, ``profiler``, ``layerwise_every``,
    ``layerwise_names``, ``layerwise_history``). Defaults are exactly
    the historical flat-kwarg defaults; semantics are documented on
    :func:`fit`. The dataclass is frozen — build variants with
    ``dataclasses.replace(options, ...)``."""
    # logging
    recorder: Optional[instrumentation.NormRecorder] = None
    log_every: int = 0
    log_fn: Callable = print
    sink: Optional["sinks.MetricsSink"] = None
    close_sink: bool = False
    callbacks: Sequence = ()
    # control
    controller: object = None
    async_metrics: Union[bool, int] = False
    donate: Optional[bool] = None
    # observability
    tracer: Optional["obs_trace.Tracer"] = None
    profiler: object = None
    layerwise_every: int = 0
    layerwise_names: Optional[Sequence[str]] = None
    layerwise_history: Optional["obs_layerwise.LayerwiseHistory"] = None


_FIT_FIELDS = tuple(f.name for f in dataclasses.fields(FitOptions))


def _resolve_fit_options(options, kwargs) -> FitOptions:
    """The deprecation shim: flat ``fit(..., sink=...)`` kwargs forward
    into :class:`FitOptions` (warning once per call site); mixing both
    spellings is an error, unknown names fail like the old signature
    did."""
    if not kwargs:
        return options if options is not None else FitOptions()
    unknown = sorted(set(kwargs) - set(_FIT_FIELDS))
    if unknown:
        raise TypeError(
            f"fit() got unexpected keyword arguments {unknown}; "
            f"valid FitOptions fields: {sorted(_FIT_FIELDS)}")
    if options is not None:
        raise TypeError(
            "pass options=FitOptions(...) OR flat kwargs, not both "
            f"(got options= and {sorted(kwargs)})")
    warnings.warn(
        "flat fit(...) keyword arguments are deprecated; pass "
        "options=FitOptions(...) (fields and defaults are identical)",
        DeprecationWarning, stacklevel=3)
    return FitOptions(**kwargs)


def fit(train_step: Optional[Callable], state: TrainState, batches,
        num_steps: int,
        *, options: Optional[FitOptions] = None, **kwargs,
        ) -> tuple[TrainState, list[dict]]:
    """Host loop used by CPU-scale experiments. ``batches`` yields one
    pytree per *global* step: dict batches (LM) or tuples
    (classifier/SSL args); for an accumulating step the leaves carry the
    stacked ``[K, B/K, ...]`` microbatch axis (see
    ``data.pipeline.stack_microbatches`` / the iterators'
    ``accum_steps=`` knob).

    Metrics stream through one :class:`repro.diagnostics.sink
    .MetricsSink`: pass ``sink=`` explicitly (JSONL/CSV/...; written
    every step) or rely on ``log_every``/``log_fn``, which build the
    default :class:`ConsoleSink` reproducing the historical console
    line at the same cadence.  ``callbacks`` are
    :class:`repro.diagnostics.probes.Probe` objects — each runs when
    ``step % probe.every == 0`` (after the optimizer step, on the
    *separate* jitted probe computation, so the train step and its
    2-``pallas_call`` fused invariant are untouched) and its metrics
    land in the sink under ``{probe.name}/{key}``.

    ``donate`` donates the TrainState argument to the jitted step so
    params and optimizer buffers update in place — this is what makes
    the fused optimizer path's flat momentum buffers memory-neutral at
    scale. Default: on for tpu/gpu, off on CPU (where XLA cannot reuse
    donated buffers and would warn every call).

    ``controller`` is an :class:`repro.training.controller
    .AdaptiveBatchController`: pass ``train_step=None`` and a
    ``batches`` stream exposing ``set_accum_steps`` (e.g.
    :class:`repro.data.pipeline.MicrobatchedStream`).  The controller
    owns the per-K compiled steps (cache-keyed, so revisiting a K is
    free), runs as a probe every ``controller.every`` steps streaming
    ``controller/*`` metrics, and its K switches take effect at the
    next batch pull — the re-stack boundary between jitted segments.
    ``donate`` is governed by the controller's own ``donate=`` flag in
    this mode.

    ``async_metrics`` makes the host loop non-blocking: instead of the
    per-step ``float()``/``jax.device_get`` (which stalls the dispatch
    loop until the device finishes the step), each step's device
    metrics enter a bounded :class:`MetricRing` and materialize
    ``window`` steps late — ``True`` picks ``max(log_every, 1)`` (or 8
    when ``log_every`` is 0), an int sets the window explicitly.
    Values are exact (same arrays, delayed materialization), history
    and sink records keep their order and step keys, and probes with a
    ``dispatch``/``resolve`` split are dispatched at their scheduled
    step and resolved through the same ring, so probe compute overlaps
    subsequent train steps instead of blocking at the probe boundary.
    Delayed metrics are safe whenever nothing on the host consumes a
    step's metric values before ``window`` later steps have been
    dispatched — the adaptive controller is the exception (its decision
    changes the next batch), so it keeps its synchronous boundary and
    only its probe dispatch overlaps.

    ``close_sink=True`` closes ``sink`` after the final write (the
    default-constructed console sink is always closed); leave False
    when the caller owns the sink (e.g. a ``with JsonlSink(...)``
    block or a sink reused across fits).

    Observability (``repro.obs``):

    * ``tracer=`` — a :class:`repro.obs.trace.Tracer`; the loop records
      ``data_wait`` (blocking on the batch iterator), ``dispatch`` (the
      jitted step call — async dispatch, so this is host-side cost, not
      device time), ``resolve`` (the MetricRing's per-entry
      ``device_get``, or the synchronous path's per-step one),
      ``probe`` / ``controller`` spans.  Export the ring afterwards
      with ``tracer.export(sink)`` / render with
      ``tools/render_trace.py``.  The tracer also puts each span, and
      each iteration as a ``"train"`` ``StepTraceAnnotation``, into a
      running profiler trace.
    * ``profiler=`` — a :class:`repro.obs.profiler.StepProfiler`
      (``obs.profile(logdir, start=, steps=)``); ``profiler.step(i)``
      runs each iteration and ``close()`` fires in the ``finally``.
    * ``layerwise_every=N`` — decimate the ``layerwise/*`` arrays a
      ``layerwise=True`` train step emits: records keep them only every
      N-th step (0/1 = every step; other steps' records carry just the
      scalar metrics).  Decimation is host-side, so the jitted step's
      signature — and the fused 2-``pallas_call`` invariant — never
      changes.  ``layerwise_names=`` (e.g.
      ``labels.leaf_names(params)``) expands the arrays to
      ``layerwise/{segment}/{metric}`` scalars;
      ``layerwise_history=`` additionally offers each kept snapshot to
      a :class:`repro.obs.LayerwiseHistory`.

    All knobs live on :class:`FitOptions` (``options=``); the flat
    keyword spellings above keep working through a deprecation shim
    that forwards them into ``FitOptions`` unchanged."""
    o = _resolve_fit_options(options, kwargs)
    recorder, sink, callbacks = o.recorder, o.sink, o.callbacks
    log_every, log_fn, close_sink = o.log_every, o.log_fn, o.close_sink
    controller, async_metrics, donate = (o.controller, o.async_metrics,
                                         o.donate)
    tracer, profiler = o.tracer, o.profiler
    layerwise_every = o.layerwise_every
    layerwise_names = o.layerwise_names
    layerwise_history = o.layerwise_history
    if controller is not None:
        if train_step is not None:
            raise ValueError(
                "pass train_step=None with controller=: the controller "
                "builds (and caches) the per-K train steps itself")
        controller.attach(batches)
        callbacks = (*callbacks, controller)
        step_fn = None
    else:
        if donate is None:
            donate = jax.default_backend() in ("tpu", "gpu")
        step_fn = jax.jit(train_step, donate_argnums=(0,)) if donate \
            else jax.jit(train_step)
    if sink is None:
        sink = sinks.ConsoleSink(every=log_every, log_fn=log_fn) \
            if log_every else None
        close_sink = close_sink or sink is not None
    if async_metrics is True:
        async_metrics = max(log_every, 1) if log_every else 8
    tracer = obs_trace.NULL if tracer is None else tracer
    ring = MetricRing(int(async_metrics), tracer=tracer) \
        if async_metrics else None
    history: list[dict] = []

    def emit_train(step, host_metrics, last, step_batch_size=None):
        host = _to_host_scalars(host_metrics)
        if step_batch_size is not None:
            # adaptive runs: every record carries the batch it trained
            # at (the static sink field would go stale across switches)
            host["global_batch"] = float(step_batch_size)
        rest, lw = obs_layerwise.split_record(host)
        if lw:
            if layerwise_every > 1 and step % layerwise_every:
                host = rest
            else:
                expanded = obs_layerwise.expand(lw, layerwise_names)
                host = {**rest, **expanded}
                if layerwise_history is not None:
                    layerwise_history.add(step, expanded)
        history.append(host)
        if sink is not None:
            sink.write(step, host, last=last)

    def emit_probe(step, out, last, probe=None):
        if out and sink is not None:
            # probe lines always flush (last=True beats the console
            # sink's every-N gate)
            sink.write(step, {f"{probe.name}/{k}": v
                              for k, v in out.items()}, last=True)

    try:
        for i in range(num_steps):
            if profiler is not None:
                profiler.step(i)
            with tracer.step("train", i):
                # read the target BEFORE the pull: controller retargets
                # land at the next pull, so this is the batch this step
                # trains at
                step_batch_size = controller.global_batch \
                    if controller is not None else None
                with tracer.span("data_wait", step=i):
                    batch = next(batches)
                fn = controller.step_fn() if controller is not None \
                    else step_fn
                with tracer.span("dispatch", step=i):
                    if isinstance(batch, dict):
                        state, metrics = fn(state, batch)
                    else:
                        state, metrics = fn(state, *batch)
                ln = metrics.pop("layer_norms", None)
                last = i == num_steps - 1
                if ring is None:
                    if recorder is not None and ln is not None:
                        recorder.record(i, ln)
                    # scalars -> python floats; non-scalar task metrics
                    # (e.g. per-class vectors) as host numpy arrays
                    with tracer.span("resolve", step=i):
                        host_metrics = jax.device_get(metrics)
                    emit_train(i, host_metrics, last, step_batch_size)
                else:
                    if recorder is not None and ln is not None:
                        ring.append(
                            i, ln,
                            lambda s, v, _l: recorder.record(s, v))
                    ring.append(
                        i, metrics,
                        lambda s, v, l, _b=step_batch_size:
                            emit_train(s, v, l, _b),
                        last=last)
                for probe in callbacks:
                    prepare = getattr(probe, "prepare", None)
                    if prepare is not None:
                        # side-stream pre-dispatch hook (e.g. the adaptive
                        # controller launching its noise probe early)
                        prepare(i, state)
                    if not probes_lib.probe_due(probe, i):
                        continue
                    span_name = "controller" if probe is controller \
                        else "probe"
                    if ring is not None and hasattr(probe, "dispatch") \
                            and hasattr(probe, "resolve") \
                            and probe is not controller:
                        with tracer.span(span_name, step=i,
                                         probe=getattr(probe, "name", "?"),
                                         mode="dispatch"):
                            raw = probe.dispatch(i, state)
                        ring.append(i, raw,
                                    lambda s, v, l, _p=probe:
                                        emit_probe(s, _p.resolve(v), l, _p))
                    else:
                        with tracer.span(span_name, step=i,
                                         probe=getattr(probe, "name", "?")):
                            out = probe(i, state)
                        if ring is None:
                            emit_probe(i, out, True, probe)
                        else:
                            # already-host values ride the ring so records
                            # keep the synchronous path's exact order
                            ring.append(i, out,
                                        lambda s, v, l, _p=probe:
                                            emit_probe(s, v, l, _p))
        if ring is not None:
            ring.drain()
    finally:
        if profiler is not None:
            profiler.close()
        if close_sink and sink is not None:
            sink.close()
    return state, history
