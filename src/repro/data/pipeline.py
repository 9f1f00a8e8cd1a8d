"""Sharded global-batch pipeline + microbatch streams.

On a real pod each process feeds its local shard of the global batch;
``shard_batch`` places a host-side global batch onto the mesh with the
batch dim sharded over the data axes (``("pod","data")`` when multi-pod)
and everything else replicated — the exact layout ``train_step`` expects.

Gradient accumulation adds one wrinkle: an accumulating step consumes
``[K, B/K, ...]`` leaves (``stack_microbatches``), where the *scan* axis
K stays replicated and the *microbatch* axis (dim 1) is the one sharded
over data — ``shard_batch(..., batch_dim=1)`` / ``microbatch_pspec``.
Accumulation therefore composes with the data/model mesh axes: the
global batch is ``K × microbatch × data_parallel`` samples.
"""
from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def resolve_data_axes(mesh: Mesh, axes=None) -> tuple[str, ...]:
    """THE data-axis resolver every ``mesh=`` entry point (train step
    and probes alike) goes through: the ``("pod", "data")`` subset
    present in ``mesh``, or explicit ``axes`` validated against it."""
    if axes is None:
        return data_axes(mesh)
    axes = tuple(axes)
    missing = [a for a in axes if a not in mesh.shape]
    if missing:
        raise ValueError(f"data_axes {axes} not in mesh axes "
                         f"{tuple(mesh.axis_names)}")
    return axes


def resolve_dp_size(mesh: Optional[Mesh], axes=None) -> int:
    """Data-parallel width of ``mesh`` (1 for ``mesh=None``)."""
    if mesh is None:
        return 1
    return dp_size(mesh, resolve_data_axes(mesh, axes))


def shard_over_data(fn: Callable, mesh: Mesh, axes: tuple,
                    accum_steps: int) -> Callable:
    """``shard_map`` a ``(replicated..., batch) -> replicated``
    computation over the data axes: every positional arg except the
    LAST is replicated, the last is the batch (microbatch dim sharded,
    the :func:`batch_axes_pspec` layout).  ``fn`` must make its
    outputs replicated itself (pmean/psum)."""
    def wrapped(*args):
        n_rep = len(args) - 1
        in_specs = (P(),) * n_rep \
            + (batch_axes_pspec(axes, accum_steps),)
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=P(), check_vma=False)(*args)
    return wrapped


def dp_size(mesh: Mesh, axes: tuple[str, ...] | None = None) -> int:
    """Total data-parallel width: the product of the data axes."""
    out = 1
    for a in (data_axes(mesh) if axes is None else axes):
        out *= int(mesh.shape[a])
    return out


def batch_axes_pspec(axes, accum_steps: int = 1) -> P:
    """Batch-leaf spec for explicit data axes — THE one encoding of
    the batch layout: the microbatch dim shards over ``axes``, the K
    scan dim (when stacked) stays replicated.  Shared by
    ``shard_batch``-placed inputs, the trainer's ``shard_map``
    in_specs, and the probes' — change it here, every mesh consumer
    follows."""
    axes = tuple(axes)
    return P(None, axes) if accum_steps > 1 else P(axes)


def batch_pspec(mesh: Mesh) -> P:
    return batch_axes_pspec(data_axes(mesh))


def microbatch_pspec(mesh: Mesh) -> P:
    """Spec for stacked ``[K, B/K, ...]`` leaves: K replicated, B/K
    sharded over the data axes."""
    return batch_axes_pspec(data_axes(mesh), 2)


def stack_microbatches(batch: Any, accum_steps: int) -> Any:
    """Reshape every ``[B, ...]`` leaf to ``[K, B/K, ...]``.

    The accumulating train step scans dim 0 (K microbatches) and sees
    dim 1 as its per-pass batch. Because this is a pure reshape of one
    global batch, K×(B/K) accumulation consumes *exactly* the same
    samples as a single B-sized pass — the basis of the parity tests.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def split(x):
        b = x.shape[0]
        if b % accum_steps:
            raise ValueError(
                f"global batch {b} not divisible by accum_steps="
                f"{accum_steps}")
        return x.reshape((accum_steps, b // accum_steps) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def shard_batch(mesh: Mesh, batch: Any, *, batch_dim: int = 0) -> Any:
    """Device-put a pytree of arrays with ``batch_dim`` sharded over the
    data axes (``batch_dim=1`` for stacked microbatch leaves).

    A batch dim that does not divide the data-parallel width raises a
    :class:`ValueError` naming the offending sizes, instead of the
    opaque GSPMD sharding error jax would produce downstream.
    """
    axes = data_axes(mesh)
    dp = dp_size(mesh)

    def place(x):
        if x.ndim <= batch_dim:
            raise ValueError(
                f"shard_batch(batch_dim={batch_dim}): leaf of shape "
                f"{x.shape} has no dim {batch_dim} to shard over "
                f"{axes}")
        if dp > 1 and x.shape[batch_dim] % dp:
            raise ValueError(
                f"batch dim {batch_dim} of size {x.shape[batch_dim]} "
                f"(leaf shape {x.shape}) is not divisible by the "
                f"data-parallel width {dp} (mesh axes "
                f"{ {a: int(mesh.shape[a]) for a in axes} }); pick a "
                f"microbatch that is a multiple of the data width")
        dims = [None] * x.ndim
        dims[batch_dim] = axes
        return jax.device_put(x, NamedSharding(mesh, P(*dims)))
    return jax.tree_util.tree_map(place, batch)


def sharded_iterator(mesh: Mesh, host_iter: Iterator, *,
                     batch_dim: int = 0) -> Iterator:
    for batch in host_iter:
        yield shard_batch(mesh, batch, batch_dim=batch_dim)


class MicrobatchedStream:
    """Microbatched batch stream whose ``accum_steps`` K *and*
    ``data_parallel`` D can be retargeted mid-stream — the adaptive
    batch-size controller's re-stack boundary, now covering both global
    batch knobs (``global_batch = K × D × microbatch``).

    ``source`` is a *sample-level* provider ``(start, count) -> batch
    pytree`` with ``count`` leading-dim samples; sample ``i`` must
    depend only on ``i`` (see ``data.synthetic.*_sample_source``).
    Each ``next()`` consumes the next ``K × D × microbatch`` contiguous
    samples and advances ``position`` by exactly that — so changing K
    or D preserves the epoch position: no sample is skipped or re-read,
    and a fresh stream started at the same ``position`` sees the
    identical upcoming samples regardless of how earlier samples were
    partitioned (the basis of the controller's switch parity tests).

    ``microbatch`` is the PER-DEVICE pass size; the per-pull microbatch
    dim is ``D × microbatch`` samples, which the train step's
    ``shard_map`` splits over the data axis. Yields
    ``[K, D·microbatch, ...]`` stacked leaves for K > 1 and plain
    ``[D·microbatch, ...]`` leaves for K = 1, matching what
    ``make_train_step(accum_steps=K, mesh=...)`` expects in each
    regime. Host-side yields are unplaced; the controller's step
    wrapper (or the caller) does the ``shard_batch`` placement.
    """

    def __init__(self, source, microbatch: int, accum_steps: int = 1,
                 *, data_parallel: int = 1, position: int = 0):
        if microbatch < 1:
            raise ValueError(f"microbatch must be >= 1, got {microbatch}")
        self.source = source
        self.microbatch = microbatch
        self.position = position
        self._k = 0
        self._dp = 0
        self.set_accum_steps(accum_steps)
        self.set_data_parallel(data_parallel)

    @property
    def accum_steps(self) -> int:
        return self._k

    @property
    def data_parallel(self) -> int:
        return self._dp

    @property
    def global_batch(self) -> int:
        return self._k * self._dp * self.microbatch

    def set_accum_steps(self, accum_steps: int) -> None:
        """Retarget K; takes effect from the next ``next()``."""
        if accum_steps < 1:
            raise ValueError(
                f"accum_steps must be >= 1, got {accum_steps}")
        self._k = int(accum_steps)

    def set_data_parallel(self, data_parallel: int) -> None:
        """Retarget D; takes effect from the next ``next()``."""
        if data_parallel < 1:
            raise ValueError(
                f"data_parallel must be >= 1, got {data_parallel}")
        self._dp = int(data_parallel)

    def __iter__(self) -> "MicrobatchedStream":
        return self

    def __next__(self):
        n = self._k * self._dp * self.microbatch
        batch = self.source(self.position, n)
        self.position += n
        if self._k == 1:
            return batch
        return stack_microbatches(batch, self._k)


def microbatched_iterator(host_iter: Iterator, accum_steps: int) -> Iterator:
    """Wrap a global-batch stream into stacked microbatch pytrees.

    Fixed-K convenience: for a stream whose K must change mid-run (the
    adaptive controller), build a :class:`MicrobatchedStream` from a
    sample-level source instead.
    """
    for batch in host_iter:
        yield stack_microbatches(batch, accum_steps)


def device_put_batch(batch: Any) -> Any:
    """Asynchronously start the host->device transfer of every leaf
    (plain single-device ``jax.device_put``) — the default placement
    for :class:`PrefetchingStream` when no mesh is involved."""
    return jax.tree_util.tree_map(jax.device_put, batch)


class PrefetchingStream:
    """Background-producer prefetch over any batch stream.

    A daemon thread pulls batches from ``stream`` ahead of the
    consumer into a bounded buffer (``size=2`` = classic double
    buffering), optionally running ``place`` on each batch *on the
    producer thread* — with ``place=device_put_batch`` (or a
    mesh-aware ``shard_batch`` closure) the host->device copy of batch
    N+1 overlaps the device compute of batch N, and the synthetic
    sources' jax-side sample generation is dispatched off the critical
    path.  ``next()`` pops the oldest buffered batch, blocking only
    when the producer has not kept up.  Producer exceptions (including
    ``StopIteration`` for finite streams) are re-raised on the
    consumer thread at the ``next()`` where they become visible.

    Retargeting contract (the adaptive controller's re-stack
    boundary): ``set_accum_steps``/``set_data_parallel`` compose with
    prefetching via an explicit **drain-and-refill**: the producer is
    held off its next pull, every buffered-but-unconsumed batch is
    discarded and the underlying stream's ``position`` is rewound by
    exactly the samples those batches had consumed, then the retarget
    is forwarded and the buffer refills at the new shape — so a switch
    at step N is sample-identical to switching an unprefetched
    ``MicrobatchedStream`` at step N (no sample skipped or re-read).
    Retargeting therefore requires the wrapped stream to expose both
    the ``set_*`` method and a writable ``position``; plain iteration
    does not.

    Thread-compat: one producer, one consumer; ``set_*`` must be
    called from the consumer thread between ``next()`` calls (exactly
    how ``trainer.fit``'s controller path drives it).

    ``tracer=`` (a :class:`repro.obs.trace.Tracer`) records a
    ``produce`` span around each producer pull+place; alongside the
    consumer loop's ``data_wait`` spans it shows whether the pipeline
    keeps up (spans land in the shared ring tagged with the producer
    thread's name).
    """

    def __init__(self, stream, *, size: int = 2,
                 place: Optional[Callable[[Any], Any]] = None,
                 tracer=None):
        if size < 1:
            raise ValueError(f"size must be >= 1, got {size}")
        from repro.obs import trace as obs_trace
        self.stream = stream
        self.size = int(size)
        self.place = place
        self._tracer = obs_trace.NULL if tracer is None else tracer
        self._buf: collections.deque = collections.deque()
        self._cv = threading.Condition()
        # serializes stream access: each producer pull vs. the
        # drain-rewind-retarget critical section
        self._plock = threading.Lock()
        self._err: Optional[BaseException] = None
        self._stop = False
        self._thread = threading.Thread(
            target=self._produce, name="PrefetchingStream-producer",
            daemon=True)
        self._thread.start()

    # ------------------------------------------------------ delegation
    @property
    def microbatch(self):
        return self.stream.microbatch

    @property
    def accum_steps(self):
        return self.stream.accum_steps

    @property
    def data_parallel(self):
        return self.stream.data_parallel

    @property
    def global_batch(self):
        return self.stream.global_batch

    @property
    def position(self):
        return self.stream.position

    # -------------------------------------------------------- producer
    def _produce(self) -> None:
        while True:
            with self._cv:
                while len(self._buf) >= self.size and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
            with self._plock:
                if self._stop:
                    return
                try:
                    pos0 = getattr(self.stream, "position", None)
                    with self._tracer.span("produce"):
                        batch = next(self.stream)
                        if self.place is not None:
                            batch = self.place(batch)
                    consumed = None if pos0 is None \
                        else self.stream.position - pos0
                except BaseException as e:   # incl. StopIteration
                    with self._cv:
                        self._err = e
                        self._cv.notify_all()
                    return
            with self._cv:
                self._buf.append((batch, consumed))
                self._cv.notify_all()

    # -------------------------------------------------------- consumer
    def __iter__(self) -> "PrefetchingStream":
        return self

    def __next__(self):
        with self._cv:
            while not self._buf and self._err is None:
                self._cv.wait()
            if self._buf:
                batch, _ = self._buf.popleft()
                self._cv.notify_all()
                return batch
            err = self._err
        if isinstance(err, StopIteration):
            raise StopIteration
        raise err

    # ------------------------------------------------------ retargeting
    def _drain_and(self, apply: Callable[[], None]) -> None:
        """Drain-and-refill: with the producer parked (plock held, so
        no pull is in flight), rewind the wrapped stream past every
        unconsumed buffered batch, apply the retarget, and let the
        buffer refill at the new shape."""
        with self._plock:
            with self._cv:
                unconsumed = 0
                for _, n in self._buf:
                    if n is None:
                        raise RuntimeError(
                            "PrefetchingStream: cannot retarget over a "
                            "stream without a sample position "
                            "(drain/rewind needs stream.position)")
                    unconsumed += n
                self._buf.clear()
                if unconsumed:
                    self.stream.position -= unconsumed
                apply()
                self._cv.notify_all()

    def set_accum_steps(self, accum_steps: int) -> None:
        if getattr(self.stream, "accum_steps", None) == accum_steps:
            return
        self._drain_and(
            lambda: self.stream.set_accum_steps(accum_steps))

    def set_data_parallel(self, data_parallel: int) -> None:
        if getattr(self.stream, "data_parallel", None) == data_parallel:
            return
        self._drain_and(
            lambda: self.stream.set_data_parallel(data_parallel))

    # ---------------------------------------------------------- close
    def close(self) -> None:
        """Stop the producer (idempotent); buffered batches are
        dropped."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=5.0)

    def __enter__(self) -> "PrefetchingStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class LengthBucketedStream:
    """Length-bucketing for LM batches (the tensor2tensor
    ``data_reader`` idiom): group samples of similar length so each
    batch only pads to its *bucket boundary* instead of the global
    max — less pad compute per token at the cost of one compiled step
    per bucket shape (bounded by ``len(boundaries)``).

    ``source`` is a sample-level provider ``(start, count) -> batch``
    whose dict batches carry a per-sample ``"length"`` leaf (e.g.
    :func:`repro.data.synthetic.lm_varlen_sample_source`); sequence
    leaves are padded to a common max length.  The stream pulls
    ``lookahead × microbatch`` samples at a time in index order,
    queues each sample into the smallest bucket whose boundary covers
    its length, and yields a ``microbatch``-sized batch from the
    first full bucket (FIFO within a bucket), with every sequence
    leaf trimmed to the bucket boundary.  Deterministic: the same
    source + boundaries + microbatch always yields the same batches,
    and every pulled sample is yielded exactly once (lookahead
    leftovers stay queued for later batches).
    """

    def __init__(self, source, microbatch: int,
                 boundaries: tuple[int, ...], *, lookahead: int = 8,
                 length_key: str = "length", position: int = 0):
        if microbatch < 1:
            raise ValueError(f"microbatch must be >= 1, got {microbatch}")
        if lookahead < 1:
            raise ValueError(f"lookahead must be >= 1, got {lookahead}")
        bounds = tuple(sorted(int(b) for b in boundaries))
        if not bounds or any(b < 1 for b in bounds) \
                or len(set(bounds)) != len(bounds):
            raise ValueError(
                f"boundaries must be distinct positive ints, "
                f"got {boundaries}")
        self.source = source
        self.microbatch = int(microbatch)
        self.boundaries = bounds
        self.lookahead = int(lookahead)
        self.length_key = length_key
        self.position = int(position)
        self._buckets: dict[int, list] = {b: [] for b in bounds}

    def _bucket_of(self, length: int) -> int:
        for b in self.boundaries:
            if length <= b:
                return b
        return self.boundaries[-1]   # longer than the last boundary:
        # padded sequences are never extended, only trimmed less

    def _refill(self) -> None:
        n = self.lookahead * self.microbatch
        batch = self.source(self.position, n)
        self.position += n
        lengths = np.asarray(batch[self.length_key])
        host = {k: np.asarray(v) for k, v in batch.items()}
        for i in range(n):
            b = self._bucket_of(int(lengths[i]))
            self._buckets[b].append(
                {k: v[i] for k, v in host.items()})

    def queued(self) -> int:
        """Samples pulled from the source but not yet yielded."""
        return sum(len(q) for q in self._buckets.values())

    def __iter__(self) -> "LengthBucketedStream":
        return self

    def __next__(self) -> dict:
        while True:
            for b in self.boundaries:
                q = self._buckets[b]
                if len(q) >= self.microbatch:
                    rows, self._buckets[b] = \
                        q[:self.microbatch], q[self.microbatch:]
                    out = {}
                    for k in rows[0]:
                        stackd = np.stack([r[k] for r in rows])
                        if stackd.ndim >= 2 and stackd.shape[1] > b:
                            stackd = stackd[:, :b]   # trim pad to the
                            # bucket boundary (sequence leaves only)
                        out[k] = stackd
                    return out
            self._refill()
