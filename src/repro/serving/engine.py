"""Continuous-batching LM serving engine.

One :class:`Engine` owns a fixed-slot decode batch (``ServeConfig.
slots`` rows, ``max_len`` cache entries each — the
:class:`~repro.serving.kv_cache.PagedKVCache` pool), a waiting queue,
and exactly one compiled decode step. Requests are admitted into free
slots via single-shot batched prefill (``model.prefill``: one
full-sequence forward + KV dump, padded to power-of-two length/count
buckets so compilations stay bounded), then every engine ``step()``
advances ALL occupied slots one token in one device call — requests
enter and leave mid-flight (continuous / in-flight batching) without
ever changing the decode step's jit signature:

* the cache pytree is always ``[slots, max_len]`` per layer,
* per-slot depths ride in as a ``[slots]`` int32 position vector
  (``layers.attention_decode``'s vector-pos path),
* free slots decode garbage that is never read (their mask attends
  position 0 only; admission overwrites the whole slot row).

``Engine.decode_compilations`` exposes the jit cache size so tests can
assert the compile-once discipline — the serving twin of the training
side's per-K compiled-step cache.

Weights restore through the sharding-aware checkpoint reader
(:meth:`Engine.from_checkpoint` -> ``checkpoint.restore(mesh=)``), so
one engine can span a data/model mesh: the payload is
mesh-independent and the decode step is jitted over whatever
placements the params carry.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import attention_decode
from repro.obs import trace
from repro.serving import sampling
from repro.serving.kv_cache import PagedKVCache

# families whose prompt forward needs an extra-embeddings frontend the
# engine does not stub (submit() has no modality input)
_NEEDS_EXTRA = ("vlm", "encdec")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The one public serving configuration.

    slots: decode-batch width (concurrent in-flight requests).
    max_len: KV cache entries per slot; every request must satisfy
        ``prompt_len + max_new_tokens <= max_len``.
    page_size: KV page granularity (tokens); ``max_len`` must divide
        into whole pages.
    prefill_batch: max requests admitted in one batched prefill.
    sampling: :class:`repro.serving.SamplingParams` (default greedy).
    use_kernel: route decode attention through the fused Pallas
        kernel (``kernels.attention_decode``: KV ring append +
        mask-from-pos + online-softmax GQA, one launch per layer).
    cache_dtype: KV pool storage dtype override (e.g. "bfloat16" to
        halve pool bytes; decode accumulates in f32 either way).
    """
    slots: int = 8
    max_len: int = 256
    page_size: int = 16
    prefill_batch: int = 4
    sampling: sampling.SamplingParams = dataclasses.field(
        default_factory=sampling.SamplingParams)
    use_kernel: bool = False
    cache_dtype: Optional[str] = None

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        if self.cache_dtype is not None:
            try:
                jnp.dtype(self.cache_dtype)
            except TypeError as e:
                raise ValueError(
                    f"cache_dtype {self.cache_dtype!r} is not a "
                    f"dtype") from e
        if self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}")
        if self.max_len < 1 or self.max_len % self.page_size:
            raise ValueError(
                f"max_len ({self.max_len}) must be a positive multiple "
                f"of page_size ({self.page_size})")
        if self.prefill_batch < 1:
            raise ValueError(
                f"prefill_batch must be >= 1, got {self.prefill_batch}")


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray                 # [S] int32
    max_new_tokens: int
    submitted: float                   # perf_counter
    tokens: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class RequestResult:
    id: int
    prompt: np.ndarray
    tokens: list                       # generated ids (ints)
    prompt_len: int
    finished: bool                     # False = evicted mid-flight
    submitted: float
    completed: float

    @property
    def latency_s(self) -> float:
        return self.completed - self.submitted


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


class Engine:
    """``submit`` / ``step`` / ``drain`` — the whole public surface.

    ``submit`` enqueues a request and returns its id; ``step`` runs one
    scheduler iteration (admit waiting requests into free slots via
    batched prefill, then one decode step over the full slot batch) and
    returns the requests that finished during it; ``drain`` steps until
    the engine is empty and returns every finished result.
    """

    def __init__(self, model, params, config: ServeConfig, *,
                 extra=None, tracer=None):
        if model.prefill is None:
            raise ValueError(
                f"family {model.cfg.family!r} has no batched-prefill "
                f"lowering; the serving engine requires model.prefill "
                f"(supported: dense / moe / gemma3-style windowed)")
        if model.cfg.family in _NEEDS_EXTRA and extra is None:
            raise ValueError(
                f"family {model.cfg.family!r} needs an extra-embeddings "
                f"frontend; pass extra= (one [slots, ...] block) or "
                f"serve a text-only family")
        if config.use_kernel or config.cache_dtype:
            # rebuild on a cfg carrying the serving overrides (params
            # are flag-independent, so the caller's tree is reused)
            from repro.models import get_model
            model = get_model(model.cfg.replace(
                use_decode_kernel=config.use_kernel
                or model.cfg.use_decode_kernel,
                kv_cache_dtype=config.cache_dtype
                or model.cfg.kv_cache_dtype))
        self.tracer = trace.NULL if tracer is None else tracer
        self.model = model
        self.params = params
        self.config = config
        self._extra = extra
        # an admission batch can never exceed the free slots, and the
        # pow2 padding must stay within the extra-embeds rows
        self._prefill_cap = min(config.prefill_batch, config.slots)
        self._kv = PagedKVCache(model, params, config, extra)
        # the decode kernel's K/V blocks: (layers, T, block_t) per
        # self-attention pool [G, B, T, Hkv·Dh]; none on the jnp path
        pools = jax.tree_util.tree_flatten_with_path(self._kv.cache)[0]
        self._kv_plan = [
            (k.shape[0], k.shape[2],
             attention_decode.block_len(k.shape[2], k.shape[3], k.dtype))
            for path, k in pools if getattr(path[-1], "key", None) == "k"
        ] if model.cfg.use_decode_kernel else []
        self._kv_pool_step = sum(g * config.slots * (t // bt)
                                 for g, t, bt in self._kv_plan)
        self._kv_blocks_read = self._kv_blocks_pool = 0
        self._pos = np.zeros(config.slots, np.int32)
        self._tok = np.zeros(config.slots, np.int32)
        self._active: list = [None] * config.slots
        self._free = list(range(config.slots - 1, -1, -1))
        self._waiting: collections.deque = collections.deque()
        self._results: dict[int, RequestResult] = {}
        self._next_id = 0
        self._tick = 0
        self._steps = 0
        self._tokens_generated = 0
        self._key = jax.random.PRNGKey(config.sampling.seed)
        self._sampler = sampling.make_sampler(config.sampling)
        # donation keeps the [slots, max_len] pool memory-neutral on
        # accelerators; CPU XLA cannot reuse donated buffers and warns
        donate = (1,) if jax.default_backend() in ("tpu", "gpu") else ()
        self._decode = jax.jit(self._decode_fn, donate_argnums=donate)
        self._prefill_fns: dict[tuple[int, int], object] = {}

    # -- construction -----------------------------------------------------

    @classmethod
    def from_checkpoint(cls, path: str, model, config: ServeConfig, *,
                        mesh=None, shardings=None, extra=None,
                        tracer=None) -> "Engine":
        """Build an engine from a trained checkpoint of the param tree.

        Restores through the sharding-aware reader: the payload is
        mesh-independent, ``mesh=`` replicates every leaf over the
        target mesh (one engine spanning a data/model mesh),
        ``shardings=`` takes explicit placements."""
        from repro import checkpoint
        template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = checkpoint.restore(path, template, mesh=mesh,
                                    shardings=shardings)
        return cls(model, params, config, extra=extra, tracer=tracer)

    # -- jitted computations ----------------------------------------------

    def _decode_fn(self, params, cache, tokens, pos, key):
        logits, cache = self.model.decode_step(params, cache, tokens,
                                               pos)
        nxt = self._sampler(logits[:, -1], key)
        return nxt, cache

    def _prefill_fn(self, params, tokens, lens, key):
        extra = None if self._extra is None \
            else self._extra[: tokens.shape[0]]
        logits, cache = self.model.prefill(params, tokens,
                                           self.config.max_len,
                                           extra, lens)
        last = logits[jnp.arange(tokens.shape[0]), lens - 1]
        return self._sampler(last, key), cache

    def _prefill_for(self, nb: int, lb: int):
        fn = self._prefill_fns.get((nb, lb))
        if fn is None:
            fn = jax.jit(self._prefill_fn)
            self._prefill_fns[(nb, lb)] = fn
        return fn

    def _fold_key(self):
        self._tick += 1
        return jax.random.fold_in(self._key, self._tick)

    # -- public API -------------------------------------------------------

    def submit(self, prompt: Union[Sequence[int], np.ndarray], *,
               max_new_tokens: int = 16) -> int:
        """Enqueue one request; returns its id (admission happens at
        the next ``step``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens > self.config.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len "
                f"{self.config.max_len}")
        rid = self._next_id
        self._next_id += 1
        self._waiting.append(Request(rid, prompt, max_new_tokens,
                                     time.perf_counter()))
        return rid

    def step(self) -> list[RequestResult]:
        """One scheduler iteration: admit -> decode -> finish.

        Each phase records a trace-v1 span (``admit`` wraps the
        scheduler move incl. the ``prefill`` device call inside it;
        ``decode`` is the async dispatch, ``sample`` the device sync
        that materializes the sampled tokens, ``finish`` the host
        bookkeeping) — ``launch/serve.py --trace-out`` exports them
        through the run-wide trace tooling. ``decode`` carries
        ``kv_blocks``: the K/V blocks the decode kernel reads in the
        step, over every layer and slot (``stats()`` keeps the running
        totals)."""
        tr = self.tracer
        with tr.span("admit", step=self._steps,
                     waiting=len(self._waiting)):
            finished = self._admit()
        if any(r is not None for r in self._active):
            tok = jnp.asarray(self._tok[:, None])
            pos = jnp.asarray(self._pos)
            kv_blocks = sum(
                g * int(attention_decode.live_blocks(self._pos, t, bt).sum())
                for g, t, bt in self._kv_plan)
            self._kv_blocks_read += kv_blocks
            self._kv_blocks_pool += self._kv_pool_step
            with tr.span("decode", step=self._steps,
                         active=self.active_count, kv_blocks=kv_blocks):
                nxt, self._kv.cache = self._decode(
                    self.params, self._kv.cache, tok, pos,
                    self._fold_key())
            with tr.span("sample", step=self._steps):
                nxt = np.asarray(nxt)
            with tr.span("finish", step=self._steps):
                for s, req in enumerate(self._active):
                    if req is None:
                        continue
                    req.tokens.append(int(nxt[s]))
                    self._tok[s] = nxt[s]
                    self._pos[s] += 1
                    self._tokens_generated += 1
                    self._kv.table.ensure(s, int(self._pos[s]) + 1)
                    if len(req.tokens) >= req.max_new_tokens:
                        finished.append(self._finish(s, done=True))
        self._steps += 1
        return finished

    def drain(self) -> list[RequestResult]:
        """Step until no request is waiting or in flight; returns every
        result that finished during the drain."""
        budget = 64 + sum(r.max_new_tokens for r in self._waiting) \
            + sum(r.max_new_tokens for r in self._active
                  if r is not None)
        out: list[RequestResult] = []
        while self._waiting or any(r is not None for r in self._active):
            out.extend(self.step())
            budget -= 1
            if budget < 0:
                raise RuntimeError(
                    "drain did not converge — scheduler bug (a step "
                    "must either admit or generate)")
        return out

    def evict(self, request_id: int) -> RequestResult:
        """Abort an in-flight (or waiting) request, freeing its slot
        and pages; the partial result is marked unfinished."""
        for s, req in enumerate(self._active):
            if req is not None and req.id == request_id:
                return self._finish(s, done=False)
        for req in list(self._waiting):
            if req.id == request_id:
                self._waiting.remove(req)
                res = RequestResult(req.id, req.prompt, req.tokens,
                                    int(req.prompt.size), False,
                                    req.submitted, time.perf_counter())
                self._results[req.id] = res
                return res
        raise KeyError(f"no waiting or in-flight request {request_id}")

    def result(self, request_id: int) -> RequestResult:
        return self._results[request_id]

    # -- scheduler internals ----------------------------------------------

    def _admit(self) -> list[RequestResult]:
        """Move waiting requests into free slots through ONE batched
        prefill (padded to pow2 count/length buckets)."""
        batch: list[tuple[Request, int]] = []
        while self._waiting and self._free \
                and len(batch) < self._prefill_cap:
            batch.append((self._waiting.popleft(), self._free.pop()))
        if not batch:
            return []
        nb = min(_next_pow2(len(batch)), self._prefill_cap)
        nb = max(nb, len(batch))
        max_prompt = max(r.prompt.size for r, _ in batch)
        lb = min(max(_next_pow2(max_prompt), self.config.page_size),
                 self.config.max_len)
        lb = max(lb, max_prompt)
        tokens = np.zeros((nb, lb), np.int32)
        lens = np.ones(nb, np.int32)
        for i, (req, _) in enumerate(batch):
            tokens[i, :req.prompt.size] = req.prompt
            lens[i] = req.prompt.size
        with self.tracer.span("prefill", step=self._steps, batch=nb,
                              length=lb):
            first, pf_cache = self._prefill_for(nb, lb)(
                self.params, jnp.asarray(tokens), jnp.asarray(lens),
                self._fold_key())
            first = np.asarray(first)
        finished = []
        for i, (req, slot) in enumerate(batch):
            self._kv.insert(pf_cache, i, slot)
            self._kv.table.ensure(slot, int(req.prompt.size) + 1)
            self._pos[slot] = req.prompt.size
            self._tok[slot] = first[i]
            req.tokens.append(int(first[i]))
            self._tokens_generated += 1
            self._active[slot] = req
            if len(req.tokens) >= req.max_new_tokens:
                finished.append(self._finish(slot, done=True))
        return finished

    def _finish(self, slot: int, *, done: bool) -> RequestResult:
        req = self._active[slot]
        self._active[slot] = None
        self._free.append(slot)
        self._kv.table.release(slot)
        self._pos[slot] = 0
        self._tok[slot] = 0
        res = RequestResult(req.id, req.prompt, req.tokens,
                            int(req.prompt.size), done, req.submitted,
                            time.perf_counter())
        self._results[req.id] = res
        return res

    # -- introspection ----------------------------------------------------

    @property
    def active_count(self) -> int:
        return sum(r is not None for r in self._active)

    @property
    def queue_depth(self) -> int:
        return len(self._waiting)

    @property
    def decode_compilations(self) -> int:
        """Compiled decode-step variants — the serving compile-once
        invariant says this stays at 1 across every admit/evict/finish
        occupancy transition."""
        return self._decode._cache_size()

    @property
    def prefill_compilations(self) -> int:
        """Compiled prefill variants (bounded by the pow2 count/length
        bucket grid, NOT by traffic)."""
        return sum(f._cache_size() for f in self._prefill_fns.values())

    def stats(self) -> dict:
        return {"steps": self._steps,
                "tokens_generated": self._tokens_generated,
                "active": self.active_count,
                "waiting": self.queue_depth,
                "decode_compilations": self.decode_compilations,
                "prefill_compilations": self.prefill_compilations,
                "decode_kv_blocks_read": self._kv_blocks_read,
                "decode_kv_blocks_pool": self._kv_blocks_pool,
                **self._kv.table.stats()}
