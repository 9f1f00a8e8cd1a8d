"""Shared transformer building blocks (pure JAX, dict-pytree params).

Conventions
-----------
* All ``init_*`` functions return nested dicts of arrays; repeated layers
  are stacked on a leading axis by the callers and consumed with
  ``jax.lax.scan`` (compact HLO, essential for 80-layer dry-runs).
* Activations flow in ``cfg.cdtype`` (bf16 on TPU); norms/softmax/rope
  compute in f32.
* Attention is grouped-query: K/V stay at ``num_kv_heads``; Q is reshaped
  to (kv_head, group) so the repeated K/V are never materialised.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

NEG_INF = -2.0e38  # f32-safe mask value

# ---------------------------------------------------------------------------
# activation sharding anchor.
#
# Two measured GSPMD pathologies this fixes (see EXPERIMENTS.md §Perf):
#  1. the token-embedding gather (data-sharded indices into a
#     vocab-sharded table) REPLICATES its output over the data axes,
#     silently un-sharding the batch for the entire network
#     (16× activation memory on train_4k);
#  2. the residual stream saved per scan step for the backward pass
#     ([L, B_local, S, D]) is the dominant training buffer; anchoring its
#     sequence dim on the ``model`` axis (Megatron sequence parallelism —
#     XLA inserts the per-layer all-gather/reduce-scatter around
#     attention/MLP) shrinks it by the TP degree.
#
# The launcher declares (batch_axes, seq_axis) once per trace;
# ``shard_batch_dim`` re-anchors [B, S, D] activations wherever they are
# (re)created. No-op when unset (CPU tests, single-device runs).
# ---------------------------------------------------------------------------
_ACT_SHARDING: tuple = (None, None)   # (batch_axes, seq_axis)
_MODEL_AXIS_SIZE: int = 1
_MESH = None                          # jax Mesh for shard_map paths


def set_batch_sharding(batch_axes: Optional[tuple],
                       seq_axis: Optional[str] = None,
                       model_size: int = 1, mesh=None) -> None:
    """batch_axes: e.g. ("data",) / ("pod","data") / None to disable.
    seq_axis: e.g. "model" for sequence-parallel residuals."""
    global _ACT_SHARDING, _MODEL_AXIS_SIZE, _MESH
    _ACT_SHARDING = (batch_axes, seq_axis)
    _MODEL_AXIS_SIZE = model_size
    _MESH = mesh


def shard_batch_dim(x: jnp.ndarray) -> jnp.ndarray:
    batch_axes, seq_axis = _ACT_SHARDING
    if batch_axes is None and seq_axis is None:
        return x
    from jax.sharding import PartitionSpec as P
    dims: list = [batch_axes] + [None] * (x.ndim - 1)
    if x.ndim == 3 and seq_axis is not None and x.shape[1] > 1:
        dims[1] = seq_axis
    return jax.lax.with_sharding_constraint(x, P(*dims))


def shard_seq_q(q: jnp.ndarray) -> jnp.ndarray:
    """Context-parallel attention: shard the QUERY sequence dim over the
    model axis (k/v get all-gathered by GSPMD). The [B,H,S,T] scores
    tensor then shards S-ways instead of (H/TP)-ways — a 4× win whenever
    H < TP·4 (e.g. qwen2-72b: 64 heads / 16 TP = 4/dev, vs S/16 = 256
    rows/dev). q: [B, S, H, Dh]."""
    batch_axes, seq_axis = _ACT_SHARDING
    if seq_axis is None or q.ndim != 4 or q.shape[1] == 1:
        return q
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        q, P(batch_axes, seq_axis, None, None))


# --------------------------------------------------------------------------
# initialisers
# --------------------------------------------------------------------------

def normal_init(key, shape, dtype, scale: float = 0.02):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def zeros_init(_key, shape, dtype, scale: float = 0.0):
    del scale
    return jnp.zeros(shape, dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.zeros((d,), dtype)}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    # statistics via f32-ACCUMULATING einsum, never materialising an f32
    # copy of x: XLA saves the hoisted convert(x)->f32 alongside the
    # bf16 residual stack in the training scan (measured +10 GiB/dev on
    # qwen2-72b train_4k). Numerics: products accumulate in f32; the
    # normalised activations stay in the compute dtype (MaxText-style).
    ss = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)[..., None]
    var = ss / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps)            # f32, [..., 1] — tiny
    y = x * inv.astype(x.dtype)               # full-size tensors stay bf16
    return y * (1.0 + params["scale"]).astype(x.dtype)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    # same no-f32-materialisation trick as rmsnorm (see comment there)
    d = x.shape[-1]
    ones = jnp.ones((d,), x.dtype)
    mu = (jnp.einsum("...d,d->...", x, ones,
                     preferred_element_type=jnp.float32) / d)[..., None]
    ss = (jnp.einsum("...d,...d->...", x, x,
                     preferred_element_type=jnp.float32) / d)[..., None]
    var = jnp.maximum(ss - jnp.square(mu), 0.0)
    inv = jax.lax.rsqrt(var + eps)
    y = (x - mu.astype(x.dtype)) * inv.astype(x.dtype)
    y = y * params["scale"].astype(x.dtype) + params["bias"].astype(x.dtype)
    return y.astype(x.dtype)


def init_norm(cfg: ModelConfig, d: int) -> dict:
    if cfg.norm == "layernorm":
        return init_layernorm(d, cfg.pdtype)
    return init_rmsnorm(d, cfg.pdtype)


def norm(cfg: ModelConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    if "bias" in params:
        return layernorm(params, x, cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


# --------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0) -> jnp.ndarray:
    """x: [B, S, H, Dh]; positions: [B, S] (int). f32 math, x-dtype out."""
    dh = x.shape[-1]
    half = dh // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                   / half)                                   # [half]
    angles = positions[..., None].astype(jnp.float32) * freq  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention (GQA, optional sliding window / cross)
# --------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, *, d_model: Optional[int] = None
                   ) -> dict:
    d = d_model or cfg.d_model
    hd, h, hkv = cfg.head_dim_, cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": normal_init(ks[0], (d, h, hd), cfg.pdtype),
        "wk": normal_init(ks[1], (d, hkv, hd), cfg.pdtype),
        "wv": normal_init(ks[2], (d, hkv, hd), cfg.pdtype),
        "wo": normal_init(ks[3], (h, hd, d), cfg.pdtype,
                          scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), cfg.pdtype)
        p["bk"] = jnp.zeros((hkv, hd), cfg.pdtype)
        p["bv"] = jnp.zeros((hkv, hd), cfg.pdtype)
    return p


def _qkv(params: dict, x: jnp.ndarray, kv_src: jnp.ndarray, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", kv_src, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", kv_src, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    return q, k, v


# query-chunk size: bounds the live scores buffer to [B, H, Q_CHUNK, T]
# instead of [B, H, S, T] (8.6 GiB/dev at 32k prefill; the f32 softmax
# backward buffers were ~12 GiB/dev on qwen2-72b train_4k). The chunk
# body is checkpointed so the backward holds ONE chunk's f32 scores.
Q_CHUNK = 512
# see the refuted-hypothesis note at the kv_span computation below
WINDOWED_KV_SLICING = False


def gqa_scores_apply(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """q: [B,S,H,Dh], k/v: [B,T,Hkv,Dh], mask: broadcastable to
    [B,1,S,T] additive. Returns [B,S,H,Dh].

    K/V are broadcast to the full H heads before the scores einsum so the
    dominant [B,H,S,T] scores tensor carries the *merged* head dim — this
    is what lets GSPMD shard it over the ``model`` axis (the grouped
    (kv, grp) factorisation leaves both factors smaller than the axis,
    forcing replicated scores — measured 13× memory blow-up on
    qwen2.5-3b train_4k). The broadcast K/V is an O(S·H·Dh) view, tiny
    next to O(S²·H) scores.
    """
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    t = k.shape[1]

    if s == 1:
        # decode path: GROUPED einsum, never broadcasting K/V to full
        # heads — the broadcast of a sequence-sharded KV cache forces an
        # "involuntary full rematerialization" reshard in GSPMD
        # (measured ~20 GiB/dev of f32 cache copies on qwen2-72b
        # decode_32k). Softmax runs over the (possibly sharded) T dim as
        # partial max/sum + all-reduce.
        grp = h // hkv
        qg = q.reshape(b, 1, hkv, grp, dh)
        # scores/softmax/probs·V accumulate strictly in f32 whatever
        # the cache storage dtype (bf16 caches used to contract in
        # bf16 here) — the fused decode kernel does the same by
        # construction, so the two paths share one numerics model
        # (kernels.ref.decode_parity_tolerance).
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                            preferred_element_type=jnp.float32
                            ) / math.sqrt(dh)
        if isinstance(mask, tuple):
            raise ValueError("decode path expects an explicit mask")
        if mask is not None:
            # mask: [1,1,1,T] additive -> broadcast over (kv, grp)
            scores = scores + mask[:, :, None]
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, v,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, 1, h, dh).astype(q.dtype)

    if hkv != h:
        rep = h // hkv
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (b, k.shape[1], hkv, rep, dh)
                             ).reshape(b, k.shape[1], h, dh)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (b, v.shape[1], hkv, rep, dh)
                             ).reshape(b, v.shape[1], h, dh)

    def full(qq, mm, q_offset, kk=None, vv=None, k_start=0):
        kk = k if kk is None else kk
        vv = v if vv is None else vv
        scores = jnp.einsum("bshd,bthd->bhst", qq, kk).astype(jnp.float32)
        scores = scores / math.sqrt(dh)
        if isinstance(mm, tuple):
            # lazy causal/window mask — never materialise a [S,T] f32
            # tensor (4.3 GiB at 32k); a bool predicate for this chunk's
            # rows is built inline and fused into the masked softmax.
            _, window = mm
            qpos = q_offset + jnp.arange(qq.shape[1])[:, None]
            kpos = k_start + jnp.arange(kk.shape[1])[None, :]
            ok = kpos <= qpos
            if window is not None:
                ok = ok & (kpos > qpos - window)
            scores = jnp.where(ok[None, None], scores, NEG_INF)
        elif mm is not None:
            scores = scores + mm
        probs = jax.nn.softmax(scores, axis=-1).astype(qq.dtype)
        return jnp.einsum("bhst,bthd->bshd", probs, vv)

    if s <= Q_CHUNK or s % Q_CHUNK != 0:
        return full(q, mask, 0)

    # long-sequence path: scan over query chunks (exact, bounded memory)
    nblk = s // Q_CHUNK
    qb = q.reshape(b, nblk, Q_CHUNK, h, dh)

    # sliding-window layers see only (window + chunk) keys per q-chunk,
    # so slicing K/V instead of masking all T keys looks like a 21x win
    # (gemma3 local at 32k: 32768 -> 1536 keys/chunk). MEASURED REFUTED
    # under SPMD: dynamic_slice with a traced offset on the sharded K/V
    # forces GSPMD to all-gather them per layer (gemma3 train_4k
    # collective 20.7 -> 70.8 s/step, memory 17.2 -> 20.5 GiB). Kept
    # behind a flag (useful on unsharded/single-host runs); the sharded
    # fix would be a shard_map halo exchange (EXPERIMENTS.md §Perf c.2).
    win = mask[1] if isinstance(mask, tuple) else None
    kv_span = Q_CHUNK + win if (WINDOWED_KV_SLICING and win is not None
                                and t > Q_CHUNK + win) else None

    @jax.checkpoint
    def chunk(qi, i):
        off = i * Q_CHUNK
        mi = mask
        if mask is not None and not isinstance(mask, tuple) \
                and mask.shape[2] > 1:
            mi = jax.lax.dynamic_slice_in_dim(mask, off, Q_CHUNK, axis=2)
        if kv_span is not None:
            start = jnp.clip(off + Q_CHUNK - kv_span, 0, t - kv_span)
            kk = jax.lax.dynamic_slice_in_dim(k, start, kv_span, axis=1)
            vv = jax.lax.dynamic_slice_in_dim(v, start, kv_span, axis=1)
            return full(qi, mi, off, kk, vv, start)
        return full(qi, mi, off)

    def body(_, xs):
        qi, i = xs
        return None, chunk(qi, i)

    _, blocks = jax.lax.scan(
        body, None, (jnp.moveaxis(qb, 1, 0), jnp.arange(nblk)))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, dh)


def causal_mask(s: int, t: Optional[int] = None,
                window: Optional[int] = None,
                q_offset: int = 0) -> jnp.ndarray:
    """Additive [1,1,s,t] mask. ``q_offset`` is the absolute position of
    query 0 (for decode, offset = cache length)."""
    t = t if t is not None else s
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok = ok & (kpos > qpos - window)
    return jnp.where(ok, 0.0, NEG_INF)[None, None]


def attention(params: dict, cfg: ModelConfig, x: jnp.ndarray,
              positions: jnp.ndarray, mask: Optional[jnp.ndarray],
              kv_src: Optional[jnp.ndarray] = None,
              use_rope: bool = True,
              kv_positions: Optional[jnp.ndarray] = None,
              return_kv: bool = False):
    """Self-attention when kv_src is None, else cross-attention.

    ``return_kv=True`` additionally returns the (rope'd) K and V
    [B,T,Hkv,Dh] — the tensors ``attention_decode`` writes into its
    cache, heads merged, so a full-sequence forward can dump a
    decode-ready KV cache (the serving engine's single-shot batched
    prefill)."""
    cross = kv_src is not None
    kv_in = kv_src if cross else x
    q, k, v = _qkv(params, x, kv_in, cfg)
    if use_rope and not cross:
        q = rope(q, positions, cfg.rope_theta)
        kpos = kv_positions if kv_positions is not None else positions
        k = rope(k, kpos, cfg.rope_theta)
    q = shard_seq_q(q)
    out = gqa_scores_apply(q, k, v, mask)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if return_kv:
        return out, (k, v)
    return out


def _write_row(cache: jnp.ndarray, new: jnp.ndarray,
               slots: jnp.ndarray) -> jnp.ndarray:
    """Per-batch cache write: cache [B,T,...], new [B,1,...], slots [B]."""
    return jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(
            c, n, s, axis=0))(cache, new.astype(cache.dtype), slots)


def attention_decode(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                     k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     pos: jnp.ndarray, *, window: Optional[int] = None,
                     use_rope: bool = True,
                     use_kernel: Optional[bool] = None):
    """One-token decode. x: [B,1,D]; caches [B,T,Hkv·Dh] (heads merged,
    the layout the decode kernel reads densely); pos: scalar
    (all rows at the same depth — the training-era path) OR a [B] int32
    vector of per-row depths — the serving engine's continuous-batching
    path, where every slot of the decode batch is mid-way through a
    different request. ``pos`` is the index to write (= number of
    tokens already cached) for each row.

    For windowed layers the cache is a ring buffer of size ``window``
    (write slot = pos % window) and RoPE uses absolute positions.
    Returns (out [B,1,D], new_k_cache, new_v_cache).

    ``use_kernel`` (default ``cfg.use_decode_kernel``) routes the
    cache write + mask + contraction through the fused Pallas decode
    kernel (``repro.kernels.ops.attention_decode_fused`` — one launch
    per layer, only the live K/V read, once, f32 online softmax);
    projections and RoPE stay here so kernel and jnp paths share them
    exactly.
    """
    b = x.shape[0]
    t = k_cache.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    vec = pos.ndim == 1                   # per-row positions
    q, k, v = _qkv(params, x, x, cfg)
    posb = pos[:, None] if vec else jnp.full((b, 1), pos, jnp.int32)
    if use_rope:
        q = rope(q, posb, cfg.rope_theta)
        k = rope(k, posb, cfg.rope_theta)
    if use_kernel is None:
        use_kernel = cfg.use_decode_kernel
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        posv = pos if vec else jnp.full((b,), pos, jnp.int32)
        out, k_cache, v_cache = kernel_ops.attention_decode_fused(
            q, k, v, k_cache, v_cache, posv, window=window)
        out = jnp.einsum("bshk,hkd->bsd", out,
                         params["wo"].astype(x.dtype))
        return out, k_cache, v_cache
    slot = pos % t if window is not None else pos
    heads = (b, t) + k.shape[2:]          # [B,T,Hkv,Dh] view of the pool
    k, v = k.reshape(b, 1, -1), v.reshape(b, 1, -1)
    if vec:
        k_cache = _write_row(k_cache, k, slot)
        v_cache = _write_row(v_cache, v, slot)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), slot, axis=1)
    kpos = jnp.arange(t)
    if vec:
        kpos = kpos[None, :]              # [1,T] vs pos/slot [B,1]
        pos_c, slot_c = pos[:, None], slot[:, None]
    else:
        pos_c, slot_c = pos, slot
    if window is not None:
        # ring buffer: slot i holds absolute position i + T*floor stuff;
        # valid iff its absolute position in (pos-window, pos].
        wraps = (pos_c // t) * t
        abs_pos = kpos + jnp.where(kpos <= slot_c, wraps, wraps - t)
        ok = (abs_pos >= 0) & (abs_pos <= pos_c) \
            & (abs_pos > pos_c - window)
    else:
        ok = kpos <= pos_c
    # scalar pos: ok is [T] -> [1,1,1,T]; vector pos: [B,T] -> [B,1,1,T]
    mask = jnp.where(ok, 0.0, NEG_INF)
    mask = mask[:, None, None, :] if vec else mask[None, None, None, :]
    out = gqa_scores_apply(q, k_cache.reshape(heads).astype(q.dtype),
                           v_cache.reshape(heads).astype(q.dtype), mask)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return out, k_cache, v_cache


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    if cfg.act == "silu":
        return {"wi": normal_init(ks[0], (d, f), cfg.pdtype),
                "wg": normal_init(ks[1], (d, f), cfg.pdtype),
                "wo": normal_init(ks[2], (f, d), cfg.pdtype, out_scale)}
    return {"wi": normal_init(ks[0], (d, f), cfg.pdtype),
            "wo": normal_init(ks[2], (f, d), cfg.pdtype, out_scale)}


def mlp(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    h = x @ params["wi"].astype(x.dtype)
    if cfg.act == "silu":
        g = x @ params["wg"].astype(x.dtype)
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return h @ params["wo"].astype(x.dtype)


# --------------------------------------------------------------------------
# embeddings / head
# --------------------------------------------------------------------------

def init_embedding(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 2)
    p = {"table": normal_init(ks[0], (cfg.vocab_size, cfg.d_model),
                              cfg.pdtype)}
    if not cfg.tie_embeddings:
        p["head"] = normal_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                cfg.pdtype)
    return p


def _shard_table(table: jnp.ndarray) -> jnp.ndarray:
    """Anchor the vocab-parallel table INSIDE the traced computation.
    with_sharding_constraint is linear and self-transposing, so the same
    constraint lands on the cotangent — without it the scatter-add grad
    of the embedding gather (and the optimizer math downstream of it)
    runs fully REPLICATED (measured ~13 GiB/dev of f32 [V, D] buffers on
    qwen2-72b train_4k)."""
    batch_axes, seq_axis = _ACT_SHARDING
    if (batch_axes is None and seq_axis is None) or _MODEL_AXIS_SIZE <= 1:
        return table
    from jax.sharding import PartitionSpec as P
    if table.shape[0] % _MODEL_AXIS_SIZE == 0:
        return jax.lax.with_sharding_constraint(table, P("model", None))
    return table


def vocab_shards(vocab: int) -> int:
    """Shards the ``model`` axis splits a vocabulary of ``vocab`` entries
    into (the table's and the head's rule in ``launch/sharding.py``); 1
    when no mesh is declared."""
    if _MODEL_AXIS_SIZE > 1 and vocab % _MODEL_AXIS_SIZE == 0:
        return _MODEL_AXIS_SIZE
    return 1


def _vocab_parallel_embed(table: jnp.ndarray, tokens: jnp.ndarray
                          ) -> Optional[jnp.ndarray]:
    """Megatron-style vocab-parallel embedding via shard_map.

    GSPMD partitions the gather's transpose (a scatter-add into the
    vocab-sharded table) by REPLICATING: ~17 full [V, D] f32 buffers on
    qwen2-72b train_4k. Explicit SPMD keeps everything [V/TP, D] local:
    each model rank masks tokens outside its row range, gathers locally,
    and psums partial embeddings; the transpose is then a LOCAL
    scatter-add. Returns None when no mesh is active (CPU tests).
    """
    batch_axes, seq_axis = _ACT_SHARDING
    mesh = _MESH
    if mesh is None or "model" not in mesh.shape or mesh.shape["model"] < 2:
        return None
    if table.shape[0] % mesh.shape["model"] != 0:
        return None
    rows = table.shape[0] // mesh.shape["model"]
    from jax.sharding import PartitionSpec as P
    # tokens MUST be replicated over "model" inside the shard_map: the
    # masked-gather+psum pattern sums PARTIAL embeddings of the SAME
    # positions across vocab shards — seq-sharding tokens over model
    # would psum embeddings of different positions (silent corruption,
    # caught by the 8-device parity test). The residual anchor re-shards
    # the output to sequence-parallel right after.
    del seq_axis
    tok_spec = P(batch_axes, None)
    out_spec = P(batch_axes, None, None)

    def f(tbl, tok):
        lo = jax.lax.axis_index("model") * rows
        loc = tok - lo
        ok = (loc >= 0) & (loc < rows)
        x = jnp.take(tbl, jnp.where(ok, loc, 0), axis=0)
        x = jnp.where(ok[..., None], x, jnp.zeros((), x.dtype))
        return jax.lax.psum(x, "model")

    return jax.shard_map(f, mesh=mesh,
                         in_specs=(P("model", None), tok_spec),
                         out_specs=out_spec)(table, tokens)


def embed(params: dict, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = _vocab_parallel_embed(params["table"], tokens)
    if x is None:
        x = jnp.take(_shard_table(params["table"]), tokens, axis=0)
    x = x.astype(cfg.cdtype)
    return shard_batch_dim(x * math.sqrt(cfg.d_model))


def unembed(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        w = params["table"].astype(x.dtype).T
    else:
        w = params["head"].astype(x.dtype)
    return x @ w
