"""Fused attention-decode step (the serving hot path) as ONE kernel.

Per layer per decode step the jnp path
(``repro.models.layers.attention_decode``) issues, for every slot row:
a KV-cache row write, a materialized ``[slots, max_len]`` additive
mask, an f32 scores tensor, a softmax, and two GQA contractions — the
KV pool streams through HBM several times per token plus the
scores/probs round-trips. This kernel fuses the whole step:

  (a) the per-row KV append at ``slot = pos % T`` (vector-``pos``
      ring-buffer semantics identical to ``attention_decode``: ``T``
      is the cache length, ``min(window, max_len)`` for windowed
      layers),
  (b) on-the-fly mask generation from ``pos`` (the causal / windowed
      ring-validity predicate is evaluated per KV block in registers —
      no ``[slots, max_len]`` tensor ever exists), and
  (c) the grouped-query attention contraction with f32 accumulation
      and an online (flash-decoding) softmax, blocked over ``max_len``
      so each KV element is read from HBM exactly once.

The grid is ``(slots, max_len // block_t)`` over the engine's FIXED
``[slots, max_len]`` pool — ``pos`` rides in SMEM as a traced ``[B]``
vector, so occupancy changes never retrace and
``Engine.decode_compilations == 1`` holds with the kernel enabled.
The caches are input/output aliased (the append is in-place on
accelerators, matching the engine's donated pool).

Numerics: scores, softmax and the probs·V accumulation run strictly in
f32 regardless of the cache storage dtype (bf16 caches are upcast on
read, exactly like the oracle and the fixed jnp path) — see
``kernels.ref.decode_parity_tolerance`` for the documented bound.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38  # f32-safe mask value (matches models.layers)

# KV block length: bounds VMEM at [block_t, Hkv, Dh] per operand while
# keeping the grid short. 128 keeps the sublane dim MXU-aligned.
MAX_BLOCK_T = 128


def _block_len(t: int) -> int:
    """Largest divisor of ``t`` that is <= MAX_BLOCK_T (cache lengths
    are page-size multiples in serving, so this is normally t itself or
    a power of two)."""
    if t <= MAX_BLOCK_T:
        return t
    for bt in range(MAX_BLOCK_T, 0, -1):
        if t % bt == 0:
            return bt
    return 1


def _decode_kernel(pos_ref, q_ref, nk_ref, nv_ref, kc_ref, vc_ref,
                   ko_ref, vo_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   block_t: int, t: int, window: Optional[int],
                   hkv: int, grp: int, dh: int, scale: float):
    i = pl.program_id(0)                  # slot row
    j = pl.program_id(1)                  # KV block along max_len
    nt = pl.num_programs(1)
    pos = pos_ref[i, 0]
    slot = pos % t if window is not None else pos

    # (a) ring append: copy the tile through; the block owning the
    # write slot overwrites that one row with the new K/V.
    ko_ref[...] = kc_ref[...]
    vo_ref[...] = vc_ref[...]
    local = slot - j * block_t

    @pl.when((local >= 0) & (local < block_t))
    def _append():
        ko_ref[0, pl.ds(local, 1)] = nk_ref[...]
        vo_ref[0, pl.ds(local, 1)] = nv_ref[...]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (c) scores for this KV block, f32 accumulation on the MXU. The
    # appended row is attended through the freshly written output tile.
    k = ko_ref[0].astype(jnp.float32)                 # [bt, Hkv, Dh]
    v = vo_ref[0].astype(jnp.float32)
    q = q_ref[0].astype(jnp.float32).reshape(hkv, grp, dh)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale   # [Hkv, grp, bt]

    # (b) validity from pos alone — no materialized mask. Ring slot q
    # holds absolute position q + wraps (q <= slot) or q + wraps - t
    # (not yet overwritten this lap); valid iff in (pos-window, pos].
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_t), 2) \
        + j * block_t
    if window is not None:
        wraps = (pos // t) * t
        abs_pos = kpos + jnp.where(kpos <= slot, wraps, wraps - t)
        ok = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - window)
    else:
        ok = kpos <= pos
    s = jnp.where(ok, s, NEG_INF)

    # online softmax across KV blocks (scratch carries m/l/acc per row)
    m_prev = m_ref[...]                               # [Hkv, grp]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)           # [Hkv, grp, Dh]
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
    m_ref[...] = m_new

    @pl.when(j == nt - 1)
    def _finish():
        out = acc_ref[...] / l_ref[...][..., None]
        o_ref[...] = out.reshape(1, hkv * grp, dh).astype(o_ref.dtype)


def attention_decode_pallas(q, new_k, new_v, k_cache, v_cache, pos, *,
                            window: Optional[int] = None,
                            interpret: bool):
    """Fused decode attention. q: [B,1,H,Dh] (rope'd); new_k/new_v:
    [B,1,Hkv,Dh] (rope'd); caches: [B,T,Hkv,Dh]; pos: [B] int32
    per-row depths. Returns (out [B,1,H,Dh], new_k_cache, new_v_cache)
    — semantics identical to ``layers.attention_decode``'s cache write
    + mask + ``gqa_scores_apply`` at vector ``pos``.
    """
    b, s, h, dh = q.shape
    assert s == 1, "decode kernel is single-token"
    t, hkv = k_cache.shape[1], k_cache.shape[2]
    grp = h // hkv
    block_t = _block_len(t)
    kernel = functools.partial(
        _decode_kernel, block_t=block_t, t=t, window=window,
        hkv=hkv, grp=grp, dh=dh, scale=1.0 / math.sqrt(dh))
    cache_spec = pl.BlockSpec((1, block_t, hkv, dh),
                              lambda i, j: (i, j, 0, 0))
    q_spec = pl.BlockSpec((1, h, dh), lambda i, j: (i, 0, 0))
    kv_spec = pl.BlockSpec((1, hkv, dh), lambda i, j: (i, 0, 0))
    ko, vo, out = pl.pallas_call(
        kernel,
        grid=(b, t // block_t),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # pos [B,1]
                  q_spec, kv_spec, kv_spec, cache_spec, cache_spec],
        out_specs=[cache_spec, cache_spec, q_spec],
        out_shape=[jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
                   jax.ShapeDtypeStruct((b, h, dh), q.dtype)],
        # append in-place on the engine's donated [slots, max_len] pool
        input_output_aliases={4: 0, 5: 1},
        scratch_shapes=[pltpu.VMEM((hkv, grp), jnp.float32),
                        pltpu.VMEM((hkv, grp), jnp.float32),
                        pltpu.VMEM((hkv, grp, dh), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(b, 1), q[:, 0],
      new_k[:, 0].astype(k_cache.dtype), new_v[:, 0].astype(v_cache.dtype),
      k_cache, v_cache)
    return out[:, None], ko, vo


def modeled_decode_hbm_bytes(cfg, max_len: int) -> dict:
    """Analytic HBM traffic per decode token per slot row for one full
    model step (sum over layers), fused kernel vs the jnp path — the
    same style of model as ``segmented_update.modeled_hbm_bytes``.

    Both paths must stream the KV pool once ([T, Hkv, Dh] ×2) and write
    one row. The jnp path additionally round-trips the materialized
    additive mask ([T] f32 write+read) and the f32 scores and probs
    tensors ([H, T] each, write+read) through HBM; the kernel keeps all
    three in VMEM. q/out traffic (O(H·Dh)) is counted for both.
    """
    hkv, h, dh = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim_
    csize = jnp.dtype(cfg.kv_dtype).itemsize
    asize = jnp.dtype(cfg.cdtype).itemsize
    fused = jnp_path = 0
    groups, kinds = _group_spec_kinds(cfg)
    for kind in kinds:
        t = (min(cfg.sliding_window, max_len)
             if kind == "local" and cfg.sliding_window else max_len)
        if kind == "cross":
            continue
        common = 2 * t * hkv * dh * csize \
            + 2 * hkv * dh * csize \
            + 2 * h * dh * asize          # KV stream + row write + q/out
        fused += common
        jnp_path += common + 2 * 4 * t + 2 * (2 * 4 * h * t)
    return {"fused": groups * fused, "jnp": groups * jnp_path}


def _group_spec_kinds(cfg):
    """Layer-kind structure (mirrors ``transformer._group_spec``
    without importing the models package from the kernel substrate)."""
    if cfg.family == "vlm" and cfg.cross_attn_every:
        n = cfg.cross_attn_every
        return cfg.num_layers // n, ["attn"] * n + ["cross"]
    if cfg.global_every and cfg.sliding_window:
        n = cfg.global_every
        return cfg.num_layers // n, ["local"] * (n - 1) + ["attn"]
    return cfg.num_layers, ["attn"]
