"""Fused attention-decode step (the serving hot path) as ONE kernel.

Per layer per decode step the jnp path
(``repro.models.layers.attention_decode``) materializes, for every slot
row, a ``[slots, max_len]`` additive mask, an f32 scores tensor and its
softmax, and reads the whole pool for the two GQA contractions. This
kernel reads, per slot, only the LIVE part of that slot's K/V, once, in
dense tiles:

  (a) the pool is stored with its heads merged, ``[B, T, Hkv·Dh]``
      (``T`` the cache length, ``min(window, max_len)`` for windowed
      layers), so a ``[block_t, Hkv·Dh]`` tile is dense in the chip's
      (sublane, 128-lane) tiling; each KV head reads its lane-aligned
      slice ``[:, h·Dh:(h+1)·Dh]``;
  (b) ``pos`` rides in scalar prefetch, and each slot's K/V block index
      is clamped to its last live block, ``min(pos, T-1) // block_t``:
      grid steps past it repeat the block index, so the pipeline skips
      their DMA and ``pl.when`` skips their compute. The same rule
      covers global layers and windowed rings (a ring is all live once
      it has wrapped);
  (c) the validity predicate is evaluated per block from ``pos`` in
      registers (causal, or ring-buffer windowed) — no ``[slots,
      max_len]`` mask ever exists — and the grouped contraction runs
      an online (flash-decoding) softmax over the live blocks.

The new row lands at ``slot = pos % T`` (windowed) or ``pos`` (global):
the pool is aliased with the kernel's output, whose block per slot is
the one packed sublane tile (16 bf16 rows) holding that slot, so the
append writes back that tile and nothing else of the pool. The block
read at the slot attends the new row in registers. The grid is
``(slots, T // block_t)`` over the engine's FIXED pool and ``pos`` is a
traced ``[B]`` vector, so occupancy changes never retrace
(``Engine.decode_compilations == 1``).

Numerics: scores, softmax and the probs·V accumulation run in f32.
Where q and the pool share bf16 the q·Kᵀ matmul takes them as they are
with an f32 result (the products are exact); otherwise both are upcast
to f32. See ``kernels.ref.decode_parity_tolerance`` for the bound.
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

# bytes of one [block_t, Hkv·Dh] K or V tile: four of them (K and V,
# double-buffered) stay at 1 MiB of VMEM, and a tile is long enough
# that each DMA moves a quarter MiB of live K/V
TILE_BYTES = 256 * 1024


def block_len(t: int, width: int, dtype) -> int:
    """K/V rows per tile for a ``[.., t, width]`` pool of ``dtype``: the
    largest divisor of ``t`` whose tile fits ``TILE_BYTES`` and is a
    whole number of packed sublane tiles (8 rows of 32 bits), else the
    smallest such divisor, else ``t``."""
    item = jnp.dtype(dtype).itemsize
    if t * width * item <= TILE_BYTES:
        return t
    sub = _sublane_rows(dtype)
    fits = [bt for bt in range(sub, t, sub) if t % bt == 0]
    small = [bt for bt in fits if bt * width * item <= TILE_BYTES]
    return max(small) if small else (min(fits) if fits else t)


def live_blocks(pos, t: int, block_t: int):
    """Blocks the kernel reads for a row at depth ``pos`` (a host
    array): every block up to ``_last_block``."""
    return pos.clip(0, t - 1) // block_t + 1


def _sublane_rows(dtype) -> int:
    """Rows of one packed (sublane, 128-lane) tile: 8 of 32 bits."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _last_block(pos, t: int, block_t: int):
    """The block holding the row's newest live key, ``min(pos, t-1)``:
    blocks past it are dead (a windowed ring is all live once wrapped)."""
    return jnp.minimum(pos, t - 1) // block_t


def _slot(pos, t: int, window: Optional[int]):
    """The pool row the new key lands in."""
    return pos % t if window is not None else jnp.minimum(pos, t - 1)


def _decode_kernel(pos_ref, q_ref, nk_ref, nv_ref, k_ref, v_ref, o_ref,
                   ko_ref, vo_ref, m_ref, l_ref, acc_ref, *, block_t: int,
                   row_t: int, t: int, window: Optional[int], hkv: int,
                   dh: int, scale: float):
    i = pl.program_id(0)                  # slot row
    j = pl.program_id(1)                  # KV block along T
    pos = pos_ref[i]
    last = _last_block(pos, t, block_t)
    slot = _slot(pos, t, window)
    local = slot - j * block_t            # write row within this block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((local >= 0) & (local < block_t))
    def _append():
        # the aliased output block is the row_t-row tile holding the
        # write slot: the old tile with the new row merged in
        off = pl.multiple_of(local // row_t * row_t, row_t)
        hit = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[1:], 0) \
            == local % row_t
        ko_ref[0] = jnp.where(hit, nk_ref[0], k_ref[0, pl.ds(off, row_t)])
        vo_ref[0] = jnp.where(hit, nv_ref[0], v_ref[0, pl.ds(off, row_t)])

    @pl.when(j <= last)
    def _block():
        # validity from pos alone. Ring slot q holds absolute position
        # q + wraps (q <= slot) or q + wraps - t (not yet overwritten
        # this lap); valid iff in (pos-window, pos].
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1) \
            + j * block_t
        if window is not None:
            wraps = (pos // t) * t
            abs_pos = kpos + jnp.where(kpos <= slot, wraps, wraps - t)
            ok = (abs_pos >= 0) & (abs_pos <= pos) \
                & (abs_pos > pos - window)
        else:
            ok = kpos <= pos
        new_row = jax.lax.broadcasted_iota(
            jnp.int32, (block_t, dh), 0) == local
        for h in range(hkv):
            lanes = slice(h * dh, (h + 1) * dh)
            q = q_ref[0, h]                               # [grp, Dh]
            # the block as it reads once the new row is in
            k = jnp.where(new_row, nk_ref[0, :, lanes], k_ref[0, :, lanes])
            v = jnp.where(new_row, nv_ref[0, :, lanes], v_ref[0, :, lanes])
            if not (q.dtype == k.dtype == jnp.bfloat16):
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [grp, bt]
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h]                             # [grp, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [grp, Dh]
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = m_new

    @pl.when(j == last)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def attention_decode_pallas(q, new_k, new_v, k_cache, v_cache, pos, *,
                            window: Optional[int] = None,
                            interpret: bool, _block_t=None):
    """Fused decode attention. q: [B,1,H,Dh] (rope'd); new_k/new_v:
    [B,1,Hkv,Dh] (rope'd); caches: [B,T,Hkv·Dh]; pos: [B] int32
    per-row depths. Returns (out [B,1,H,Dh], new_k_cache, new_v_cache)
    — semantics identical to ``layers.attention_decode``'s cache write
    + mask + ``gqa_scores_apply`` at vector ``pos``. ``_block_t``
    overrides ``block_len`` (tests, to cover many blocks at small T).
    """
    b, s, h, dh = q.shape
    assert s == 1, "decode kernel is single-token"
    hkv = new_k.shape[2]
    t, width = k_cache.shape[1], k_cache.shape[2]
    assert width == hkv * dh, (k_cache.shape, new_k.shape)
    grp = h // hkv
    block_t = _block_t or block_len(t, width, k_cache.dtype)
    # rows of the tile the append writes back: one packed sublane tile
    row_t = math.gcd(block_t, _sublane_rows(k_cache.dtype))

    def kv_block(i, j, pos_ref):      # dead steps repeat the last block
        return (i, jnp.minimum(j, _last_block(pos_ref[i], t, block_t)), 0)

    def row_tile(i, j, pos_ref):
        return (i, _slot(pos_ref[i], t, window) // row_t, 0)

    head_spec = pl.BlockSpec((1, hkv, grp, dh),
                             lambda i, j, p: (i, 0, 0, 0))
    new_spec = pl.BlockSpec((1, 1, width), lambda i, j, p: (i, 0, 0))
    kv_spec = pl.BlockSpec((1, block_t, width), kv_block)
    row_spec = pl.BlockSpec((1, row_t, width), row_tile)
    kernel = functools.partial(
        _decode_kernel, block_t=block_t, row_t=row_t, t=t, window=window,
        hkv=hkv, dh=dh, scale=1.0 / math.sqrt(dh))
    out, ko, vo = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // block_t),
            in_specs=[head_spec, new_spec, new_spec, kv_spec, kv_spec],
            out_specs=[head_spec, row_spec, row_spec],
            scratch_shapes=[pltpu.VMEM((hkv, grp, 1), jnp.float32),
                            pltpu.VMEM((hkv, grp, 1), jnp.float32),
                            pltpu.VMEM((hkv, grp, dh), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, grp, dh), q.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # the append writes one tile per slot into the pool in place
        # (operand indices count the scalar-prefetch pos as 0)
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32), q.reshape(b, hkv, grp, dh),
      new_k.reshape(b, 1, width).astype(k_cache.dtype),
      new_v.reshape(b, 1, width).astype(v_cache.dtype), k_cache, v_cache)
    return out.reshape(b, 1, h, dh), ko, vo
