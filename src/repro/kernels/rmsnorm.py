"""Fused RMSNorm Pallas TPU kernel.

RMSNorm is the most frequent small op in every assigned architecture
(2–4 per layer). Unfused it costs three HBM passes (square-reduce,
rsqrt-mul, scale-mul); fused it is one read + one write.

Tiling: grid over row blocks; each tile is (BLOCK_ROWS, d) in VMEM with
the full feature dim resident (d ≤ 8192 → ≤ 16 MiB f32 worst case at
BLOCK_ROWS=512 is too big, so rows are chosen by a VMEM budget).
The reduction is per-row, so the feature dim must not be split —
hardware-aligned because d is a multiple of 128 for all configs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

VMEM_BUDGET_BYTES = 4 * 1024 * 1024


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + w_ref[...].astype(jnp.float32))
                  ).astype(o_ref.dtype)


def rmsnorm_pallas(x: jnp.ndarray, weight: jnp.ndarray, *,
                   eps: float = 1e-6, interpret: bool) -> jnp.ndarray:
    """x: (..., d), weight: (d,). Returns same shape/dtype as x."""
    orig_shape = x.shape
    d = orig_shape[-1]
    x2d = x.reshape(-1, d)
    rows = x2d.shape[0]

    # Pick the largest power-of-two row block fitting the VMEM budget
    # (2 live f32 buffers of (block, d)).
    block_rows = max(1, min(rows, VMEM_BUDGET_BYTES // (2 * 4 * d)))
    block_rows = 1 << (block_rows.bit_length() - 1)
    pad_rows = -(-rows // block_rows) * block_rows
    if pad_rows != rows:
        x2d = jnp.concatenate(
            [x2d, jnp.zeros((pad_rows - rows, d), x2d.dtype)], axis=0)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(pad_rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pad_rows, d), x.dtype),
        interpret=interpret,
    )(x2d, weight)
    return out[:rows].reshape(orig_shape)
