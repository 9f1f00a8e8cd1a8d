"""Fused LARS/TVLARS parameter-update Pallas TPU kernel — PER-TENSOR path.

Dispatch story: this kernel is ``use_kernel="per_tensor"`` in
``repro.core.layerwise`` — two ``pallas_call``s per >=2-D leaf, heavy
ball only. It wins over pure XLA for a handful of large tensors, but a
ResNet/transformer with hundreds of small leaves becomes launch-bound
and tile-underfilled; the segmented substrate path
(``use_kernel="fused"``, ``repro.kernels.segmented_update``) packs the
whole tree into one lane-padded buffer and does the entire step — every
leaf, every momentum style, LAMB included — in two ``pallas_call``s
total. Prefer "fused"; this file stays as the simplest kernel reference
and as a bisection point for substrate bugs.

The optimizer inner loop is memory-bound: per parameter tensor it reads
(w, g, m) and writes (m', w') — a pure streaming workload. Unfused, XLA
materialises the scaled gradient and momentum separately (≥7 HBM passes
per tensor). The fused kernel does it in two passes:

  pass 1  ``_norm2_kernel``   — tiled Σw², Σg² reduction (VMEM tiles,
                                sequential-grid accumulation into SMEM
                                scalars; f32 accumulators),
  host    trust ratio         — η‖w‖/(‖g‖+wd‖w‖+eps), a scalar,
  pass 2  ``_apply_kernel``   — fused elementwise
                                scaled = lr·ratio·(g + wd·w)
                                m'     = μ·m + scaled
                                Δ      = −(scaled + μ·m')  (nesterov)
                                       | −m'               (heavy ball)

TPU adaptation (vs. the CUDA elementwise-kernel norm): tiles are
(BLOCK_ROWS, 128) — lane-dim 128 to match the VPU/VREG layout, row
count chosen so all live operands fit a ~1 MiB VMEM budget. Tensors of
any rank are flattened and zero-padded to a lane multiple; zero padding
is exact for both the norm (adds 0) and the elementwise pass (sliced
off).

Scalars (lr·ratio already folded) are passed as a (1, 1) SMEM operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 512          # (512, 128) f32 tile = 256 KiB per operand


def _pad_to_tiles(x: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    """Flatten to (rows, LANES) with zero padding; returns (arr, n_valid)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // LANES)
    rows_padded = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    padded = jnp.zeros((rows_padded * LANES,), flat.dtype).at[:n].set(flat)
    return padded.reshape(rows_padded, LANES), n


def _norm2_kernel(w_ref, g_ref, w2_ref, g2_ref):
    """Grid-sequential accumulation of Σw², Σg² into (1,1) SMEM scalars."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        w2_ref[0, 0] = 0.0
        g2_ref[0, 0] = 0.0

    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w2_ref[0, 0] += jnp.sum(w * w)
    g2_ref[0, 0] += jnp.sum(g * g)


def _apply_kernel(scale_ref, w_ref, g_ref, m_ref, new_m_ref, delta_ref, *,
                  weight_decay: float, momentum_mu: float, nesterov: bool):
    scale = scale_ref[0, 0]           # = base_lr * trust_ratio
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    scaled = scale * (g + weight_decay * w)
    new_m = momentum_mu * m + scaled
    if nesterov:
        delta = -(scaled + momentum_mu * new_m)
    else:
        delta = -new_m
    new_m_ref[...] = new_m
    delta_ref[...] = delta


def _norms_sq(w2d: jnp.ndarray, g2d: jnp.ndarray, *, interpret: bool
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    rows = w2d.shape[0]
    grid = (rows // BLOCK_ROWS,)
    block = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    out_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    w2, g2 = pl.pallas_call(
        _norm2_kernel,
        grid=grid,
        in_specs=[block, block],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 2,
        interpret=interpret,
    )(w2d, g2d)
    return w2[0, 0], g2[0, 0]


def lars_update_pallas(w: jnp.ndarray, g: jnp.ndarray, m: jnp.ndarray, *,
                       base_lr, eta: float, weight_decay: float,
                       momentum_mu: float, eps: float = 1e-9,
                       nesterov: bool = False, interpret: bool):
    """Fused LARS step. Returns (new_momentum, delta), f32, shape of w."""
    orig_shape = w.shape
    w2d, n = _pad_to_tiles(w.astype(jnp.float32))
    g2d, _ = _pad_to_tiles(g.astype(jnp.float32))
    m2d, _ = _pad_to_tiles(m.astype(jnp.float32))

    w2, g2 = _norms_sq(w2d, g2d, interpret=interpret)
    w_norm = jnp.sqrt(w2)
    g_norm = jnp.sqrt(g2)
    ratio = jnp.where((w_norm > 0.0) & (g_norm > 0.0),
                      eta * w_norm / (g_norm + weight_decay * w_norm + eps),
                      1.0)
    scale = (jnp.asarray(base_lr, jnp.float32) * ratio).reshape(1, 1)

    rows = w2d.shape[0]
    grid = (rows // BLOCK_ROWS,)
    block = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_apply_kernel, weight_decay=weight_decay,
                               momentum_mu=momentum_mu, nesterov=nesterov)
    new_m2d, delta2d = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[scalar_spec, block, block, block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct(w2d.shape, jnp.float32)] * 2,
        interpret=interpret,
    )(scale, w2d, g2d, m2d)

    new_m = new_m2d.reshape(-1)[:n].reshape(orig_shape)
    delta = delta2d.reshape(-1)[:n].reshape(orig_shape)
    return new_m, delta
