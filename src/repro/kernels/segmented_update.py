"""Segmented multi-tensor optimizer-update Pallas TPU kernels.

The per-tensor kernel (``lars_update.py``) issues two ``pallas_call``s
PER LEAF — launch-bound and tile-underfilled on models with hundreds of
small tensors. These kernels operate on the flat substrate from
``repro.core.flatten`` instead, so one optimizer step is exactly two
``pallas_call``s TOTAL, regardless of leaf count:

  pass 1  ``_seg_norm_*``   — one sweep over the (num_rows, 128) buffer
                              accumulating per-SEGMENT Σw², Σb² into a
                              (2, nseg_pad) VMEM table. Each row belongs
                              to exactly one segment (flatten.py pads
                              segments to whole rows), so the segmented
                              reduction is per-row partial sums scattered
                              by a one-hot(segment-id) matmul — an
                              MXU-friendly scatter-add.
  host    trust table       — ``ref.trust_scale_table``: per-segment
                              (sg, sw) = (lr·ratio, lr·ratio·wd), with
                              ratio forced to 1 and sw to 0 for 1-D
                              bypass segments. O(nseg) scalar work.
  pass 2  ``_seg_apply_*``  — fused elementwise update; each row GATHERS
                              its (sg, sw) from the table (same one-hot
                              matmul) and applies the mode's momentum
                              math (heavy ball / Alg. 1 "paper" /
                              LAMB's Adam moments).

Modes (static, selected by ``functools.partial``):
  * "lars"  — LARS / TVLARS(momentum_style="lars") heavy ball, optional
              nesterov;  b = g.
  * "paper" — TVLARS Algorithm 1 parameter-space momentum;  b = g.
  * "lamb"  — Adam moments recomputed in BOTH passes (elementwise-cheap,
              saves a full HBM round-trip of writing them twice);
              b = m̂/(√v̂+eps) + wd·w.

Mixed precision: operands arrive at the substrate's STORAGE dtype (f32,
or bf16 under the ``"bf16_master"`` policy) and every tile is upcast to
f32 in VMEM on read — segment norms, the trust table and the momentum
integration accumulate strictly in f32. State buffers are written back
at their own storage dtype (round-to-nearest, or ``ref.store`` with
per-element hash bits under the ``_sr`` stochastic-rounding policies)
while the weight-update delta is ALWAYS emitted f32, so the caller's
f32 master params never see storage rounding. The rounding points match
``ref.ref_segmented_update`` exactly — ``REPRO_FORCE_REF=1`` stays the
ground truth at any precision policy. Tile heights come from
``flatten.max_block_rows(dtype)``, so bf16 buffers run 1024-row tiles
under the same 256 KiB budget that gives f32 512.

Traced step-dependent scalars (LAMB bias corrections) ride in a (1, 2)
SMEM operand; the stochastic-rounding seed in a (1, 1) int32 SMEM
operand; everything else is baked in statically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flatten import LANES, max_block_rows
from repro.kernels import ref
from repro.obs import scopes


def _onehot(ids_block: jnp.ndarray, nseg_pad: int) -> jnp.ndarray:
    """(B, 1) int32 segment ids -> (B, nseg_pad) f32 one-hot."""
    cols = jax.lax.broadcasted_iota(
        jnp.int32, (ids_block.shape[0], nseg_pad), 1)
    return (ids_block == cols).astype(jnp.float32)


def _onehot_dot(a, b):
    """f32 contraction against a one-hot operand. HIGHEST pins the MXU
    to full f32: the scatter/gather must be exact, and a single bf16
    pass would round every row's partial sum (and every gathered
    trust scale) to 8 mantissa bits."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _store_state(val32, out_ref, buf: int, *, sr: bool, seed_ref,
                 block_rows: int) -> None:
    """Write an f32 state tile back at the buffer's storage dtype —
    round-to-nearest, or stochastically with the shared oracle hash
    (global element index ⇒ per-block bits equal the oracle's)."""
    bits = None
    if sr:
        idx = ref.element_index(val32.shape[0], val32.shape[1],
                                row0=pl.program_id(0) * block_rows)
        bits = ref.buf_bits(idx, seed_ref[0, 0], buf)
    out_ref[...] = ref.store(val32, out_ref.dtype, bits=bits)


# ---------------------------------------------------------------------------
# pass 1: segmented norms
# ---------------------------------------------------------------------------

def _seg_norm_lars(ids_ref, w_ref, g_ref, out_ref, *, nseg_pad: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    oh = _onehot(ids_ref[...], nseg_pad)
    rows = jnp.stack([jnp.sum(w * w, axis=1), jnp.sum(g * g, axis=1)])
    out_ref[...] += _onehot_dot(rows, oh)


def _seg_norm_lamb(ids_ref, sc_ref, w_ref, g_ref, mu_ref, nu_ref, out_ref,
                   *, nseg_pad: int, weight_decay: float, b1: float,
                   b2: float, eps: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    nu = nu_ref[...].astype(jnp.float32)
    d, _ = ref.direction("lamb", w, g, (mu, nu),
                         b1=b1, b2=b2, bc1=sc_ref[0, 0], bc2=sc_ref[0, 1],
                         eps=eps)
    b = d + weight_decay * w
    oh = _onehot(ids_ref[...], nseg_pad)
    rows = jnp.stack([jnp.sum(w * w, axis=1), jnp.sum(b * b, axis=1)])
    out_ref[...] += _onehot_dot(rows, oh)


# ---------------------------------------------------------------------------
# pass 2: gathered-scale apply
# ---------------------------------------------------------------------------

def _gather_scales(ids_ref, tab_ref, nseg_pad: int):
    """Per-row (sg, sw) via one-hot @ tableᵀ -> two (B, 1) columns."""
    oh = _onehot(ids_ref[...], nseg_pad)
    sgw = _onehot_dot(oh, tab_ref[...].T)
    return sgw[:, 0:1], sgw[:, 1:2]


def _seg_apply_lars(ids_ref, seed_ref, tab_ref, w_ref, g_ref, m_ref,
                    newm_ref, delta_ref, *, nseg_pad: int, mode: str,
                    momentum: float, nesterov: bool, sr: bool,
                    block_rows: int):
    sg, sw = _gather_scales(ids_ref, tab_ref, nseg_pad)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    scaled = sg * g + sw * w
    (new_m,), delta = ref.integrate(mode, w, (m,), scaled,
                                    momentum=momentum, nesterov=nesterov)
    _store_state(new_m, newm_ref, 0, sr=sr, seed_ref=seed_ref,
                 block_rows=block_rows)
    delta_ref[...] = delta


def _seg_apply_lamb(ids_ref, sc_ref, seed_ref, tab_ref, w_ref, g_ref,
                    mu_ref, nu_ref, newmu_ref, newnu_ref, delta_ref, *,
                    nseg_pad: int, b1: float, b2: float, eps: float,
                    sr: bool, block_rows: int):
    sg, sw = _gather_scales(ids_ref, tab_ref, nseg_pad)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    nu = nu_ref[...].astype(jnp.float32)
    d, (new_mu, new_nu) = ref.direction(
        "lamb", w, g, (mu, nu), b1=b1, b2=b2,
        bc1=sc_ref[0, 0], bc2=sc_ref[0, 1], eps=eps)
    _store_state(new_mu, newmu_ref, 0, sr=sr, seed_ref=seed_ref,
                 block_rows=block_rows)
    _store_state(new_nu, newnu_ref, 1, sr=sr, seed_ref=seed_ref,
                 block_rows=block_rows)
    delta_ref[...] = -(sg * d + sw * w)


# ---------------------------------------------------------------------------
# analytic HBM-traffic model
# ---------------------------------------------------------------------------

def modeled_hbm_bytes(mode: str, rows: int, *, itemsize: int) -> dict:
    """Per-step HBM traffic of the 2-pass segmented step, in bytes.

    ``itemsize`` is the substrate storage dtype's width (4 = f32,
    2 = bf16). Accesses per element, by operand class:

      * operands  — w and g are each READ by both passes (packed fresh
                    at the storage dtype every step): 4 accesses.
      * state     — "lars"/"paper": the single momentum buffer is read
                    by pass 2 and written once (2 accesses);
                    "lamb": both Adam moments are recomputed in BOTH
                    passes (read twice) and written once (6 accesses).
      * delta     — written once, ALWAYS f32 (master-update precision).
      * ids       — the (rows, 1) int32 segment-id column, both passes.

    The ``state`` term is what a precision policy moves: bf16 halves it
    exactly (2.0x), which is the bench's headline ratio. Returns
    ``{"state", "operand", "delta", "ids", "total"}``.
    """
    if mode not in ref.MODES:
        raise ValueError(f"unknown mode {mode!r}; one of {ref.MODES}")
    n = rows * LANES
    state_accesses = 6 if mode == "lamb" else 2
    out = {
        "state": state_accesses * n * itemsize,
        "operand": 4 * n * itemsize,
        "delta": 4 * n,
        "ids": 2 * rows * 4,
    }
    out["total"] = sum(out.values())
    return out


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------

def segmented_update_pallas(w2d, g2d, bufs, *, seg_ids, adapt_mask, base_lr,
                            mode: str, eta: float, weight_decay: float,
                            momentum: float, b1: float, b2: float,
                            eps: float, nesterov: bool = False,
                            trust_clip=None, bc1=1.0, bc2=1.0,
                            stochastic_round: bool = False, seed=0,
                            telemetry: bool = False,
                            interpret: bool):
    """Whole-tree layer-wise step: exactly two ``pallas_call``s.

    Same contract as ``ref.ref_segmented_update`` — flat ``(rows, 128)``
    buffers in (any storage dtype; norms/table/integration accumulate
    in f32), ``(new_bufs, delta2d)`` out with state buffers at their
    input dtype and ``delta2d`` in f32.  ``telemetry=True`` adds the
    per-segment ``(w_norm, g_norm, trust_ratio)`` dict third return —
    it is read off the pass-1 norm table between the two launches, so
    the 2-``pallas_call`` invariant holds with telemetry on.
    """
    if mode not in ref.MODES:
        raise ValueError(f"unknown mode {mode!r}; one of {ref.MODES}")
    rows, lanes = w2d.shape
    assert lanes == LANES, w2d.shape
    nseg = adapt_mask.shape[0]
    nseg_pad = -(-nseg // LANES) * LANES
    # mirrors flatten._build_spec_cached's padding: num_rows is either
    # < max_block_rows(storage dtype) (single grid step) or a multiple
    mbr = max_block_rows(w2d.dtype)
    block_rows = rows if rows < mbr else mbr
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)

    block = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    ids_block = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    tab_block = pl.BlockSpec((2, nseg_pad), lambda i: (0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    sc = jnp.stack([jnp.asarray(bc1, jnp.float32),
                    jnp.asarray(bc2, jnp.float32)]).reshape(1, 2)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    # ---- pass 1: per-segment Σw², Σb² ----
    if mode == "lamb":
        norm_kernel = functools.partial(
            _seg_norm_lamb, nseg_pad=nseg_pad, weight_decay=weight_decay,
            b1=b1, b2=b2, eps=eps)
        norm_in = [ids_block, smem, block, block, block, block]
        norm_args = (seg_ids, sc, w2d, g2d, bufs[0], bufs[1])
    else:
        norm_kernel = functools.partial(_seg_norm_lars, nseg_pad=nseg_pad)
        norm_in = [ids_block, block, block]
        norm_args = (seg_ids, w2d, g2d)
    with jax.named_scope(scopes.SEG_NORM):
        table2 = pl.pallas_call(
            norm_kernel,
            grid=grid,
            in_specs=norm_in,
            out_specs=tab_block,
            out_shape=jax.ShapeDtypeStruct((2, nseg_pad), jnp.float32),
            interpret=interpret,
        )(*norm_args)

    # ---- host: per-segment trust table, padded back to nseg_pad ----
    with jax.named_scope(scopes.TRUST_TABLE):
        wn, bn, ratio = ref.trust_ratio(
            table2[0, :nseg], table2[1, :nseg], adapt_mask, mode=mode,
            eta=eta, weight_decay=weight_decay, eps=eps,
            trust_clip=trust_clip)
        table = ref.scales_from_ratio(ratio, adapt_mask, base_lr,
                                      weight_decay)
        table = jnp.pad(table, ((0, 0), (0, nseg_pad - nseg)))

    # ---- pass 2: gathered-scale elementwise apply ----
    if mode == "lamb":
        apply_kernel = functools.partial(
            _seg_apply_lamb, nseg_pad=nseg_pad, b1=b1, b2=b2, eps=eps,
            sr=stochastic_round, block_rows=block_rows)
        in_specs = [ids_block, smem, smem, tab_block,
                    block, block, block, block]
        args = (seg_ids, sc, seed_arr, table, w2d, g2d, bufs[0], bufs[1])
    else:
        apply_kernel = functools.partial(
            _seg_apply_lars, nseg_pad=nseg_pad, mode=mode,
            momentum=momentum, nesterov=nesterov,
            sr=stochastic_round, block_rows=block_rows)
        in_specs = [ids_block, smem, tab_block, block, block, block]
        args = (seg_ids, seed_arr, table, w2d, g2d, bufs[0])
    # state buffers keep their storage dtype; the delta is always f32
    out_shape = [jax.ShapeDtypeStruct(b.shape, b.dtype) for b in bufs] \
        + [jax.ShapeDtypeStruct(w2d.shape, jnp.float32)]
    with jax.named_scope(scopes.SEG_APPLY):
        outs = pl.pallas_call(
            apply_kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[block] * len(out_shape),
            out_shape=out_shape,
            interpret=interpret,
        )(*args)
    if telemetry:
        telem = {"w_norm": wn, "g_norm": bn, "trust_ratio": ratio}
        return tuple(outs[:-1]), outs[-1], telem
    return tuple(outs[:-1]), outs[-1]
