"""Pure-jnp oracles for every Pallas kernel (the source of truth).

Each ``ref_*`` function implements exactly the math its kernel fuses;
tests sweep shapes/dtypes and assert_allclose kernel-vs-ref.

This module also hosts the *shared* layer-wise update math
(:func:`direction`, :func:`integrate`, :func:`trust_scale_table`) used
by all three dispatch paths — the pure tree_map path in
``repro.core.layerwise``, the per-tensor Pallas kernel, and the
segmented (fused multi-tensor) kernel — so the paths agree by
construction and parity tests only have to catch kernel plumbing bugs.

The unified update for every optimizer in the family is

    d          = direction(mode, ...)        # g, or the Adam direction
    scaled     = sg·d + sw·w                 # sg = lr·ratio, sw = sg·wd
    new, delta = integrate(mode, ...)        # heavy ball / Alg.1 / none

with per-segment (sg, sw) from :func:`trust_scale_table`.

Mixed precision: the segmented oracle (and kernels) accept flat buffers
at ANY storage dtype. Every operand is upcast to f32 on read, all math
— segment norms, the trust table, momentum integration — runs strictly
in f32, state buffers are written back at their own storage dtype
(round-to-nearest, or :func:`stochastic_round_to` under the ``_sr``
policies) and the weight-update delta is ALWAYS emitted in f32 so the
caller's f32 master params never see storage rounding. The oracle
rounds at exactly the same program points as the kernels, so
``REPRO_FORCE_REF=1`` stays the bitwise-comparable ground truth at
every precision policy; :func:`parity_tolerance` is the documented
bound for comparing a low-precision policy against the f32 reference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MODES = ("lars", "paper", "lamb")


# ---------------------------------------------------------------------------
# precision model: parity bounds + stochastic rounding
# ---------------------------------------------------------------------------

def parity_tolerance(precision: str, steps: int = 1) -> dict:
    """Documented bound for fused-vs-f32-reference update parity.

    * ``"f32"`` — the substrate stores exact f32 copies; the only
      divergence is norm-accumulation order, bounded at 1e-6.
    * ``"bf16_master"`` (and ``_sr``) — params/grads/momentum are
      rounded once to bf16 (8-bit mantissa, round-to-nearest error
      <= 2^-9 relative per operand) before the f32 math, so each
      step's update carries a few-ulp-of-bf16 relative error; momentum
      state compounds it linearly in ``steps``. The bound is
      ``4·2^-8·steps`` relative with a matching absolute floor scaled
      to O(1) update magnitudes.

    Kernel-vs-oracle parity is NOT governed by this bound: both round
    at identical program points, so they agree to <= 1e-6 at any
    policy (see ``tests/test_precision.py``).
    """
    if precision == "f32":
        return {"rtol": 1e-6, "atol": 1e-6}
    eps = 2.0 ** -8
    return {"rtol": 4 * eps * steps, "atol": 4 * eps * steps}


def hash_bits(idx: jnp.ndarray, seed) -> jnp.ndarray:
    """Counter-based uint32 hash of per-element indices (xxhash-style
    avalanche) — the stateless RNG behind stochastic rounding. Pure
    elementwise integer ops, so it runs identically inside a Pallas
    kernel and in this oracle (indices wrap at 2^32 elements; fine for
    hashing)."""
    x = idx.astype(jnp.uint32) * jnp.uint32(2654435761)
    x = x + jnp.asarray(seed, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(3266489917)
    return x ^ (x >> 16)


def stochastic_round_to(x: jnp.ndarray, bits: jnp.ndarray,
                        dtype) -> jnp.ndarray:
    """Stochastically round f32 ``x`` to bf16 using uniform ``bits``.

    bf16 is the top 16 bits of f32, so adding a uniform uint16 to the
    f32 bit pattern and truncating the low half rounds x up with
    probability equal to the discarded fraction — unbiased in
    expectation, unlike round-to-nearest whose per-step momentum bias
    compounds. Non-bf16 dtypes fall back to round-to-nearest.
    """
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return x.astype(dtype)
    x32 = x.astype(jnp.float32)
    u = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    u = (u + (bits & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    rounded = jax.lax.bitcast_convert_type(u, jnp.float32)
    # inf/nan bit patterns must not be perturbed by the mantissa add
    return jnp.where(jnp.isfinite(x32), rounded, x32).astype(dtype)


def store(x: jnp.ndarray, dtype, *, bits: jnp.ndarray | None = None
          ) -> jnp.ndarray:
    """Write-back cast for state buffers: round-to-nearest, or
    stochastic when ``bits`` is given (the ``_sr`` policies)."""
    if bits is not None:
        return stochastic_round_to(x, bits, dtype)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# shared elementwise math (modes: "lars" heavy-ball, "paper" Alg. 1, "lamb")
# ---------------------------------------------------------------------------

def direction(mode: str, w, g, bufs, *, b1: float = 0.9, b2: float = 0.999,
              bc1=1.0, bc2=1.0, eps: float = 1e-6):
    """Pre-trust-ratio descent direction + (for LAMB) updated moments.

    Returns ``(d, new_bufs)``; for "lars"/"paper" the momentum buffer is
    integrated later by :func:`integrate` and passes through unchanged.
    """
    if mode == "lamb":
        mu, nu = bufs
        new_mu = b1 * mu + (1.0 - b1) * g
        new_nu = b2 * nu + (1.0 - b2) * jnp.square(g)
        d = (new_mu / bc1) / (jnp.sqrt(new_nu / bc2) + eps)
        return d, (new_mu, new_nu)
    return g, bufs


def integrate(mode: str, w, bufs, scaled, *, momentum: float = 0.9,
              nesterov: bool = False):
    """Momentum integration -> ``(new_bufs, delta)``; params' = w + delta.

    * "lars":  m' = μm + scaled;  Δ = −m' (or nesterov −(scaled + μm'))
    * "paper": Algorithm 1 l.7–8 — buffer stores previous *proposed*
      params:  m' = w − scaled;  Δ = (m' − w) + μ(m' − m)
    * "lamb":  moments were already advanced in :func:`direction`;
      Δ = −scaled.
    """
    if mode == "paper":
        (m,) = bufs
        proposed = w - scaled
        delta = (proposed - w) + momentum * (proposed - m)
        return (proposed,), delta
    if mode == "lars":
        (m,) = bufs
        new_m = momentum * m + scaled
        delta = -(scaled + momentum * new_m) if nesterov else -new_m
        return (new_m,), delta
    return bufs, -scaled    # lamb


def trust_ratio(w2, b2, adapt_mask, *, mode: str, eta: float,
                weight_decay: float, eps: float, trust_clip=None):
    """Per-segment ``(w_norm, b_norm, ratio)`` from Σw², Σb².

    The layer-wise telemetry triple the paper's analysis runs on
    (LWN, LGN and the effective trust ratio), factored out of
    :func:`trust_scale_table` so the fused step can surface it without
    recomputing anything — the table is just ``base_lr · ratio``.
    """
    wn = jnp.sqrt(w2)
    bn = jnp.sqrt(b2)
    nonzero = (wn > 0.0) & (bn > 0.0)
    if mode == "lamb":
        ratio = jnp.where(nonzero, wn / jnp.where(nonzero, bn, 1.0), 1.0)
    else:
        ratio = jnp.where(
            nonzero, eta * wn / (bn + weight_decay * wn + eps), 1.0)
    if trust_clip is not None:
        ratio = jnp.minimum(ratio, trust_clip)
    ratio = jnp.where(adapt_mask, ratio, 1.0)
    return wn, bn, ratio


def scales_from_ratio(ratio, adapt_mask, base_lr,
                      weight_decay: float) -> jnp.ndarray:
    """(sg, sw) = (lr·ratio, lr·ratio·wd) stacked -> (2, ...) f32;
    non-ADAPT segments take no weight decay."""
    sg = jnp.asarray(base_lr, jnp.float32) * ratio
    sw = jnp.where(adapt_mask, sg * weight_decay, 0.0)
    return jnp.stack([sg, sw]).astype(jnp.float32)


def trust_scale_table(w2, b2, adapt_mask, base_lr, *, mode: str,
                      eta: float, weight_decay: float, eps: float,
                      trust_clip=None) -> jnp.ndarray:
    """Per-segment (sg, sw) from per-segment Σw², Σb² -> (2, nseg) f32.

    ``b`` is the trust denominator vector: g for LARS/TVLARS, the
    wd-augmented Adam direction for LAMB. Non-ADAPT (1-D bypass)
    segments get ratio 1 and no weight decay, reproducing the reference
    implementations' bias/norm handling.
    """
    _, _, ratio = trust_ratio(w2, b2, adapt_mask, mode=mode, eta=eta,
                              weight_decay=weight_decay, eps=eps,
                              trust_clip=trust_clip)
    return scales_from_ratio(ratio, adapt_mask, base_lr, weight_decay)


# ---------------------------------------------------------------------------
# per-tensor oracle (matches kernels/lars_update.py)
# ---------------------------------------------------------------------------

def ref_lars_update(w: jnp.ndarray, g: jnp.ndarray, m: jnp.ndarray, *,
                    base_lr, eta: float, weight_decay: float,
                    momentum_mu: float, eps: float = 1e-9,
                    nesterov: bool = False):
    """LARS trust-ratio + momentum + delta (matches core/lars.py ADAPT path).

    Returns (new_momentum, delta) where new params = w + delta.
    """
    w32 = w.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    w_norm = jnp.sqrt(jnp.sum(jnp.square(w32)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g32)))
    denom = g_norm + weight_decay * w_norm + eps
    ratio = jnp.where((w_norm > 0.0) & (g_norm > 0.0),
                      eta * w_norm / denom, 1.0)
    scaled = base_lr * ratio * (g32 + weight_decay * w32)
    new_m = momentum_mu * m + scaled
    step_dir = scaled + momentum_mu * new_m if nesterov else new_m
    return new_m, -step_dir


# ---------------------------------------------------------------------------
# segmented (fused multi-tensor) oracle — matches kernels/segmented_update.py
# ---------------------------------------------------------------------------

def buf_bits(idx: jnp.ndarray, seed, buf: int) -> jnp.ndarray:
    """Random bits for state-buffer ``buf``'s write-back — the seed is
    golden-ratio-mixed per buffer so LAMB's mu and nu draw independent
    streams. Shared verbatim by oracle and kernel."""
    return hash_bits(idx, jnp.asarray(seed, jnp.uint32)
                     + jnp.uint32(buf) * jnp.uint32(0x9E3779B9))


def element_index(rows: int, lanes: int, row0: int = 0) -> jnp.ndarray:
    """(rows, lanes) int32 global flat element index starting at row
    ``row0`` — the SR hash counter. In the kernel ``row0`` is the grid
    step's first row, so per-block bits equal the oracle's."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) + row0
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    return r * lanes + c


def ref_segmented_update(w2d, g2d, bufs, *, seg_ids, adapt_mask, base_lr,
                         mode: str, eta: float, weight_decay: float,
                         momentum: float, b1: float, b2: float, eps: float,
                         nesterov: bool = False, trust_clip=None,
                         bc1=1.0, bc2=1.0, stochastic_round: bool = False,
                         seed=0, telemetry: bool = False):
    """Whole-tree layer-wise step on the flat substrate, in pure jnp.

    Inputs are ``(num_rows, LANES)`` buffers from
    ``repro.core.flatten.pack`` — at ANY storage dtype — plus the
    spec's ``(num_rows, 1)`` segment-id map and ``(nseg,)`` adapt mask.
    Operands are upcast to f32 on read; segment norms, the trust table
    and the integration run strictly in f32; new state buffers are
    written back at their input storage dtype (stochastically rounded
    when ``stochastic_round``, seeded per step by ``seed``) and the
    returned ``delta2d`` is always f32. Returns ``(new_bufs, delta2d)``
    with the same flat layout.

    ``telemetry=True`` additionally returns the per-segment
    ``{"w_norm", "g_norm", "trust_ratio"}`` triple (each ``(nseg,)``
    f32) already materialized on the way to the trust table — the
    layer-wise stream ``repro.obs.layerwise`` surfaces, at zero extra
    passes over the buffers.
    """
    nseg = adapt_mask.shape[0]
    ids = seg_ids.reshape(-1)
    state_dtypes = tuple(b.dtype for b in bufs)
    w32 = w2d.astype(jnp.float32)
    g32 = g2d.astype(jnp.float32)
    bufs32 = tuple(b.astype(jnp.float32) for b in bufs)

    d, bufs2 = direction(mode, w32, g32, bufs32, b1=b1, b2=b2,
                         bc1=bc1, bc2=bc2, eps=eps)
    bvec = d + weight_decay * w32 if mode == "lamb" else g32
    row_w2 = jnp.sum(jnp.square(w32), axis=1)
    row_b2 = jnp.sum(jnp.square(bvec), axis=1)
    w2 = jax.ops.segment_sum(row_w2, ids, num_segments=nseg)
    b2sum = jax.ops.segment_sum(row_b2, ids, num_segments=nseg)

    wn, bn, ratio = trust_ratio(w2, b2sum, adapt_mask, mode=mode, eta=eta,
                                weight_decay=weight_decay, eps=eps,
                                trust_clip=trust_clip)
    table = scales_from_ratio(ratio, adapt_mask, base_lr, weight_decay)
    sg = table[0][ids][:, None]
    sw = table[1][ids][:, None]
    scaled = sg * d + sw * w32
    new_bufs, delta = integrate(mode, w32, bufs2, scaled,
                                momentum=momentum, nesterov=nesterov)
    idx = element_index(*w2d.shape) if stochastic_round else None
    new_bufs = tuple(
        store(nb, dt, bits=buf_bits(idx, seed, k)
              if stochastic_round else None)
        for k, (nb, dt) in enumerate(zip(new_bufs, state_dtypes)))
    if telemetry:
        telem = {"w_norm": wn, "g_norm": bn, "trust_ratio": ratio}
        return new_bufs, delta, telem
    return new_bufs, delta


def ref_rmsnorm(x: jnp.ndarray, weight: jnp.ndarray,
                eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm: x / rms(x) * (1 + weight)   (gemma/llama convention)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 / jnp.sqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# fused attention-decode oracle — matches kernels/attention_decode.py
# ---------------------------------------------------------------------------

NEG_INF = -2.0e38  # f32-safe mask value (matches models.layers)


def decode_parity_tolerance(cache_dtype) -> dict:
    """Documented bound for fused-decode attention parity.

    * Kernel ≡ oracle ≡ jnp ``attention_decode`` at the SAME cache
      dtype: all three upcast the identical stored KV values to f32
      and accumulate scores/softmax/probs·V strictly in f32, so the
      only divergence is reassociation (online blockwise softmax vs
      one global softmax) — bounded at 1e-5 on O(1) outputs for any
      storage dtype.
    * bf16 cache vs an f32-cache reference (the accumulation-fix
      test): each KV operand is rounded once to bf16 (8-bit mantissa,
      <= 2^-8 relative) before the f32 math, so the attention output
      carries a few-ulp-of-bf16 relative error — ``4·2^-8`` with a
      matching absolute floor.
    """
    if jnp.dtype(cache_dtype) == jnp.dtype(jnp.bfloat16):
        eps = 2.0 ** -8
        return {"rtol": 4 * eps, "atol": 4 * eps}
    return {"rtol": 1e-5, "atol": 1e-5}


def ref_attention_decode(q, new_k, new_v, k_cache, v_cache, pos, *,
                         window=None):
    """Pure-jnp oracle for the fused decode step, same operand layout
    as ``attention_decode_pallas``: q [B,1,H,Dh], new_k/new_v
    [B,1,Hkv,Dh] (both already rope'd), caches [B,T,Hkv·Dh] (heads
    merged), pos [B] int32 per-row depths. Per-row ring append at
    ``pos % T`` (windowed) or ``pos`` (global), validity mask derived
    from ``pos``, grouped contraction with f32 scores/softmax/
    accumulation. Returns (out [B,1,H,Dh], new_k_cache, new_v_cache).
    """
    b, _, h, dh = q.shape
    hkv = new_k.shape[2]
    t = k_cache.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    slot = pos % t if window is not None else pos

    def write(cache, new):
        return jax.vmap(lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(
            c, n, s, axis=0))(cache, new.reshape(b, 1, hkv * dh).astype(
                cache.dtype), slot)

    kc, vc = write(k_cache, new_k), write(v_cache, new_v)
    kpos = jnp.arange(t)[None, :]                      # [1,T]
    pos_c, slot_c = pos[:, None], slot[:, None]
    if window is not None:
        wraps = (pos_c // t) * t
        abs_pos = kpos + jnp.where(kpos <= slot_c, wraps, wraps - t)
        ok = (abs_pos >= 0) & (abs_pos <= pos_c) \
            & (abs_pos > pos_c - window)
    else:
        ok = kpos <= pos_c                             # [B,T]
    k4 = kc.reshape(b, t, hkv, dh).astype(jnp.float32)
    v4 = vc.reshape(b, t, hkv, dh).astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(b, hkv, h // hkv, dh)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k4) / math.sqrt(dh)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v4)
    return out.reshape(b, 1, h, dh).astype(q.dtype), kc, vc
