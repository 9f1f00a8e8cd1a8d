"""Public jit'd wrappers for the Pallas kernels.

On TPU the kernels compile natively; on CPU (this container) they run in
``interpret=True`` mode — same kernel body, executed in Python — so all
correctness tests exercise the real kernel logic. ``REPRO_FORCE_REF=1``
falls back to the pure-jnp oracles (useful for bisecting kernel bugs).

Two kernels:

  * ``segmented_update``       — the layer-wise optimizers' whole-tree
                                 step on the flat substrate (two
                                 ``pallas_call``s per STEP, any leaf
                                 count); covers LARS (incl. nesterov +
                                 trust_clip), TVLARS both momentum
                                 styles, and LAMB. See
                                 ``repro.core.layerwise``.
  * ``attention_decode_fused`` — the serving decode step's attention
                                 (one ``pallas_call`` per layer).
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.attention_decode import attention_decode_pallas
from repro.kernels.segmented_update import segmented_update_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _force_ref() -> bool:
    return os.environ.get("REPRO_FORCE_REF", "0") == "1"


def segmented_update(w2d, g2d, bufs, **kw):
    """Segmented whole-tree layer-wise step -> (new_bufs, delta2d).

    ``kw``: seg_ids, adapt_mask, base_lr, mode, eta, weight_decay,
    momentum, b1, b2, eps, nesterov, trust_clip, bc1, bc2, plus the
    mixed-precision knobs ``stochastic_round``/``seed`` (state buffers
    keep their storage dtype; the delta is always f32 — kernel and
    oracle round at identical points, so REPRO_FORCE_REF=1 remains
    ground truth at any precision policy) and ``telemetry`` (surface
    the per-segment ``(w_norm, g_norm, trust_ratio)`` triple as a
    third return — zero extra launches, identical under kernel and
    oracle dispatch; see ``repro.obs.layerwise``).
    """
    if _force_ref():
        return ref.ref_segmented_update(w2d, g2d, bufs, **kw)
    return segmented_update_pallas(w2d, g2d, bufs, interpret=_interpret(),
                                   **kw)


def attention_decode_fused(q, new_k, new_v, k_cache, v_cache, pos, *,
                           window=None):
    """Fused serving-decode attention: per-row KV ring append +
    mask-from-``pos`` + online-softmax GQA contraction in one launch.
    q [B,1,H,Dh], new_k/new_v [B,1,Hkv,Dh] (rope'd), caches
    [B,T,Hkv·Dh], pos [B] int32 -> (out, new_k_cache, new_v_cache);
    see ``kernels.ref.decode_parity_tolerance`` for the parity model.
    """
    if _force_ref():
        return ref.ref_attention_decode(q, new_k, new_v, k_cache,
                                        v_cache, pos, window=window)
    return attention_decode_pallas(q, new_k, new_v, k_cache, v_cache,
                                   pos, window=window,
                                   interpret=_interpret())


def count_pallas_calls(jaxpr) -> int:
    """Recursively count pallas_call eqns (incl. nested call jaxprs).

    Launch accounting for the dispatch paths — exact and
    backend-independent (works on interpret-mode jaxprs too). Used by
    the parity tests and ``benchmarks/bench_kernels.py`` to evidence
    the fused path's 2-launches-per-step invariant.
    """
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            leaves = jax.tree_util.tree_leaves(
                v, is_leaf=lambda x: hasattr(x, "eqns")
                or hasattr(x, "jaxpr"))
            for j in leaves:
                if hasattr(j, "eqns"):
                    n += count_pallas_calls(j)
                elif hasattr(j, "jaxpr"):
                    n += count_pallas_calls(j.jaxpr)
    return n
