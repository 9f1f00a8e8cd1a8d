"""LARS — Layer-wise Adaptive Rate Scaling (You, Gitman, Ginsburg 2017).

Implements Eq. (2) of the paper:

    γ_t^k = γ_scale(t) · η · ‖w^k‖ / (‖∇L(w^k)‖ + wd·‖w^k‖ + eps)

followed by momentum:  m ← μ·m + γ_t^k · (g + wd·w);  w ← w − m.

``γ_scale(t)`` is an external schedule:
  * WA-LARS   — ``schedules.warmup_cosine`` (Eq. 4),
  * NOWA-LARS — ``schedules.polynomial``.

1-D params (bias / norm) bypass the trust ratio (see ``labels.py``),
matching the cited reference implementations.

The update itself is built by ``repro.core.layerwise`` (shared with
TVLARS and LAMB). Dispatch story for the per-parameter hot-spot:

  * ``use_kernel=False``   — pure-jnp tree_map, the oracle (mesh-sharding
                             friendly; norms all-reduce per shard).
  * ``use_kernel="fused"`` — the flat substrate: the whole tree is
                             packed once and updated by two segmented
                             Pallas calls total
                             (``kernels.segmented_update``); supports
                             nesterov and ``trust_clip``.
  * ``use_kernel=True``    — alias for ``"fused"``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from repro.core.base import GradientTransform, PyTree
from repro.core.layerwise import layerwise_transform
from repro.core.schedules import Schedule
from repro.kernels import ref


class LarsState(NamedTuple):
    step: jnp.ndarray
    momentum: PyTree    # per-leaf tree, or flat (rows, 128) when fused


def _trust_ratio(w: jnp.ndarray, g: jnp.ndarray, eta: float,
                 weight_decay: float, eps: float) -> jnp.ndarray:
    """η·‖w‖ / (‖g‖ + wd·‖w‖ + eps) — the paper's LNR × η, guarded.

    Returns 1.0 when either norm is zero (reference-impl behaviour:
    freshly-initialised zero layers take a plain step). Thin view over
    the LIVE formula (``ref.trust_scale_table``'s "lars" branch) so the
    trust-ratio unit/property tests exercise what the optimizers run.
    """
    table = ref.trust_scale_table(
        jnp.sum(jnp.square(w.astype(jnp.float32))),
        jnp.sum(jnp.square(g.astype(jnp.float32))),
        jnp.asarray(True), 1.0, mode="lars", eta=eta,
        weight_decay=weight_decay, eps=eps)
    return table[0]    # base_lr=1 ⇒ sg == the bare ratio


def lars(learning_rate: Schedule, *, eta: float = 1e-3,
         momentum: float = 0.9, weight_decay: float = 5e-4,
         eps: float = 1e-9, nesterov: bool = False,
         trust_clip: Optional[float] = None,
         param_labels: Optional[PyTree] = None,
         use_kernel=False, precision: str = "f32") -> GradientTransform:
    """Build a LARS GradientTransform. Updates are returned as deltas.

    ``trust_clip`` caps the trust ratio (LAMBC-style clipping, Fong et
    al. 2020 — cited in the paper's related work as a stability
    alternative to warm-up); None reproduces vanilla LARS.
    ``precision`` ("f32" | "bf16_master" | "bf16_master_sr", fused
    only) selects the flat substrate's storage dtype — see
    ``repro.core.layerwise``."""
    return layerwise_transform(
        learning_rate, mode="lars", state_cls=LarsState, eta=eta,
        momentum=momentum, weight_decay=weight_decay, eps=eps,
        nesterov=nesterov, trust_clip=trust_clip,
        param_labels=param_labels, use_kernel=use_kernel,
        precision=precision, optimizer_name="lars")
