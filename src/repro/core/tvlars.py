"""TVLARS — Time-Varying LARS (the paper's contribution, Algorithm 1).

Replaces warm-up with the configurable inverted-sigmoid base LR of
Eq. (5):

    φ_t  = 1/(α + exp(λ(t − d_e))) + γ_min
    γ_t^k = γ_target · η · φ_t · ‖w^k‖ / (‖∇L(w^k)‖ + wd·‖w^k‖ + eps)

so the run *starts* at (roughly) the target LR — "Initiating Exploration
Excitation" — holds for ~d_e steps, then anneals smoothly to
γ_target·γ_min, converging to plain-LARS behaviour ("Alignment with
LARS").  Bounds (Eq. 6):  γ_min ≤ φ_t ≤ 1/(α+exp(−λ d_e)) (+γ_min).

Momentum (Algorithm 1 lines 7–8, the paper's parameter-space heavy ball):

    m_{t+1} = w_t − γ_t^k (g + wd·w)        # proposed params
    w_{t+1} = m_{t+1} + μ (m_{t+1} − m_t)   # extrapolate along history

``momentum_style="paper"`` implements exactly that (the momentum buffer
stores the previous *proposed parameters*; m_0 := w_0 so step 0 is a
plain scaled step). ``momentum_style="lars"`` uses the conventional
LARS buffer (m ← μm + γ(g+wd·w); w ← w − m). Both are tested. The two
equations above are this module's reading of Algorithm 1, whose printed
lines 7–8 carry a typo.

TVLARS uses NO external LR scheduler (Appendix B) — φ_t is the schedule.

Kernel dispatch (shared ``repro.core.layerwise`` core): the fused flat
substrate (``use_kernel="fused"``/``True``) covers BOTH momentum styles
in two segmented Pallas calls per step; ``use_kernel=False`` runs the
same math as a pure-jnp tree_map, the oracle.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from repro.core.base import GradientTransform, PyTree
from repro.core.layerwise import layerwise_transform
from repro.core.schedules import tvlars_phi


class TVLarsState(NamedTuple):
    step: jnp.ndarray
    momentum: PyTree   # previous proposed params (paper) or velocity (lars)


def tvlars(gamma_target: float, *, lam: float = 1e-4,
           delay_steps: int = 100, alpha: float = 1.0,
           gamma_min: float = 1e-3, eta: float = 1e-3,
           momentum: float = 0.9, weight_decay: float = 5e-4,
           eps: float = 1e-9, momentum_style: str = "paper",
           param_labels: Optional[PyTree] = None,
           use_kernel=False, precision: str = "f32") -> GradientTransform:
    """Build TVLARS. ``gamma_target`` is the target LR of Table 1;
    ``gamma_min`` is typically (B/B_base)·1e-3 (§5.2.1).
    ``precision`` selects the fused substrate's storage dtype (see
    ``repro.core.layerwise``); note the "paper" momentum buffer stores
    previous proposed PARAMS, so under bf16 it carries bf16-rounded
    params — covered by the documented parity bound."""
    if momentum_style not in ("paper", "lars"):
        raise ValueError(f"unknown momentum_style {momentum_style!r}")
    phi = tvlars_phi(lam, delay_steps, alpha, gamma_min)

    def base_lr(step):
        return gamma_target * phi(step)

    return layerwise_transform(
        base_lr, mode=momentum_style, state_cls=TVLarsState, eta=eta,
        momentum=momentum, weight_decay=weight_decay, eps=eps,
        param_labels=param_labels, use_kernel=use_kernel,
        precision=precision, optimizer_name="tvlars")
