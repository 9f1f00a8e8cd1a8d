"""LAMB — Layer-wise Adaptive Moments for Batch training (You et al. 2020).

Adam moments + layer-wise trust ratio:

    m ← β1·m + (1−β1)·g            v ← β2·v + (1−β2)·g²
    m̂ = m/(1−β1^t)                 v̂ = v/(1−β2^t)
    r  = m̂/(√v̂ + eps) + wd·w
    w ← w − lr · φ(‖w‖)/‖r‖ · r,    φ(z)=z (optionally clipped)

1-D params bypass the trust ratio (labels.py), as in the cited
pytorch-optimizer reference implementation.

Built on the shared ``repro.core.layerwise`` core; with
``use_kernel="fused"`` (or ``True``) the Adam moments live as flat
substrate buffers and the whole step — moments, segmented ‖w‖/‖r‖,
trust scaling, apply — is two segmented Pallas calls
(``kernels.segmented_update``, mode "lamb"); ``use_kernel=False`` is
the pure-jnp tree_map oracle.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from repro.core.base import GradientTransform, PyTree
from repro.core.layerwise import layerwise_transform
from repro.core.schedules import Schedule


class LambState(NamedTuple):
    step: jnp.ndarray
    mu: PyTree      # per-leaf trees, or flat (rows, 128) when fused
    nu: PyTree


def lamb(learning_rate: Schedule, *, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-6, weight_decay: float = 5e-4,
         trust_clip: Optional[float] = 10.0,
         param_labels: Optional[PyTree] = None,
         use_kernel=False, precision: str = "f32") -> GradientTransform:
    """``precision`` ("f32" | "bf16_master" | "bf16_master_sr", fused
    only) stores BOTH Adam moments at the policy's dtype — the largest
    state-memory win in the family (2 buffers/param)."""
    return layerwise_transform(
        learning_rate, mode="lamb", state_cls=LambState, b1=b1, b2=b2,
        eps=eps, weight_decay=weight_decay, trust_clip=trust_clip,
        param_labels=param_labels, use_kernel=use_kernel,
        precision=precision, optimizer_name="lamb")
