"""Optimizer factory — the config system's entry point.

``build_optimizer(name, total_steps=..., **hyper)`` returns a
GradientTransform for:

  * ``"wa-lars"``    LARS + warm-up + cosine (Eq. 4) — the paper's WA-LARS
  * ``"nowa-lars"``  LARS + polynomial decay          — NOWA-LARS
  * ``"lamb"``       LAMB + warm-up + cosine          — WA-LAMB (Table 1)
  * ``"tvlars"``     TVLARS (Eq. 5 / Algorithm 1)     — the contribution
  * ``"sgd"``        SGD + momentum + cosine

Batch-size LR scaling (§5.2.2): pass ``batch_size``/``base_batch_size``
and the factory applies the chosen ``scaling_rule`` ("sqrt" default,
"linear" = Goyal et al.) to the target LR, and sets TVLARS's
γ_min = (B/B_base)·1e-3 as in §5.2.1 unless overridden.
``batch_size`` is the **global** batch — the total samples consumed per
optimizer step (``accum_steps × microbatch × data_parallel``), NOT the
per-device or per-microbatch size; the launcher passes its
``--global-batch`` here.

``use_kernel`` selects the layer-wise update's dispatch path
(``repro.core.layerwise``): ``False`` = pure-jnp tree_map (the oracle),
``"fused"`` (alias ``True``) = the flat substrate — the whole tree
updated by exactly two segmented Pallas calls per step, covering LARS
(nesterov, trust_clip), both TVLARS momentum styles, and LAMB. Any
other value raises at build time.

``precision`` selects the fused substrate's mixed-precision policy:
``"f32"`` (default, bitwise-legacy), ``"bf16_master"`` (bf16 working
params / grads / momentum with strictly-f32 norm accumulation and f32
master updates — half the optimizer-state bytes per step), or
``"bf16_master_sr"`` (plus stochastic rounding on the bf16 state
write-back). Non-f32 policies require ``use_kernel="fused"``.
"""
from __future__ import annotations

from typing import Optional

from repro.core import schedules
from repro.core.base import GradientTransform
from repro.core.lamb import lamb
from repro.core.lars import lars
from repro.core.layerwise import normalize_use_kernel
from repro.core.sgd import sgd
from repro.core.tvlars import tvlars

OPTIMIZERS = ("wa-lars", "nowa-lars", "lars", "lambc-lars", "lamb",
              "tvlars", "sgd")


def build_optimizer(name: str, *, total_steps: int,
                    learning_rate: float = 1.0,
                    batch_size: Optional[int] = None,
                    base_batch_size: int = 256,
                    warmup_steps: Optional[int] = None,
                    delay_steps: Optional[int] = None,
                    lam: float = 1e-4,
                    alpha: float = 1.0,
                    gamma_min: Optional[float] = None,
                    eta: float = 1e-3,
                    momentum: float = 0.9,
                    weight_decay: float = 5e-4,
                    use_kernel=False,   # False | "fused"/True
                    precision: str = "f32",
                    momentum_style: str = "paper",
                    scaling_rule: str = "sqrt",
                    ) -> GradientTransform:
    name = name.lower()
    if name not in OPTIMIZERS:
        raise ValueError(f"unknown optimizer {name!r}; one of {OPTIMIZERS}")

    lr = learning_rate
    if batch_size is not None:
        lr = schedules.batch_scaled_lr(learning_rate, batch_size,
                                       base_batch_size, scaling_rule)
    if warmup_steps is None:
        warmup_steps = max(total_steps // 10, 1)
    if delay_steps is None:
        delay_steps = max(total_steps // 10, 1)
    if gamma_min is None:
        if batch_size is not None:
            gamma_min = (batch_size / base_batch_size) * 1e-3  # §5.2.1
        else:
            gamma_min = 1e-3
    # γ_min is a *fraction of γ_target* in φ_t; keep it sane.
    gamma_min = min(gamma_min, 0.5)

    if name in ("wa-lars", "lars"):
        sched = schedules.warmup_cosine(lr, warmup_steps, total_steps)
        return lars(sched, eta=eta, momentum=momentum,
                    weight_decay=weight_decay, use_kernel=use_kernel,
                    precision=precision)
    if name == "lambc-lars":
        # trust-ratio-clipped LARS WITHOUT warm-up (Fong et al. 2020):
        # the clip replaces warm-up's job of bounding the early LNR.
        sched = schedules.polynomial(lr, total_steps)
        return lars(sched, eta=eta, momentum=momentum,
                    weight_decay=weight_decay, trust_clip=10.0,
                    use_kernel=use_kernel, precision=precision)
    if name == "nowa-lars":
        sched = schedules.polynomial(lr, total_steps)
        return lars(sched, eta=eta, momentum=momentum,
                    weight_decay=weight_decay, use_kernel=use_kernel,
                    precision=precision)
    if name == "lamb":
        sched = schedules.warmup_cosine(lr, warmup_steps, total_steps)
        return lamb(sched, weight_decay=weight_decay,
                    use_kernel=use_kernel, precision=precision)
    if name == "tvlars":
        return tvlars(lr, lam=lam, delay_steps=delay_steps, alpha=alpha,
                      gamma_min=gamma_min, eta=eta, momentum=momentum,
                      weight_decay=weight_decay,
                      momentum_style=momentum_style, use_kernel=use_kernel,
                      precision=precision)
    if name == "sgd":
        if normalize_use_kernel(use_kernel):
            raise ValueError(
                "sgd has no layer-wise kernel path; use_kernel must be "
                "False (the trust-ratio kernels only apply to "
                "lars/tvlars/lamb)")
        if precision != "f32":
            raise ValueError(
                "sgd has no fused substrate; precision must be 'f32'")
        sched = schedules.warmup_cosine(lr, warmup_steps, total_steps)
        return sgd(sched, momentum=momentum, weight_decay=weight_decay)
    raise AssertionError(name)
