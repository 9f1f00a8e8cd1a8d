"""Shared core for the layer-wise trust-ratio optimizer family.

``lars.py``, ``tvlars.py`` and ``lamb.py`` used to carry three
near-identical ``per_leaf``/tuple-unpacking ``tree_map`` bodies; they
are now thin instantiations of :func:`layerwise_transform`, which owns
labelling, state plumbing and the three dispatch paths:

  * ``use_kernel=False``        — pure-jnp ``tree_map`` over leaves
                                  (sharding-friendly: per-leaf norms
                                  lower to per-shard partials +
                                  all-reduce under a mesh).
  * ``use_kernel="per_tensor"`` — the original fused Pallas kernel, two
                                  ``pallas_call``s PER >=2-D leaf
                                  (heavy-ball LARS math only).
  * ``use_kernel="fused"``      — the flat substrate: all leaves packed
                                  into one lane-padded f32 buffer
                                  (``core.flatten``), the whole step is
                                  two segmented ``pallas_call``s
                                  (``kernels.segmented_update``)
                                  regardless of leaf count. Momentum /
                                  Adam state is STORED flat, so only
                                  params+grads pay pack traffic per
                                  step. Covers every mode: heavy ball,
                                  nesterov, trust_clip, TVLARS "paper"
                                  momentum, and LAMB.

``use_kernel=True`` is accepted as an alias for ``"fused"``.
Unsupported combinations (e.g. ``"per_tensor"`` with ``trust_clip`` or
TVLARS "paper" momentum) raise at build time instead of silently
falling back — see ``_validate_use_kernel``.

Mixed precision (fused path only) — ``precision=``:

  * ``"f32"``            — everything f32 (bitwise the legacy path).
  * ``"bf16_master"``    — the flat substrate stores working params,
                           grads and momentum/Adam moments in bf16
                           (half the optimizer-state memory and HBM
                           traffic of the bandwidth-bound fused step),
                           while the kernels upcast tiles to f32 in
                           VMEM, accumulate segment norms and the
                           trust table strictly in f32, and emit the
                           delta in f32 — the split-SGD master-weight
                           idiom, with the caller's full-precision
                           params as the f32 master rows.
  * ``"bf16_master_sr"`` — same, plus stochastic rounding on the bf16
                           state write-back (unbiased momentum
                           accumulation; seeded per step).

Tolerances: kernel-vs-oracle deltas (and therefore the f32 master
params) stay <= 1e-6 at any policy — both round at the same program
points, so ``REPRO_FORCE_REF=1`` remains ground truth. The bf16 STATE
buffers may disagree by at most one storage ulp (an ~1e-8 f32
accumulation-order difference can land on a bf16 rounding boundary);
policy-vs-f32-reference is bounded by ``ref.parity_tolerance``.

The elementwise math itself lives in ``repro.kernels.ref``
(:func:`~repro.kernels.ref.direction` /
:func:`~repro.kernels.ref.integrate` /
:func:`~repro.kernels.ref.trust_scale_table`) and is shared verbatim by
all three paths, so they agree by construction.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import flatten
from repro.core import labels as labels_lib
from repro.core.base import GradientTransform, PyTree
from repro.kernels import ref
from repro.obs import layerwise as obs_layerwise
from repro.obs import scopes

UseKernel = Union[bool, str]

KERNEL_CHOICES = (False, "per_tensor", "fused")

PRECISIONS = ("f32", "bf16_master", "bf16_master_sr")

# which (mode, feature) combos the per-tensor kernel can express
_PER_TENSOR_MODES = ("lars",)


def storage_dtype(precision: str):
    """The flat substrate's storage dtype under ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision={precision!r}; expected one of {PRECISIONS}")
    return jnp.float32 if precision == "f32" else jnp.bfloat16


def _validate_precision(precision: str, use_kernel: UseKernel,
                        optimizer: str) -> None:
    if precision not in PRECISIONS:
        raise ValueError(
            f"{optimizer}: precision={precision!r}; expected one of "
            f"{PRECISIONS}")
    if precision != "f32" and use_kernel != "fused":
        raise ValueError(
            f"{optimizer}: precision={precision!r} requires "
            f"use_kernel='fused' — only the flat substrate has a "
            f"storage-dtype axis (got use_kernel={use_kernel!r})")


def normalize_use_kernel(use_kernel: UseKernel) -> UseKernel:
    """Map the public flag onto ``False | "per_tensor" | "fused"``.

    ``True`` historically meant the per-tensor kernel; it now aliases
    the strictly-more-capable fused path.
    """
    if use_kernel is True:
        return "fused"
    if use_kernel in (False, None):
        return False
    if use_kernel not in ("per_tensor", "fused"):
        raise ValueError(
            f"use_kernel={use_kernel!r}; expected one of "
            f"{(False, True) + KERNEL_CHOICES[1:]}")
    return use_kernel


def _validate_use_kernel(use_kernel: UseKernel, *, mode: str,
                         trust_clip, optimizer: str) -> None:
    if use_kernel != "per_tensor":
        return
    if mode not in _PER_TENSOR_MODES:
        raise ValueError(
            f"{optimizer}: use_kernel='per_tensor' only supports "
            f"heavy-ball LARS math (got mode={mode!r}); use "
            f"use_kernel='fused' which covers it")
    if trust_clip is not None:
        raise ValueError(
            f"{optimizer}: use_kernel='per_tensor' does not support "
            f"trust_clip; use use_kernel='fused'")


def layerwise_transform(base_lr_fn: Callable[[jnp.ndarray], jnp.ndarray], *,
                        mode: str,
                        state_cls: Any,
                        eta: float = 1e-3,
                        momentum: float = 0.9,
                        weight_decay: float = 5e-4,
                        b1: float = 0.9,
                        b2: float = 0.999,
                        eps: float = 1e-9,
                        nesterov: bool = False,
                        trust_clip: Optional[float] = None,
                        param_labels: Optional[PyTree] = None,
                        use_kernel: UseKernel = False,
                        precision: str = "f32",
                        optimizer_name: str = "layerwise",
                        ) -> GradientTransform:
    """Build a layer-wise GradientTransform. Updates are deltas.

    ``mode``: "lars" (heavy ball, optional nesterov), "paper" (TVLARS
    Algorithm 1 parameter-space momentum) or "lamb" (Adam moments).
    ``state_cls(step, *bufs)`` is the optimizer's public state
    NamedTuple; buffers are momentum trees (unfused/per-tensor) or flat
    ``(rows, 128)`` substrate arrays (fused) at the ``precision``
    policy's storage dtype (f32, or bf16 under ``"bf16_master"`` /
    ``"bf16_master_sr"`` — fused only).
    """
    if mode not in ref.MODES:
        raise ValueError(f"unknown mode {mode!r}; one of {ref.MODES}")
    use_kernel = normalize_use_kernel(use_kernel)
    _validate_use_kernel(use_kernel, mode=mode, trust_clip=trust_clip,
                         optimizer=optimizer_name)
    _validate_precision(precision, use_kernel, optimizer_name)
    sdtype = storage_dtype(precision)
    stochastic = precision.endswith("_sr")
    n_bufs = 2 if mode == "lamb" else 1

    def _labels(params):
        return param_labels if param_labels is not None \
            else labels_lib.default_labels(params)

    def _init_buffer_trees(params):
        if mode == "paper":
            # copy=True: f32->f32 astype would alias the param buffer and
            # break donation (same buffer donated twice in train_step)
            return (jax.tree_util.tree_map(
                lambda p: jnp.array(p, dtype=jnp.float32, copy=True),
                params),)
        def zeros():
            return jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, jnp.float32), params)
        return tuple(zeros() for _ in range(n_bufs))

    def init(params):
        bufs = _init_buffer_trees(params)
        if use_kernel == "fused":
            spec = flatten.build_spec(params, _labels(params),
                                      dtype=sdtype)
            bufs = tuple(flatten.pack_tree(b, spec) for b in bufs)
        return state_cls(jnp.zeros((), jnp.int32), *bufs)

    def _step_scalars(state):
        base_lr = base_lr_fn(state.step)
        stepf = (state.step + 1).astype(jnp.float32)
        bc1 = 1.0 - b1 ** stepf
        bc2 = 1.0 - b2 ** stepf
        return base_lr, bc1, bc2

    # ---- fused path: flat substrate, two pallas_calls per step ----

    def _update_fused(grads, state, params):
        # the packed buffers are the WORKING copies at the storage
        # dtype; ``params`` itself is the f32 master the f32 delta is
        # applied to outside (split-SGD structure)
        spec = flatten.build_spec(params, _labels(params), dtype=sdtype)
        base_lr, bc1, bc2 = _step_scalars(state)
        from repro.kernels import ops as kops
        telemetry = obs_layerwise.active()
        with jax.named_scope(scopes.PACK):
            w2d = flatten.pack_tree(params, spec)
            g2d = flatten.pack_tree(grads, spec)
        out = kops.segmented_update(
            w2d, g2d, tuple(state[1:]),
            seg_ids=spec.segment_ids(), adapt_mask=spec.adapt_mask(),
            base_lr=base_lr, mode=mode, eta=eta,
            weight_decay=weight_decay, momentum=momentum, b1=b1, b2=b2,
            eps=eps, nesterov=nesterov, trust_clip=trust_clip,
            bc1=bc1, bc2=bc2, stochastic_round=stochastic,
            seed=state.step, telemetry=telemetry)
        if telemetry:
            new_bufs, delta2d, telem = out
            # the triple the kernel's host pass already materialized
            # between its two launches — surfacing it is free
            obs_layerwise.deposit(telem)
        else:
            new_bufs, delta2d = out
        with jax.named_scope(scopes.UNPACK):
            updates = flatten.unpack_tree(delta2d, spec)
        return updates, state_cls(state.step + 1, *new_bufs)

    # ---- tree paths: per-leaf jnp math, optional per-tensor kernel ----

    def _update_tree(grads, state, params):
        lab = _labels(params)
        base_lr, bc1, bc2 = _step_scalars(state)
        telemetry = obs_layerwise.active()
        # per-leaf (w_norm, g_norm, trust_ratio) in tree_map order —
        # the same segment order the fused substrate packs, so the two
        # paths' telemetry streams are name-compatible
        rows: list = []
        if use_kernel == "per_tensor":
            from repro.kernels import ops as kops

        def per_leaf(g, w, *bufs_and_tag):
            bufs, tag = bufs_and_tag[:-1], bufs_and_tag[-1]
            g32 = g.astype(jnp.float32)
            w32 = w.astype(jnp.float32)
            adapt = tag == labels_lib.ADAPT
            if (use_kernel == "per_tensor" and adapt
                    and w.ndim >= 1 and w.size >= 8):
                new_m, delta = kops.lars_update(
                    w32, g32, bufs[0], base_lr=base_lr, eta=eta,
                    weight_decay=weight_decay, momentum_mu=momentum,
                    eps=eps, nesterov=nesterov)
                if telemetry:
                    # per-tensor kernel is "lars"-only: bvec == g
                    rows.append(ref.trust_ratio(
                        jnp.sum(jnp.square(w32)), jnp.sum(jnp.square(g32)),
                        jnp.asarray(adapt), mode=mode, eta=eta,
                        weight_decay=weight_decay, eps=eps,
                        trust_clip=trust_clip))
                return (new_m, delta)
            d, bufs2 = ref.direction(mode, w32, g32, bufs, b1=b1, b2=b2,
                                     bc1=bc1, bc2=bc2, eps=eps)
            # same table math as the fused host pass, on a 1-segment
            # "tree": the leaf's Σw²/Σb² and its own adapt flag
            bvec = d + weight_decay * w32 if mode == "lamb" else g32
            wn, bn, ratio = ref.trust_ratio(
                jnp.sum(jnp.square(w32)), jnp.sum(jnp.square(bvec)),
                jnp.asarray(adapt), mode=mode, eta=eta,
                weight_decay=weight_decay, eps=eps, trust_clip=trust_clip)
            if telemetry:
                rows.append((wn, bn, ratio))
            table = ref.scales_from_ratio(ratio, jnp.asarray(adapt),
                                          base_lr, weight_decay)
            scaled = table[0] * d + table[1] * w32
            new_bufs, delta = ref.integrate(mode, w32, bufs2, scaled,
                                            momentum=momentum,
                                            nesterov=nesterov)
            return (*new_bufs, delta)

        out = jax.tree_util.tree_map(per_leaf, grads, params,
                                     *state[1:], lab)
        if telemetry and rows:
            obs_layerwise.deposit({
                "w_norm": jnp.stack([r[0] for r in rows]),
                "g_norm": jnp.stack([r[1] for r in rows]),
                "trust_ratio": jnp.stack([r[2] for r in rows]),
            })
        def is_out(x):
            return isinstance(x, tuple)
        new_bufs = tuple(
            jax.tree_util.tree_map(lambda o, k=k: o[k], out, is_leaf=is_out)
            for k in range(n_bufs))
        updates = jax.tree_util.tree_map(lambda o: o[n_bufs], out,
                                         is_leaf=is_out)
        return updates, state_cls(state.step + 1, *new_bufs)

    def update(grads, state, params=None):
        if params is None:
            raise ValueError(f"{optimizer_name} requires params")
        if use_kernel == "fused":
            return _update_fused(grads, state, params)
        return _update_tree(grads, state, params)

    return GradientTransform(init, update)
