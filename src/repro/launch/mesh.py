"""Mesh construction.

Production target: TPU v5e pods of 256 chips. Single-pod mesh is
(16, 16) over ("data", "model"); multi-pod is (2, 16, 16) over
("pod", "data", "model") — the batch shards over ("pod","data") jointly.

Functions (never module-level constants) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE any jax
import to fabricate the placeholder devices. CPU runs fabricate smaller
hosts the same way (the launcher's ``--mesh-data/--mesh-model`` set the
flag to ``data*model`` automatically when it is absent).

All constructors validate the device budget up front:
``data * model`` (× pods) exceeding the available devices raises a
:class:`ValueError` naming both numbers and the fabrication flag,
instead of letting ``jax.make_mesh`` error opaquely from deep inside
its device-assignment solver.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _check_devices(shape: tuple[int, ...], axes: tuple[str, ...]) -> None:
    need = int(np.prod(shape, dtype=int))
    for ax, n in zip(axes, shape):
        if n < 1:
            raise ValueError(f"mesh axis {ax!r} must be >= 1, got {n}")
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices but only "
            f"{have} are available; fabricate host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"(set BEFORE the first jax device access) or shrink the mesh")


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto axes: since JAX 0.9 the default is
    Explicit, and ``with_sharding_constraint`` (the activation anchors
    in ``models.layers``) may only name Auto axes."""
    _check_devices(shape, axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_data_mesh(data: int, model: int = 1) -> Mesh:
    """A ("data", "model") mesh over the FIRST ``data*model`` devices.

    Unlike :func:`make_host_mesh` (which lets jax pick a device
    assignment for the whole host), this pins the mesh to a stable
    prefix of ``jax.devices()`` so meshes of different data widths
    share devices — the adaptive controller's (D, K) retargeting builds
    one of these per visited D and jit reshards state across them.
    """
    _check_devices((data, model), ("data", "model"))
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def required_devices(*, multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256
