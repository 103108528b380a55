"""Mesh construction — the single source of device meshes for both the LM
dry-run path and the VFL lane engine.

Functions (not module constants) so importing this module never touches
jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* jax
initializes, while tests and benches must see one device.

Every constructor validates the requested shape against
``jax.devices()`` up front: an oversized ``jax.make_mesh`` otherwise
fails deep inside jax with a reshape error that names neither the mesh
nor the fix.  The ``ValueError`` raised here names both.  A mesh smaller
than the device list spans its first devices.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _checked_mesh(shape: tuple, axes: tuple):
    for ax, n in zip(axes, shape):
        if not (isinstance(n, int) and n >= 1):
            raise ValueError(f"mesh axis {ax!r} must be a positive int, "
                             f"got {n!r}")
    need = math.prod(shape)
    devices = jax.devices()
    have = len(devices)
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices but only "
            f"{have} are available — on CPU, fake host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "(set BEFORE jax initializes)")
    # Auto axes: shardings propagate from the placed inputs, as the lane
    # engine and the dry-run expect (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _checked_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist."""
    return _checked_mesh((data, model), ("data", "model"))


def make_lane_mesh(lane: int = 1, data: int = 1):
    """Mesh for the replica-lane training engine
    (``core.training.train_lanes(..., mesh=...)``): the ``lane`` axis
    shards independent lanes across devices, the ``data`` axis optionally
    shards rows within a lane (``shard_rows=True``).  Axis names line up
    with the logical-axis policy (``sharding.policy``: ``"lane"`` ->
    ``("lane",)``, ``"dp"`` -> ``("data",)``).  The mesh spans the first
    ``lane * data`` of ``jax.devices()``."""
    return _checked_mesh((lane, data), ("lane", "data"))
