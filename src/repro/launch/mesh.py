"""Production mesh builders (MULTI-POD DRY-RUN step 1).

Functions, not module-level constants — importing this module never touches
jax device state (smoke tests must keep seeing 1 device).

Meshes are Auto-typed: ``jax.make_mesh`` defaults to Explicit axes, and the
model's shard_map / sharding-constraint code is written for Auto ones.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips).

    The dry-run host exposes 512 placeholder devices; the single-pod mesh
    takes the first 256 so both meshes build in one process.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax (launch/dryrun.py does this)")
    return make_mesh(shape, axes, devices=devices)


def make_mesh(shape, axes, devices=None):
    """Mesh with Auto axis types (shard_map-compatible) over ``devices``
    (default: all devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
