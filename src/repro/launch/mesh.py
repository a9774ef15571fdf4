"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch JAX device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import; real deployments get the same meshes from the TPU topology.

Mesh axes:
* ``pod``   — data parallelism across pods (slow inter-pod links carry only
  the gradient all-reduce / cross-pod job migration traffic),
* ``data``  — intra-pod data parallelism / FSDP shard axis,
* ``model`` — tensor/expert/context parallel axis (16-way).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax (dry-run) or run on a real pod")
    return _auto_mesh(shape, axes, devices[:n])


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for CPU integration tests (requires forced device count)."""
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return _auto_mesh(shape, axes, devices[:n])


def _auto_mesh(shape, axes, devices):
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which JAX refuses on Explicit axes (the
    # make_mesh default since JAX 0.7).
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def batch_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
