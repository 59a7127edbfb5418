"""Production mesh builders.

NOTE: functions, not module-level constants — importing this module never
touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (tests, benches, the FL sim) sees the real single
CPU device.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16x16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (axes sized 1)."""
    return _auto_mesh((1, 1), ("data", "model"))


def _auto_mesh(shape, axes):
    """A mesh whose axes the compiler partitions (``make_mesh`` defaults
    to explicit axes, which would carry shardings in the types)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_client_mesh(n_devices: Optional[int] = None):
    """1-D mesh over the ``clients`` axis for the sharded round engine.

    Uses all visible devices by default; on CPU, virtual devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
    first jax import — same mechanism as ``launch/dryrun.py``).
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(
            f"client mesh needs {n} devices but only {len(devs)} are "
            f"visible; set XLA_FLAGS=--xla_force_host_platform_device_count")
    return jax.sharding.Mesh(np.asarray(devs[:n]), ("clients",))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over (pod included when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
