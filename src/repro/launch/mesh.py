"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module touches no jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* jax
initializes, and smoke tests must keep seeing 1 device.

Topology (TPU v5e):
  single-pod  (data=16, model=16)           — 256 chips, all-ICI
  multi-pod   (pod=2, data=16, model=16)    — 512 chips; the leading "pod"
                                              axis crosses DCN
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_production_mesh",
    "make_mesh_for",
    "require_devices",
    "use_host_devices",
]

_HOST_COUNT = "--xla_force_host_platform_device_count"


def use_host_devices(n: int) -> None:
    """Give a CPU run ``n`` virtual host devices, so a sharded path can run
    without a multi-chip host.  A run that will land on a TPU (JAX_PLATFORMS
    does not start with ``cpu`` and the TPU library is installed) keeps
    its real devices.  Call before anything initializes JAX's backends —
    importing this module does not."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    on_cpu = platforms.split(",")[0] == "cpu" or (
        not platforms and importlib.util.find_spec("libtpu") is None
    )
    flags = os.environ.get("XLA_FLAGS", "")
    if on_cpu and _HOST_COUNT not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_HOST_COUNT}={n}".strip()


def require_devices(n: int, what: str) -> list:
    """The first ``n`` devices, or a clear error naming what needed them."""
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"{what} needs {n} devices; JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)"
        )
    return devs[:n]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_for(shape, axes)


def make_mesh_for(shape, axes) -> Mesh:
    """Build a mesh over the first prod(shape) available devices."""
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {dict(zip(axes, shape))}, "
            f"have {len(devs)} — did you set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (see launch/dryrun.py)?"
        )
    grid = np.asarray(devs[:n]).reshape(shape)
    return Mesh(grid, axes, axis_types=(AxisType.Auto,) * len(axes))
