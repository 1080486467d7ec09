"""Production mesh builders.

Functions (not module-level constants) so importing never touches jax
device state.  Hardware model: TPU v5e pod = 16x16 = 256 chips;
multi-pod = 2 pods = 512 chips with a leading "pod" axis.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} are "
            "visible; the dry-run entrypoint must set "
            'XLA_FLAGS="--xla_force_host_platform_device_count=512" before '
            "any jax import (see launch/dryrun.py)")
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), axes)


def make_data_mesh(n_devices: int | None = None) -> Mesh:
    """The seizure system's 1-D ``("data",)`` mesh over the first
    ``n_devices`` of ``jax.devices()`` (all of them by default).

    Every mesh of the repo is built here, with ``AxisType.Auto`` axes
    spelled out: ``jax.make_mesh`` now defaults to ``Explicit`` axes,
    under which the sharding of every intermediate becomes part of its
    type, and the training and serving programs declare shardings only
    at their jit boundaries."""
    if n_devices is None:
        n_devices = len(jax.devices())
    return _auto_mesh((n_devices,), ("data",))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny ("data", "model") mesh over the devices that exist (tests)."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape: tuple, axes: tuple[str, ...]) -> Mesh:
    devices = jax.devices()
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {n} devices but only "
            f"{len(devices)} are visible"
        )
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes),
        devices=devices[:n],
    )


# Hardware constants for the roofline (TPU v5e; see brief).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
HBM_BYTES = 16 * 1024**3        # v5e HBM capacity
