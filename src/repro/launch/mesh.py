"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the "pod" axis
is pure data parallelism across the cross-pod links (where gradient
compression and the ring schedules in distributed/collectives.py apply).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state; callers opt in.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

__all__ = ["make_production_mesh", "mesh_shape", "require_devices"]


def mesh_shape(multi_pod: bool = False) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    if multi_pod:
        return (2, 16, 16), ("pod", "data", "model")
    return (16, 16), ("data", "model")


def require_devices(n: int):
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, found {len(devs)} — the dry-run entrypoint "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "BEFORE importing jax (launch/dryrun.py does this)"
        )
    return devs[:n]


def make_production_mesh(*, multi_pod: bool = False):
    """The target mesh: (16, 16) single-pod or (2, 16, 16) multi-pod."""
    import jax

    shape, axes = mesh_shape(multi_pod)
    n = int(np.prod(shape))
    devs = require_devices(n)
    return jax.make_mesh(shape, axes, devices=devs)
