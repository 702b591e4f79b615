"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is pure
data parallelism crossing DCI (gradient all-reduce, optionally int8-
compressed — distributed/compression.py).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run pins the device count via XLA_FLAGS before
any jax import; tests import this file under a 1-device CPU)."""
from __future__ import annotations

import jax
import numpy as np



def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (the sharding-in-types
    default would make each axis explicit)."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Largest (data, model) mesh on the CURRENT device set (examples,
    reduced-scale training, elastic restarts)."""
    n = len(jax.devices())
    if model is None:
        model = 1
        for cand in (16, 8, 4, 2):
            if n % cand == 0 and n >= cand:
                model = cand
                break
    return make_mesh((n // model, model), ("data", "model"))


def mesh_num_devices(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
