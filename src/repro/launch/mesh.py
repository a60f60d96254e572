"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: a leading 'pod'
axis, (pod=2, data=16, model=16) = 512 chips; batch shards over
('pod', 'data') and the model axis stays intra-pod (ICI), so the only
inter-pod (DCI) collective is the DP gradient reduction — the standard
multi-pod posture.

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before *any* device query).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def fit_mesh(n_devices: Optional[int] = None, *,
             model: Optional[int] = None) -> jax.sharding.Mesh:
    """The largest valid ``('data', 'model')`` mesh the host actually has.

    ``make_production_mesh`` hard-codes 256/512 chips and simply cannot be
    constructed on a 1–8 device host; everything that wants a mesh sized
    to reality (``launch.serve --shard``, the multi-stream bench, tests on
    forced-host-device subprocesses) goes through here instead.

    ``n_devices`` caps how many devices to use (default: all available —
    never more than the host has).  ``model`` pins the tensor-parallel
    axis; by default it is the largest power-of-two divisor of the device
    count with ``model**2 <= n`` — balanced, and degenerating to
    ``(n, 1)`` on non-power-of-two counts so the mesh always builds:

        1 -> (1, 1)   2 -> (2, 1)   4 -> (2, 2)   8 -> (4, 2)
        16 -> (4, 4)  64 -> (8, 8)  256 -> (16, 16)  6 -> (3, 2)
    """
    avail = jax.device_count()
    n = avail if n_devices is None else min(int(n_devices), avail)
    if n < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if model is not None:
        model = int(model)
        if model < 1 or n % model:
            raise ValueError(
                f"model axis {model} does not divide {n} devices")
    else:
        model = 1
        while n % (model * 2) == 0 and (model * 2) ** 2 <= n:
            model *= 2
    return make_mesh((n // model, model), ("data", "model"))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """A mesh whose axes are all ``Auto``: the compiler places what the
    partition specs leave open, and eager slicing of a sharded result (a
    served batch's real rows) reshards instead of raising."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def single_device_mesh() -> jax.sharding.Mesh:
    return make_mesh((1, 1), ("data", "model"))


def describe(mesh: jax.sharding.Mesh) -> dict:
    return {"axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "n_devices": int(mesh.devices.size)}
