"""Production mesh construction.

A FUNCTION (not a module constant) so importing never touches jax device
state. Single pod: 16x16 = 256 chips (v5e pod), axes (data, model).
Multi-pod: 2 x 16 x 16 = 512 chips, axes (pod, data, model) — 'pod' joins
the DP axes (gradient sync crosses DCN).
"""
from __future__ import annotations

import math

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, found {len(devs)} — "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before any "
            "jax import")
    arr = np.asarray(devs[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def make_host_mesh(shape: tuple[int, ...] = (1, 1),
                   axes: tuple[str, ...] = ("data", "model")):
    """Tiny mesh over whatever devices exist — smoke tests / CPU runs."""
    import jax

    n = math.prod(shape)
    arr = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def mesh_context(mesh):
    """Context manager activating ``mesh`` for a trace (``jax.set_mesh``);
    ``None`` yields a null context."""
    import contextlib

    import jax

    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)
