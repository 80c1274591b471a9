"""Production mesh construction (single-pod 16x16 and multi-pod 2x16x16).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (device count is locked at first jax init).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 512 if multi_pod else 256
    return _make_mesh(shape, axes, jax.devices()[:n])


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for subprocess-based distributed tests."""
    n = n_data * n_model
    return _make_mesh((n_data, n_model), ("data", "model"), jax.devices()[:n])
