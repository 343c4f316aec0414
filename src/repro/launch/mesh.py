"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
import; tests see the default single device).
"""
from __future__ import annotations

import math

import jax


def _auto_axes(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False) -> "jax.sharding.Mesh":
    """16x16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} exist; "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax")
    return jax.make_mesh(
        shape, axes, devices=devices[:n], axis_types=_auto_axes(len(axes)))


def make_host_mesh(shape=(1, 1), axes=("data", "model")) -> \
        "jax.sharding.Mesh":
    """Tiny mesh over whatever devices exist (tests / smoke runs)."""
    n = math.prod(shape)
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=_auto_axes(len(axes)))
