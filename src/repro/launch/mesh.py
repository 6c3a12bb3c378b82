"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device state.
Single pod: (16, 16) = ("data", "model") — 256 chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips, the "pod"
axis adds a second data-parallel tier whose gradient reduction crosses DCI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_compat(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis Auto-typed."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax")
    return make_mesh_compat(shape, axes, devices=devices[:n])


def make_local_mesh(shape=(1, 1), axes=("data", "model")):
    """Degenerate mesh over however many local devices exist (tests/examples)."""
    n = 1
    for s in shape:
        n *= s
    return make_mesh_compat(shape, axes, devices=jax.devices()[:n])
