"""Production mesh construction (pure functions — importing this module never
touches jax device state)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512 chips).

    Axes: ``data`` (batch + FSDP), ``model`` (tensor/expert parallel),
    ``pod`` (pure DP across pods; only gradient all-reduce crosses it).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_ring_mesh(n_devices: int | None = None, name: str = "ring") -> Mesh:
    """1-D mesh over all devices — used by the domain-decomposed ring AIDW."""
    n = n_devices or len(jax.devices())
    return _make((n,), (name,))


def make_host_mesh(shape=None, axes=("data", "model")) -> Mesh:
    """Small mesh over whatever devices exist (tests on forced host devices)."""
    n = len(jax.devices())
    if shape is None:
        m = 1
        while m * 2 <= n // (m * 2) and n % (m * 2) == 0:
            m *= 2
        m = m if n % m == 0 else 1
        shape = (n // m, m)
    return _make(shape, axes)
