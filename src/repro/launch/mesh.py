"""Production meshes.

A function (not a module constant) so importing this module never touches
jax device state — the dry-run driver sets XLA_FLAGS *before* any jax
import, then calls this.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
               devices=None) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int | None = None, devices=None):
    """Small ("data", "model") mesh over ``devices`` (default: every
    device of the host) — tests / local runs."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    model = model or 1
    assert n % model == 0
    return _make_mesh((n // model, model), ("data", "model"), devices)
