"""Production mesh factory.

Kept as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — a process that forces a host device count
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) must set it before
first init.

Every mesh in the repo comes from :func:`make_mesh`, which gives each axis
``AxisType.Auto``: the model code places arrays with ``with_sharding_constraint``
and lets GSPMD propagate, which ``Explicit`` axes (``jax.make_mesh``'s default)
refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None):
    """Mesh over the first prod(shape) devices (or ``devices``), Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16x16 (256 chips) or 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (axes present, size 1)."""
    return make_mesh((1, 1), ("data", "model"))


def make_serving_mesh(data: int = 1, model: int = 1):
    """Serving mesh: ``data`` carries slot-pool sharding (DESIGN.md §8),
    ``model`` carries TP. Uses the first data*model devices, so the
    sharded-parity tests can build mesh=(1,) and mesh=(data=4,) side by
    side in one forced-multi-device CPU process."""
    if data * model > jax.device_count():
        raise ValueError(
            f"mesh ({data}x{model}) needs {data * model} devices, have "
            f"{jax.device_count()} (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)")
    return make_mesh((data, model), ("data", "model"))
