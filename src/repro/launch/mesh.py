"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2x16x16 = 512 chips (pod, data, model) — the `pod` axis is
the slowest (DCI-connected) dimension; the dry-run proves every program
shards over it.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """A mesh whose axes are all Auto (the sharding rules rely on it)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over the actually-present devices (tests / examples)."""
    n = jax.device_count()
    return _auto_mesh((n // model, model), ("data", "model"))
