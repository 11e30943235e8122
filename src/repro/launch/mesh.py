"""Production meshes. Functions (never module-level constants) so importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod outer axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """A mesh whose axes are all `Auto`: the model's `constrain` calls
    (`dist.sharding.constrain`) only take Auto axes, and `jax.make_mesh`
    defaults to Explicit ones."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    n_data = min(n_data, n)
    n_model = max(1, min(n_model, n // max(1, n_data)))
    return make_mesh((n_data, n_model), ("data", "model"))
