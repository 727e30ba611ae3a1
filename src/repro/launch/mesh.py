"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
import; tests run on the 1-CPU default).  Axes are ``Auto``: callers
shard with ``with mesh:`` + ``with_sharding_constraint``, which
``Explicit`` axes (``jax.make_mesh``'s default) refuse.
"""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(model_degree: int = 1):
    """Whatever this host has — used by tests and CPU examples."""
    n = len(jax.devices())
    data = max(1, n // model_degree)
    return jax.make_mesh(
        (data, model_degree), ("data", "model"), axis_types=_auto(2)
    )
