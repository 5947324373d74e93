"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state, so tests and benches keep their 1-CPU view.
The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import (launch/dryrun.py lines 1-2).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``.

    The repo's sharding code constrains with ``with_sharding_constraint``
    and mixes sharded with replicated operands, which needs Auto axes;
    newer JAX defaults ``make_mesh`` axes to ``Explicit``.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16x16 = 256 chips per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, *,
                   multi_pod: bool = False):
    """Small mesh for CPU tests (requires forced host device count)."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))


def client_axes(mesh) -> tuple:
    """Mesh axes that carry the FedEPM client / batch axis."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_client_groups(mesh) -> int:
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n
