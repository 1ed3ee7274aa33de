"""Production mesh construction.

A *function*, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before the first
jax call; tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices=None) -> jax.sharding.Mesh:
    """A mesh with Auto axes: the sharding code places params with
    ``NamedSharding``s and leaves the rest to the partitioner."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """(16, 16) = 256-chip pod; (2, 16, 16) = 2 pods = 512 chips.

    ``pod`` is pure data-parallel (the slow inter-pod link is crossed once
    per step by the gradient all-reduce); ``data`` carries DP + FSDP;
    ``model`` carries TP / EP / SP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist right now (tests / examples on 1 CPU)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))
