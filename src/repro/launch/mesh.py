"""Production mesh construction (multi-pod dry-run spec).

A FUNCTION, not a module constant: importing this module never touches
jax device state. The dry-run sets XLA_FLAGS before importing jax to
fabricate 512 host devices; real deployments get the same shapes from
actual TPU topology.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "sharding_for"]


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) single pod (256 chips) or (2,16,16) two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto.

    ``devices`` selects an explicit device subset (e.g. the first
    ``pod * data`` of ``jax.devices()`` for a :class:`repro.dist.MeshPlan`
    smaller than the host); ``jax.make_mesh`` orders it for the chips'
    physical topology.
    """
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def sharding_for(mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree on this mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, PartitionSpec),
    )
