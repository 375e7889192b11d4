"""Host-side mesh planning for the distributed solver layer.

A :class:`MeshPlan` is to device meshes what
:class:`repro.kernels.dispatch.KernelPolicy` is to kernel backends: a
frozen, hashable description resolved OUTSIDE ``jax.jit`` and passed
around as a static argument, so the jit/shard_map callable cache is
keyed on the concrete mesh shape and can never serve a plan built for a
different device set.

Two axes (paper §5 mapped onto SPMD):

* ``data`` — the embarrassingly-parallel fan-out axis: independent
  feasibility lanes (binary-search bounds, stacked graph instances,
  lpserve lane slots) shard here with zero cross-device communication,
  exactly the MPI rank-level parallelism of the paper's bound sweep.
* ``pod``  — the within-solve axis: one LP's *variable space* is
  slab-partitioned here (:mod:`repro.dist.shard`), with the smax/smin
  coupling completed by per-iteration ``psum``s — the paper's
  edge-partitioned OpenMP+MPI scheme, with the psum standing in for its
  neighbor exchange.

``MeshPlan.build`` constructs the actual ``jax.sharding.Mesh`` over the
first ``pod * data`` host devices (via :func:`repro.launch.mesh.make_mesh`),
and :meth:`MeshPlan.shard_map` wraps ``jax.shard_map`` over it.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from ..launch.mesh import make_mesh

__all__ = ["MeshPlan", "POD_AXIS", "DATA_AXIS"]

POD_AXIS = "pod"
DATA_AXIS = "data"

# one Mesh per plan per process: Mesh construction touches device state,
# and shard_map callables close over the mesh, so identity stability
# keeps the downstream jit caches warm.
_MESH_CACHE: dict["MeshPlan", object] = {}


@dataclass(frozen=True)
class MeshPlan:
    """A hashable (pod, data) mesh request, resolved host-side.

    ``pod`` devices cooperate on each solve (variable-space slabs +
    psum); ``data`` groups run independent lanes. ``MeshPlan()`` is the
    1-device identity plan — the distributed driver run under it is
    bit-identical to the single-device ``Solver`` path.
    """

    pod: int = 1
    data: int = 1

    def __post_init__(self):
        if self.pod < 1 or self.data < 1:
            raise ValueError(f"MeshPlan axes must be >= 1, got pod={self.pod} data={self.data}")

    @property
    def n_devices(self) -> int:
        return self.pod * self.data

    @property
    def axes(self) -> tuple[str, str]:
        return (POD_AXIS, DATA_AXIS)

    def build(self):
        """The concrete ``Mesh`` over the first ``pod * data`` devices."""
        mesh = _MESH_CACHE.get(self)
        if mesh is not None:
            return mesh
        devices = jax.devices()
        if len(devices) < self.n_devices:
            raise ValueError(
                f"MeshPlan(pod={self.pod}, data={self.data}) needs "
                f"{self.n_devices} devices but only {len(devices)} are "
                "visible (on CPU, set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N before importing jax)"
            )
        mesh = make_mesh((self.pod, self.data), self.axes, devices=devices[: self.n_devices])
        _MESH_CACHE[self] = mesh
        return mesh

    def shard_map(self, f, *, in_specs, out_specs, check_vma: bool = False):
        """``jax.shard_map`` over this plan's mesh.

        ``check_vma`` defaults off: the solver's replication invariants
        (constraint-space vectors re-replicate through the operator
        psums) are not expressible to the static rep checker — they are
        asserted numerically by ``tests/test_dist_solver.py`` instead.
        """
        return jax.shard_map(
            f, mesh=self.build(), in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
        )
