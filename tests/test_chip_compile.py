"""Compile the solver's chip path for a described TPU v5e, running nothing.

Each case lowers and compiles for one chip of a ``v5e:2x2`` topology that
``jax.experimental.topologies`` describes without a chip attached: the
Mosaic and XLA TPU compilers refuse here what they would refuse on the
chip (an unaligned tile, a scalar stored to VMEM, a program over the
device's memory), at no chip time.

* one ``_feasibility_batch`` per family under the XLA policy (the
  default ``auto`` resolves to it), at Graph500 scale 20 widths
  (edgefactor 16, seed 1: 1,048,576 vertices, 15,702,278 edges) with
  four bounds, the ``Solver.solve`` default; dense-sub at scale 10,
  the largest that compiles in seconds (its compile time and temp bytes
  grow with the edge count: ROADMAP §1.5); match's and vcover's MWU
  loops hold no scatter and no sort;
* one case per Pallas kernel that runs on the chip, at 2^20 and at
  15.7M elements.

The topology is described in a module fixture, never while a module is
imported: only the worker that runs this file loads the TPU compiler.
"""
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import MWUOptions, Problem
from repro.api.solver import _feasibility_batch
from repro.core.operators import (
    AdjacencyPlusId,
    Incidence,
    InterweavedId,
    Transposed,
    VertexEdgePair,
)
from repro.kernels import dispatch

G500_20 = (1 << 20, 15_702_278)  # (vertices, edges) of kron(20, seed=1, edgefactor=16)
G500_10 = (1 << 10, 16 * (1 << 10))  # dense-sub: an upper bound on kron-10's edges
BOUNDS = 4  # Solver.solve's default batch_width


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler here"
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _problem(family: str, n: int, m: int, sharding) -> Problem:
    """The family's Problem with abstract leaves at (n vertices, m edges)."""
    edges = dict(u=_sds(sharding, (m,), jnp.int32), v=_sds(sharding, (m,), jnp.int32))
    bounds = dict(lo=_sds(sharding, ()), hi=_sds(sharding, ()))
    if family == "match":
        return Problem(
            name="match", kind="packing", sense="max", bound_mode="objective_covering",
            P=Incidence(**edges, n_vertices=n), c=_sds(sharding, (m,)), n_vars=m, **bounds,
        )
    if family == "vcover":
        return Problem(
            name="vcover", kind="covering", sense="min", bound_mode="objective_packing",
            C=Transposed(Incidence(**edges, n_vertices=n)), c=_sds(sharding, (n,)), n_vars=n,
            **bounds,
        )
    if family == "dom-set":
        return Problem(
            name="dom-set", kind="covering", sense="min", bound_mode="objective_packing",
            C=AdjacencyPlusId(**edges, n_vertices=n), c=_sds(sharding, (n,)), n_vars=n,
            **bounds,
        )
    return Problem(
        name="dense-sub", kind="densest", sense="min", bound_mode="scale_packing",
        P=VertexEdgePair(**edges, n_vertices=n), C=InterweavedId(n_edges=m), n_vars=2 * m,
        **bounds,
    )


@pytest.mark.parametrize(
    "family, size",
    [("match", G500_20), ("vcover", G500_20), ("dom-set", G500_20), ("dense-sub", G500_10)],
)
def test_solve_batch_compiles_for_v5e(one_chip, family, size):
    """The batched MWU solve under the default policy compiles and fits a chip."""
    policy = dispatch.resolve("auto")
    assert policy == dispatch.XLA_POLICY
    problem = _problem(family, *size, one_chip)
    with jax.enable_x64(False):
        t0 = time.perf_counter()
        compiled = _feasibility_batch.lower(
            problem, _sds(one_chip, (BOUNDS,)), MWUOptions(eps=0.1), None, kernels=policy
        ).compile()
        seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    print(f"{family} {size}: compile {seconds:.1f} s, {used / 2**30:.2f} GiB, temp {mem.temp_size_in_bytes} B")
    assert used < 16 * 10**9, f"{family}: {used} bytes do not fit one v5e chip"
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # no Pallas under XLA
    if family in ("match", "vcover"):
        # the incidence scatter direction reduces over an endpoint order
        # sorted once per launch: the MWU loop scatters and sorts nothing
        assert _loop_op_kinds(text) & {"scatter", "sort"} == set()


def _loop_op_kinds(text: str) -> set[str]:
    """The kinds of every op in the program's while bodies (nested calls too)."""
    from repro.tracecheck.hlo_ir import parse_hlo, reachable, while_ops

    mod = parse_hlo(text)
    comps = {c for w in while_ops(mod) for c in reachable(mod.comps, w["body"])}
    assert comps, "no while body in the program"
    return {op.kind for c in comps for op in mod.comps[c].ops}


def _kernel_call(name: str, n: int, sharding):
    from repro.kernels.axpy_reduce.kernel import axpy_reduce_pallas
    from repro.kernels.linesearch_probe.kernel import linesearch_probe_pallas
    from repro.kernels.softmax_weights.kernel import softmax_weights_pallas

    vec, scalar = _sds(sharding, (n,)), _sds(sharding, ())
    if name == "softmax_weights":
        return (lambda v, e: softmax_weights_pallas(v, e, sign=-1.0, interpret=False)), (vec, scalar)
    if name == "linesearch_probe":
        return (
            lambda y, dy, a, e: linesearch_probe_pallas(y, dy, a, e, sign=1.0, interpret=False),
            (vec, vec, scalar, scalar),
        )
    return (lambda y, dy, a: axpy_reduce_pallas(y, dy, a, interpret=False)), (vec, vec, scalar)


@pytest.mark.parametrize("n", [1 << 20, G500_20[1]])
@pytest.mark.parametrize("name", ["softmax_weights", "linesearch_probe", "axpy_reduce"])
def test_kernel_compiles_for_v5e(one_chip, name, n):
    """The kernel lowers through Mosaic (scalars via SMEM and vector blocks)."""
    fn, args = _kernel_call(name, n, one_chip)
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
