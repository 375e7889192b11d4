"""Backend dispatch for the Pallas kernel pack (the MWU hot-path switch).

This module is the single place where "which implementation runs this
vector op" is decided. Three layers cooperate:

1. **Host-side resolution** — :func:`resolve` turns a backend *request*
   (``"auto" | "pallas" | "xla"``, from ``MWUOptions.kernel_backend`` or
   the ``REPRO_KERNEL_BACKEND`` env var) into a concrete, hashable
   :class:`KernelPolicy`. It reads ``jax.default_backend()`` and MUST be
   called outside ``jax.jit`` so a CPU→TPU device switch can never serve
   a stale cached choice: callers bake the resolved policy into their
   jit cache key as a static argument (``core.mwu.solve`` and
   ``repro.api.Solver.solve_batch`` both do).
2. **Trace-scoped policy** — :func:`use_policy` installs the resolved
   policy in a context variable for the duration of one solve trace;
   ``core.operators`` / ``core.smoothing`` / ``core.stepsize`` /
   ``core.mwu`` consult it via :func:`choose` at trace time. The default
   policy is pure XLA, so operators used outside a solve behave exactly
   as before.
3. **Per-op gate** — even under a ``pallas`` policy an individual call
   falls back to XLA when the kernel cannot serve it: every gather on a
   real TPU (the single-block ``incidence_gather`` kernel indexes a 1-D
   VMEM ref, which Mosaic cannot lower: "Only 2D gather is supported"),
   gathers whose vertex vector exceeds :data:`VMEM_VERTEX_LIMIT`,
   float64 on a real TPU (no f64 VPU; interpret mode keeps f64 for CPU
   CI parity), line-search probes below f64 (the kernel returns lse,
   and Psi/Phi as a difference of two lse cancel in f32; the XLA probe
   takes them as ``log1p`` of a sum that does not), or masked
   reductions (the mask-aware paths stay on XLA — handled at the call
   sites). Every decision is counted in
   :func:`stats`, each gated fallback with its reason, so tests and
   ``benchmarks/bench_breakdown.py`` can prove the pallas path is
   active rather than silently falling back.

``"auto"`` resolves to XLA on every platform, TPU included: no chip
measurement yet shows a kernel of the pack beating XLA, so the kernels
run only on an explicit ``"pallas"`` request.

The pallas entry points are wrapped in ``jax.custom_batching.custom_vmap``
with an XLA batch rule: ``Solver.solve_batch`` and the ``repro.lpserve``
lanes vmap the whole MWU ``lax.while_loop`` across bounds/instances, and
the batched lanes then run the (vmap-composable, still fused-by-XLA)
reference path while unbatched solves keep the Mosaic kernels.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .axpy_reduce.kernel import axpy_reduce_pallas
from .axpy_reduce.ref import axpy_reduce_ref
from .incidence_gather.kernel import incidence_gather_pallas
from .incidence_gather.ref import incidence_gather_ref
from .linesearch_probe.kernel import linesearch_probe_pallas
from .linesearch_probe.ref import linesearch_probe_ref
from .softmax_weights.kernel import softmax_weights_pallas
from .softmax_weights.ref import softmax_weights_ref

__all__ = [
    "KernelPolicy",
    "XLA_POLICY",
    "BACKENDS",
    "ENV_VAR",
    "VMEM_VERTEX_LIMIT",
    "VMEM_BYTES_PER_CORE",
    "VMEM_HEADROOM_BYTES",
    "vmem_budget_bytes",
    "vmem_vertex_limit",
    "resolve",
    "resolve_impl",
    "use_policy",
    "active_policy",
    "choose",
    "stats",
    "reset_stats",
    "gather_pallas",
    "softmax_pallas",
    "probe_pallas",
    "axpy_pallas",
]

ENV_VAR = "REPRO_KERNEL_BACKEND"
BACKENDS = ("auto", "pallas", "xla")

# Single-block gather keeps the whole vertex vector w resident in VMEM.
# A TPU core has ~16 MiB of VMEM; at 3M f32 vertices w occupies 12 MiB,
# leaving >= 4 MiB for the double-buffered (8, 128) edge-index and output
# tiles the grid streams. (4M vertices — the figure an old kernel.py
# docstring quoted — would fill VMEM exactly and leave no tile headroom.)
VMEM_VERTEX_LIMIT = 3_000_000

# The budget the limits above are derived from, shared with
# repro.tracecheck's vmem-footprint rule so the static linter and the
# runtime gate can never disagree about what "fits": a TPU core's VMEM
# minus headroom for Mosaic scratch/semaphores and scalar prefetch.
VMEM_BYTES_PER_CORE = 16 * 2**20
VMEM_HEADROOM_BYTES = 2**20


def vmem_budget_bytes() -> int:
    """Max estimated block footprint a dispatched kernel may occupy."""
    return VMEM_BYTES_PER_CORE - VMEM_HEADROOM_BYTES


def vmem_vertex_limit(dtype) -> int:
    """Vertex cap for the VMEM-resident gather, scaled by element size."""
    return VMEM_VERTEX_LIMIT * 4 // jnp.dtype(dtype).itemsize


@dataclass(frozen=True)
class KernelPolicy:
    """A concrete, hashable backend choice (usable as a jit static arg).

    ``backend`` is ``"pallas"`` or ``"xla"`` — never ``"auto"``; the
    resolution happened in :func:`resolve`. ``interpret`` runs the
    pallas kernels through the Pallas interpreter (pure XLA lowering),
    which is how CPU CI exercises the exact kernel code path.
    """

    backend: str = "xla"
    interpret: bool = False


XLA_POLICY = KernelPolicy("xla", False)


def resolve(request: str | None = "auto") -> KernelPolicy:
    """Resolve a backend request into a concrete :class:`KernelPolicy`.

    Precedence: an explicit ``"pallas"`` / ``"xla"`` request wins; for
    ``"auto"`` (or ``None``) the ``REPRO_KERNEL_BACKEND`` env var is
    consulted, then the default, which is xla on every platform until a
    chip measurement shows a kernel winning. Call this OUTSIDE
    ``jax.jit`` and pass the result through as a static argument —
    ``jax.default_backend()`` read inside a traced function is frozen
    into the jit cache and goes stale when the device set changes.
    """
    req = request or "auto"
    if req == "auto":
        req = os.environ.get(ENV_VAR, "") or "auto"
    if req not in BACKENDS:
        raise ValueError(f"kernel backend must be one of {BACKENDS}, got {req!r}")
    if req in ("auto", "xla"):
        return XLA_POLICY
    return KernelPolicy("pallas", interpret=jax.default_backend() != "tpu")


_ACTIVE: contextvars.ContextVar[KernelPolicy] = contextvars.ContextVar(
    "repro_kernel_policy", default=XLA_POLICY
)


@contextlib.contextmanager
def use_policy(policy: KernelPolicy):
    """Install ``policy`` for the enclosed (trace-time) region."""
    token = _ACTIVE.set(policy)
    try:
        yield policy
    finally:
        _ACTIVE.reset(token)


def active_policy() -> KernelPolicy:
    return _ACTIVE.get()


# -- dispatch accounting ---------------------------------------------------
# Counts trace-time decisions per op; benchmarks and tests use this to
# assert the pallas path is genuinely active (not silently falling back).
# A pallas request the gate sent to XLA is also counted by reason, under
# the op's "fallback" key.
_STATS: dict[str, dict] = {}

# Why a gated op left the pallas path (keys of stats()[op]["fallback"]).
GATHER_NO_TPU_LOWERING = "gather: 1-D VMEM gather has no Mosaic lowering"
F64_ON_TPU = "f64: no f64 vector unit on TPU"
OVER_VMEM = "gather: vertex vector over the VMEM limit"
PROBE_CANCELS = "probe: a difference of two lse cancels below f64"


def _note(op: str, impl: str, reason: str | None = None, key: str = "fallback") -> None:
    d = _STATS.setdefault(op, {"pallas": 0, "xla": 0})
    d[impl] += 1
    if reason is not None:
        fb = d.setdefault(key, {})
        fb[reason] = fb.get(reason, 0) + 1


def note_scatter(ordered: bool) -> None:
    """Count one traced incidence scatter direction under ``stats()["scatter"]``.

    Its ``"order"`` entry says where the endpoint order came from:
    ``"ordered"`` (computed once per launch) or ``"inline"`` (computed in
    place, outside a solve).
    """
    _note("scatter", "xla", "ordered" if ordered else "inline", key="order")


def reset_stats() -> None:
    _STATS.clear()


def stats() -> dict[str, dict]:
    return {
        op: {k: dict(v) if isinstance(v, dict) else v for k, v in d.items()}
        for op, d in _STATS.items()
    }


def _gate(op: str, policy: KernelPolicy, n: int, dtype) -> tuple[str, str | None]:
    """Per-op feasibility of the pallas path, from static shape/dtype.

    Returns ``(impl, reason)``: ``reason`` says why a pallas policy's op
    had to take XLA, and is None otherwise.
    """
    if policy.backend != "pallas":
        return "xla", None
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.floating) and dt.itemsize >= 8 and not policy.interpret:
        return "xla", F64_ON_TPU
    if op == "probe" and dt.itemsize < 8:
        return "xla", PROBE_CANCELS
    if op == "gather" and not policy.interpret:
        return "xla", GATHER_NO_TPU_LOWERING
    if op == "gather" and n > vmem_vertex_limit(dtype):
        return "xla", OVER_VMEM  # w no longer fits VMEM single-block
    return "pallas", None


def choose(op: str, x) -> str:
    """Trace-time per-op decision under the active policy (records stats).

    ``x`` is the vector whose length/dtype gates the kernel: the vertex
    vector for ``"gather"``, the reduced vector for ``"softmax"`` /
    ``"probe"`` / ``"axpy"``.
    """
    impl, reason = _gate(op, active_policy(), x.shape[0], x.dtype)
    _note(op, impl, reason)
    return impl


def resolve_impl(op: str, impl: str, *, n: int, dtype) -> tuple[str, bool]:
    """Host-side resolution for the standalone ``ops.py`` dispatchers.

    Returns ``(impl, interpret)`` with ``impl`` concrete. An explicit
    ``"pallas"``/``"xla"`` request is honored as-is (tests force the
    kernel path regardless of platform); only ``"auto"`` consults the
    env var, platform, and the per-op gate. Lives outside the jitted
    inner functions so repeated calls re-read the platform.
    """
    interpret = jax.default_backend() != "tpu"
    if impl == "auto":
        impl, _ = _gate(op, resolve("auto"), n, dtype)
    return impl, interpret


# -- vmap-composable pallas entry points -----------------------------------
def _bcast(x, batched: bool, axis_size: int):
    return x if batched else jax.lax.broadcast(x, (axis_size,))


@functools.lru_cache(maxsize=None)
def _gather_fn(interpret: bool):
    @jax.custom_batching.custom_vmap
    def gather(u, v, w):
        return incidence_gather_pallas(u, v, w, interpret=interpret)

    @gather.def_vmap
    def _rule(axis_size, in_batched, u, v, w):  # noqa: ARG001
        # Batched lanes (solve_batch / lpserve) take the XLA gather —
        # vmap-composable and still one fused HLO per lane.
        u, v, w = (
            _bcast(a, b, axis_size) for a, b in zip((u, v, w), in_batched)
        )
        return jax.vmap(incidence_gather_ref)(u, v, w), True

    return gather


@functools.lru_cache(maxsize=None)
def _softmax_fn(sign: float, interpret: bool):
    @jax.custom_batching.custom_vmap
    def softmax(v, eta):
        return softmax_weights_pallas(v, eta, sign=sign, interpret=interpret)

    @softmax.def_vmap
    def _rule(axis_size, in_batched, v, eta):  # noqa: ARG001
        v, eta = (_bcast(a, b, axis_size) for a, b in zip((v, eta), in_batched))
        lse, w = jax.vmap(lambda vv, ee: softmax_weights_ref(vv, ee, sign))(v, eta)
        return (lse, w), (True, True)

    return softmax


@functools.lru_cache(maxsize=None)
def _probe_fn(sign: float, interpret: bool):
    @jax.custom_batching.custom_vmap
    def probe(y, dy, alpha, eta):
        return linesearch_probe_pallas(y, dy, alpha, eta, sign=sign, interpret=interpret)

    @probe.def_vmap
    def _rule(axis_size, in_batched, y, dy, alpha, eta):  # noqa: ARG001
        y, dy, alpha, eta = (
            _bcast(a, b, axis_size) for a, b in zip((y, dy, alpha, eta), in_batched)
        )
        out = jax.vmap(lambda *a: linesearch_probe_ref(*a, sign))(y, dy, alpha, eta)
        return out, (True, True, True)

    return probe


@functools.lru_cache(maxsize=None)
def _axpy_fn(interpret: bool):
    @jax.custom_batching.custom_vmap
    def axpy(y, dy, alpha):
        return axpy_reduce_pallas(y, dy, alpha, interpret=interpret)

    @axpy.def_vmap
    def _rule(axis_size, in_batched, y, dy, alpha):  # noqa: ARG001
        y, dy, alpha = (
            _bcast(a, b, axis_size) for a, b in zip((y, dy, alpha), in_batched)
        )
        out = jax.vmap(axpy_reduce_ref)(y, dy, alpha)
        return out, (True, True, True)

    return axpy


def gather_pallas(u, v, w):
    """``g_e = w[u_e] + w[v_e]`` through the Pallas kernel (vmap-safe)."""
    return _gather_fn(active_policy().interpret)(u, v, w)


def softmax_pallas(v, eta, sign: float = 1.0):
    """``(lse, softmax(sign*eta*v))`` through the fused kernel (vmap-safe)."""
    return _softmax_fn(float(sign), active_policy().interpret)(v, jnp.asarray(eta, v.dtype))


def probe_pallas(y, dy, alpha, eta, sign: float = 1.0):
    """One fused line-search probe sweep: ``(lse, slope, min_v)`` (vmap-safe)."""
    return _probe_fn(float(sign), active_policy().interpret)(
        y, dy, jnp.asarray(alpha, y.dtype), jnp.asarray(eta, y.dtype)
    )


def axpy_pallas(y, dy, alpha):
    """``(y + alpha*dy, min, max)`` in one fused sweep (vmap-safe)."""
    return _axpy_fn(active_policy().interpret)(y, dy, jnp.asarray(alpha, y.dtype))
