"""Jaxpr walkers for the tracecheck rules.

The jaxpr is the pre-compilation view of a traced program: primitives
like ``pallas_call``, ``psum`` and ``io_callback`` are still visible as
themselves (after XLA compilation on CPU they disappear into loops,
all-reduces or host custom-calls whose shape is backend-dependent), so
every rule about *which primitives the trace contains* runs here, and
only compiled-artifact facts (trip constants, f64 op survival,
custom-call targets) run on the HLO text IR (:mod:`.hlo_ir`).

The central helper is :func:`iter_eqns`, a recursive walk over every
equation in a jaxpr nest — through ``jit`` bodies, ``cond`` branches,
``shard_map``/``custom_vmap_call`` call jaxprs, and ``while`` loops —
tagging each equation with whether it sits inside a ``while`` body or
condition (the solver's hot loop).
"""
from __future__ import annotations

from typing import Iterator

from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = [
    "iter_eqns",
    "find_eqns",
    "count_primitives",
    "sub_jaxprs",
    "COLLECTIVE_PRIMS",
    "CALLBACK_PRIMS",
]

# SPMD collectives a loop body may (or may not) be allowed to contain.
COLLECTIVE_PRIMS = frozenset(
    {
        "psum",
        "pmax",
        "pmin",
        "pbroadcast",
        "ppermute",
        "all_gather",
        "all_to_all",
        "psum_scatter",
        "reduce_scatter",
    }
)

# Host round-trips: every one of these inside the MWU while body stalls
# the device per iteration (the exact class of regression the trace hook
# opt-in exists to contain).
CALLBACK_PRIMS = frozenset(
    {
        "io_callback",
        "pure_callback",
        "python_callback",
        "callback",
        "debug_callback",
        "debug_print",
        "host_callback_call",
        "outside_call",
        "infeed",
        "outfeed",
        "device_put",  # explicit transfers traced into the loop
    }
)


def sub_jaxprs(eqn) -> Iterator[Jaxpr]:
    """Every jaxpr nested in an equation's params (any call-like prim)."""
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for x in vs:
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def iter_eqns(jaxpr: Jaxpr | ClosedJaxpr, in_while: bool = False) -> Iterator[tuple]:
    """Yield ``(eqn, in_while)`` over the whole nest.

    ``in_while`` is True for equations inside any ``while`` body *or
    condition* (a host callback in the condition is just as much a
    per-iteration stall as one in the body).
    """
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, in_while
        sub = in_while or eqn.primitive.name == "while"
        for j in sub_jaxprs(eqn):
            yield from iter_eqns(j, sub)


def find_eqns(jaxpr, name: str, in_while_only: bool = False) -> list:
    """All equations binding primitive ``name`` (optionally loop-scoped)."""
    return [
        eqn
        for eqn, in_w in iter_eqns(jaxpr)
        if eqn.primitive.name == name and (in_w or not in_while_only)
    ]


def count_primitives(jaxpr, names, in_while_only: bool = False) -> dict[str, int]:
    """Occurrence count per primitive name (only names present are keyed)."""
    counts: dict[str, int] = {}
    for eqn, in_w in iter_eqns(jaxpr):
        if in_while_only and not in_w:
            continue
        n = eqn.primitive.name
        if n in names:
            counts[n] = counts.get(n, 0) + 1
    return counts
