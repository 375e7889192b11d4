"""Differential jaxpr prover: canonicalize two traces and diff them.

The repo makes two parity claims that until now were enforced only by
runtime tests (bit-equal outputs on one seed) and prose:

* **dist-identity** — on a 1-device :class:`~repro.dist.mesh.MeshPlan`,
  ``DistSolver.solve_batch`` traces the *same program* as
  ``Solver.solve_batch`` (the wrappers are skipped entirely, DESIGN
  contract of PR 4). Bit-equal outputs on one input do not prove the
  programs match; an op-for-op structural diff of the canonicalized
  jaxprs does, for every input.
* **backend parity** — ``Solver.solve`` traced under the ``pallas``
  policy may differ from the ``xla`` trace *only inside the dispatched
  kernel regions*: every divergent region must either contain a
  ``pallas_call`` (the kernel side) or consist purely of vector math
  (the XLA reference expression for the same op). Loop structure,
  collectives, callbacks and dtypes must be identical — a refactor that
  perturbs the while body outside a dispatch site fails the gate even
  when both backends still produce correct numbers.

Canonicalization (:func:`canonical_tokens`): alpha-rename variables in
order of first appearance, render avals as ``dtype[shape]``, sort the
operands of commutative primitives, drop trace-incidental params
(names, source info, unhashable backend objects), and flatten nested
jaxprs (while bodies, branches, jit calls) into the token stream with
structural brackets so a sequence diff aligns loop bodies. Call-like
wrapper eqns that are the *sole* content of a jaxpr (``jit`` around
``shard_map`` around the body, from jitting) are unwrapped first, which
is what lets the mesh-wrapped DistSolver program be compared op-for-op
against the plain Solver body.

Diffing comes in two granularities. :func:`diff_tokens` aligns flat
token streams (``difflib.SequenceMatcher``) — exact, used for the
all-or-nothing dist-identity check. :func:`hierarchical_regions` aligns
eqn *headers* level by level and recurses into matched containers
(while bodies, cond branches, jit shells), so a divergence deep inside
a loop body is scoped to that body instead of derailing the global
alignment — that is what lets :func:`check_backend_parity` classify
each divergence by its deep primitive content. Both report through the
standard :class:`~repro.tracecheck.rules.Finding` machinery (rules
``jaxpr-parity-dist`` / ``jaxpr-parity-backend``).
"""
from __future__ import annotations

import difflib
import re
from dataclasses import dataclass

from .jaxpr_scan import CALLBACK_PRIMS, COLLECTIVE_PRIMS
from .rules import ERROR, Finding

__all__ = [
    "canonical_tokens",
    "diff_tokens",
    "hierarchical_regions",
    "DiffRegion",
    "check_dist_identity",
    "check_backend_parity",
    "DIST_PARITY_RULE",
    "BACKEND_PARITY_RULE",
]

DIST_PARITY_RULE = "jaxpr-parity-dist"
BACKEND_PARITY_RULE = "jaxpr-parity-backend"

# primitives whose operand order is mathematically irrelevant; sorting
# them makes `a + b` vs `b + a` canonical-equal
_COMMUTATIVE = frozenset({"add", "mul", "max", "min", "and", "or", "xor", "add_any"})

# call-like wrappers that are transparent when they are a jaxpr's sole
# content: jitting adds a jit shell, DistSolver adds a shard_map shell
_TRANSPARENT_WRAPPERS = frozenset({"jit", "shard_map", "closed_call", "core_call", "remat2", "custom_vmap_call"})

# params that vary per trace without changing the program
_DROP_PARAMS = frozenset({
    "name", "source_info", "inline", "keep_unused", "donated_invars",
    "in_shardings", "out_shardings", "in_layouts", "out_layouts",
    "resource_env", "compiler_options_kvs", "ctx_mesh", "mesh",
    "name_and_src_info", "debug_info", "interpret", "backend", "device",
    "sharding",
})

_DISPATCH_PRIMS = frozenset({"pallas_call", "custom_vmap_call"})


def _jaxpr_of(x):
    return x.jaxpr if hasattr(x, "jaxpr") else x


def _unwrap(jaxpr):
    """Descend through sole-eqn transparent wrappers (jit/shard_map shells)."""
    jaxpr = _jaxpr_of(jaxpr)
    while len(jaxpr.eqns) == 1 and jaxpr.eqns[0].primitive.name in _TRANSPARENT_WRAPPERS:
        eqn = jaxpr.eqns[0]
        inner = None
        for v in eqn.params.values():
            if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
                inner = _jaxpr_of(v)
                break
        if inner is None:
            break
        jaxpr = inner
    return jaxpr


def _aval_str(v) -> str:
    aval = getattr(v, "aval", None)
    if aval is None:
        return "?"
    s = str(aval)
    # strip weak-type / named-shape noise that varies across jax versions
    return re.sub(r"\{[^}]*\}", "", s)


class _Namer:
    def __init__(self):
        self.names: dict[int, str] = {}

    def __call__(self, v) -> str:
        if type(v).__name__ == "Literal" or hasattr(v, "val"):
            val = getattr(v, "val", None)
            try:
                size = val.size  # 0-d array literal
            except AttributeError:
                size = 1
            if size <= 1:
                return f"lit({val})"
            return f"lit[{_aval_str(v)}]"
        key = id(v)
        if key not in self.names:
            self.names[key] = f"v{len(self.names)}"
        return self.names[key]


def _fmt_param(v) -> str:
    if isinstance(v, (type(None), bool, int, float, str)):
        return repr(v)
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_fmt_param(x) for x in v) + ")"
    if isinstance(v, dict):
        return "{" + ",".join(f"{k}:{_fmt_param(x)}" for k, x in sorted(v.items())) + "}"
    if hasattr(v, "dtype") and hasattr(v, "shape"):
        return f"arr[{getattr(v, 'dtype', '?')}{tuple(getattr(v, 'shape', ()))}]"
    try:
        import numpy as _np

        if isinstance(v, _np.dtype):
            return str(v)
    except ImportError:  # pragma: no cover
        pass
    return f"<{type(v).__name__}>"


def _emit(jaxpr, namer: _Namer, out: list[str]) -> None:
    jaxpr = _jaxpr_of(jaxpr)
    for v in list(getattr(jaxpr, "constvars", ())) + list(jaxpr.invars):
        namer(v)
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [namer(v) for v in eqn.invars]
        if prim in _COMMUTATIVE:
            ins = sorted(ins)
        subs = []
        params = []
        for k in sorted(eqn.params):
            if k in _DROP_PARAMS:
                continue
            v = eqn.params[k]
            vs = v if isinstance(v, (tuple, list)) else (v,)
            if any(hasattr(x, "eqns") or hasattr(x, "jaxpr") for x in vs):
                subs.extend((k, x) for x in vs if hasattr(x, "eqns") or hasattr(x, "jaxpr"))
                continue
            params.append(f"{k}={_fmt_param(v)}")
        outs = [f"{namer(v)}:{_aval_str(v)}" for v in eqn.outvars]
        out.append(f"{prim}[{' '.join(params)}]({','.join(ins)})->({','.join(outs)})")
        for k, sub in subs:
            out.append(f"{prim}:{k}{{")
            # sub-jaxpr variables are a fresh scope
            _emit(sub, _Namer(), out)
            out.append(f"}}{prim}:{k}")


def canonical_tokens(jaxpr, *, unwrap: bool = True) -> list[str]:
    """Canonical token stream of a (Closed)Jaxpr (see module docstring)."""
    jaxpr = _unwrap(jaxpr) if unwrap else _jaxpr_of(jaxpr)
    out: list[str] = []
    _emit(jaxpr, _Namer(), out)
    return out


@dataclass
class DiffRegion:
    """One divergent run between two canonical token streams."""

    kind: str  # replace | delete | insert
    a_start: int
    a_tokens: list[str]
    b_start: int
    b_tokens: list[str]

    def prims(self, side: str) -> set[str]:
        toks = self.a_tokens if side == "a" else self.b_tokens
        out = set()
        for t in toks:
            m = re.match(r"\}?([\w.\-]+?)(?:\[|:|\{)", t)
            if m:
                out.add(m.group(1))
        return out

    def summary(self, n: int = 3) -> str:
        def clip(toks):
            shown = [t[:90] for t in toks[:n]]
            more = f" …+{len(toks) - n}" if len(toks) > n else ""
            return "; ".join(shown) + more

        return f"a[{self.a_start}]: {clip(self.a_tokens) or '∅'}  <->  b[{self.b_start}]: {clip(self.b_tokens) or '∅'}"


def diff_tokens(a: list[str], b: list[str]) -> list[DiffRegion]:
    """Non-equal opcode runs of a sequence alignment of two token streams."""
    sm = difflib.SequenceMatcher(a=a, b=b, autojunk=False)
    regions = []
    for tag, i1, i2, j1, j2 in sm.get_opcodes():
        if tag == "equal":
            continue
        regions.append(DiffRegion(
            kind=tag, a_start=i1, a_tokens=a[i1:i2], b_start=j1, b_tokens=b[j1:j2],
        ))
    return regions


# ------------------------------------------------------------ the checks --
def _finding(rule, artifact, message, *, key="", severity=ERROR, **detail) -> Finding:
    return Finding(rule=rule, severity=severity, artifact=artifact,
                   message=message, key=key, detail=detail)


def check_dist_identity(jaxpr_solver, jaxpr_dist, artifact: str) -> list[Finding]:
    """Prove an identity-plan DistSolver trace ≡ the plain Solver trace.

    Both jaxprs are canonicalized (the dist side's jit/shard_map shells
    unwrap) and must be token-for-token equal; any divergence is an
    error finding carrying the first few divergent regions.
    """
    a = canonical_tokens(jaxpr_solver)
    b = canonical_tokens(jaxpr_dist)
    regions = diff_tokens(a, b)
    if not regions:
        return []
    head = regions[:4]
    msg = (
        f"identity-MeshPlan DistSolver trace diverges from Solver in "
        f"{len(regions)} region(s) — the 1-device parity contract is broken: "
        + " | ".join(r.summary() for r in head)
    )
    return [_finding(
        DIST_PARITY_RULE, artifact, msg, key="diverged",
        n_regions=len(regions),
        regions=[{"kind": r.kind, "a_start": r.a_start, "b_start": r.b_start,
                  "a": r.a_tokens[:6], "b": r.b_tokens[:6]} for r in head],
    )]


# -- hierarchical diff (backend parity) ------------------------------------
# Containers recurse level-by-level so a divergence deep inside a while
# body is scoped to that body instead of derailing the global alignment.
# Their level-header deliberately drops invars and const-count params:
# the pallas path changes which closure consts a loop body captures, but
# the carried state (outvars) must match for the loops to be "the same
# loop". Transparent containers (jit shells jnp emits, cond branches of
# one op's implementation, custom_vmap wrappers) are not structural by
# themselves — only their *deep* content (loops, collectives, callbacks)
# is held against a region.
_CLASSIFY_STRUCTURAL = (
    frozenset({"while", "scan"}) | COLLECTIVE_PRIMS | CALLBACK_PRIMS
)


def _sub_jaxprs_of(eqn) -> list:
    subs = []
    for k in sorted(eqn.params):
        v = eqn.params[k]
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "eqns") or hasattr(x, "jaxpr"):
                subs.append(_jaxpr_of(x))
    return subs


def _deep_prims(eqns) -> set[str]:
    out: set[str] = set()
    stack = list(eqns)
    while stack:
        eqn = stack.pop()
        out.add(eqn.primitive.name)
        for sub in _sub_jaxprs_of(eqn):
            stack.extend(sub.eqns)
    return out


def _level_header(eqn, namer: _Namer) -> str:
    prim = eqn.primitive.name
    outs = ",".join(_aval_str(v) for v in eqn.outvars)
    if _sub_jaxprs_of(eqn):
        return f"{prim}->({outs})"
    ins = [namer(v) for v in eqn.invars]
    if prim in _COMMUTATIVE:
        ins = sorted(ins)
    named_outs = ",".join(f"{namer(v)}:{_aval_str(v)}" for v in eqn.outvars)
    return f"{prim}({','.join(ins)})->({named_outs})"


def hierarchical_regions(jaxpr_a, jaxpr_b) -> list[tuple[str, "DiffRegion"]]:
    """(path, region) pairs of a container-scoped structural diff.

    Aligns the two eqn sequences level by level; matched container pairs
    (same primitive, same output avals) recurse into their sub-jaxprs
    with the path extended (``while/0`` = first sub-jaxpr of the matched
    while). Regions carry raw eqn lists so callers can classify them by
    deep primitive content.
    """
    out: list[tuple[str, DiffRegion]] = []

    def walk(ja, jb, path):
        ea, eb = list(_jaxpr_of(ja).eqns), list(_jaxpr_of(jb).eqns)
        na, nb = _Namer(), _Namer()
        for v in list(getattr(_jaxpr_of(ja), "constvars", ())) + list(_jaxpr_of(ja).invars):
            na(v)
        for v in list(getattr(_jaxpr_of(jb), "constvars", ())) + list(_jaxpr_of(jb).invars):
            nb(v)
        ha = [_level_header(e, na) for e in ea]
        hb = [_level_header(e, nb) for e in eb]
        sm = difflib.SequenceMatcher(a=ha, b=hb, autojunk=False)
        for tag, i1, i2, j1, j2 in sm.get_opcodes():
            if tag == "equal":
                for ea_i, eb_i in zip(ea[i1:i2], eb[j1:j2]):
                    sa, sb = _sub_jaxprs_of(ea_i), _sub_jaxprs_of(eb_i)
                    if len(sa) != len(sb):
                        out.append((path, DiffRegion(
                            "replace", i1, [f"{ea_i.primitive.name}:{len(sa)} sub-jaxprs"],
                            j1, [f"{eb_i.primitive.name}:{len(sb)} sub-jaxprs"],
                        )))
                        continue
                    for k, (xa, xb) in enumerate(zip(sa, sb)):
                        walk(xa, xb, f"{path}/{ea_i.primitive.name}.{k}")
            else:
                r = DiffRegion(tag, i1, ha[i1:i2], j1, hb[j1:j2])
                r.a_eqns = ea[i1:i2]  # raw eqns ride along for deep classification
                r.b_eqns = eb[j1:j2]
                out.append((path, r))

    walk(_unwrap(jaxpr_a), _unwrap(jaxpr_b), "")
    return out


def check_backend_parity(jaxpr_xla, jaxpr_pallas, artifact: str) -> list[Finding]:
    """The pallas trace may differ from xla only inside dispatch regions.

    Every divergent region must be explainable by the kernel dispatch:
    one side (deep-)contains a ``pallas_call``/``custom_vmap_call``, or
    both sides are pure vector math (the two implementations of one
    dispatched op). A region whose deep content touches structural
    primitives (loops, collectives, callbacks) on either side is an
    error — the backends no longer run the same algorithm.
    """
    regions = hierarchical_regions(jaxpr_xla, jaxpr_pallas)
    bad = []
    for path, r in regions:
        da = _deep_prims(getattr(r, "a_eqns", []))
        db = _deep_prims(getattr(r, "b_eqns", []))
        if (da | db) & _DISPATCH_PRIMS:
            continue  # the dispatched kernel region itself
        structural = (da | db) & _CLASSIFY_STRUCTURAL
        if structural:
            bad.append((path, r, sorted(structural)))
    if not bad:
        return []
    head = bad[:4]
    msg = (
        f"{len(bad)} pallas-vs-xla divergence region(s) outside the "
        "dispatched kernel regions touch structural primitives "
        f"({sorted(set().union(*(set(s) for _, _, s in head)))}) — the two "
        "backends no longer trace the same algorithm: "
        + " | ".join(f"at {p or '<top>'}: {r.summary()}" for p, r, _ in head)
    )
    return [_finding(
        BACKEND_PARITY_RULE, artifact, msg, key="structural-drift",
        n_regions=len(regions), n_bad=len(bad),
        regions=[{"path": p, "kind": r.kind, "prims": s,
                  "a": r.a_tokens[:6], "b": r.b_tokens[:6]} for p, r, s in head],
    )]
