"""Shared textual-HLO IR: one parser for the roofline analyzer and the linter.

Historically :mod:`repro.utils.hlo` owned a private parser for its
roofline terms; ``repro.tracecheck`` needs the same structure (ops,
computations, while condition/body wiring, trip counts) to lint compiled
programs, so the parser lives here and both consumers import it. The IR
is deliberately *textual*: it parses ``compiled.as_text()`` (post-fusion
scheduled HLO), which is the program XLA actually runs — jaxpr-level
checks see the pre-compilation view instead (:mod:`.jaxpr_scan`).

Structure:

* :class:`Op`           — one instruction (name, result type, kind, raw tail);
* :class:`Computation`  — one ``%comp { ... }`` block with a name index;
* :class:`HloModule`    — all computations + the ``ENTRY`` name;
* :func:`parse_hlo`     — text -> :class:`HloModule`;
* :func:`trip_count`    — loop bound of a ``while`` condition: the max
  integer literal on an operand path *into a compare op* (unrelated
  constants in the condition cannot inflate it — see the regression
  test in tests/test_hlo_analyzer.py);
* :func:`reachable` / :func:`while_ops` / :func:`custom_calls` — graph
  helpers the tracecheck rules and the roofline walker share.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = [
    "DTYPE_BYTES",
    "Op",
    "Computation",
    "HloModule",
    "parse_hlo",
    "shape_bytes",
    "shape_dims",
    "group_size",
    "reachable",
    "trip_count",
    "while_ops",
    "custom_calls",
]

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(?[^=]+?\)?)\s+([a-z][a-z0-9\-]*)\((.*)$"
)
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_CALLS_RE = re.compile(r"(?:calls|to_apply|condition|body)=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def shape_bytes(type_str: str) -> int:
    """Total byte size of every shape literal in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str) -> list[int]:
    """Dims of the first shape literal in an HLO type string."""
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


@dataclass
class Op:
    """One HLO instruction, kept close to its textual form."""

    name: str
    type_str: str
    kind: str
    rest: str  # operands + attrs (raw tail of the line)

    @property
    def operands(self) -> list[str]:
        # operand names appear before the closing paren of the call
        depth = 0
        for i, ch in enumerate(self.rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    head = self.rest[:i]
                    break
                depth -= 1
        else:
            head = self.rest
        return re.findall(r"%([\w.\-]+)", head)

    @property
    def attrs(self) -> str:
        return self.rest

    def called_comps(self) -> list[str]:
        """Computation names this op references (calls/body/condition/branches)."""
        out = _CALLS_RE.findall(self.rest)
        bm = _BRANCHES_RE.search(self.rest)
        if bm:
            out += re.findall(r"%([\w.\-]+)", bm.group(1))
        return out

    def const_int(self) -> int | None:
        """The integer literal of a scalar ``constant(N)`` op, else None."""
        if self.kind != "constant":
            return None
        m = re.match(r"\s*(\d+)\)", self.rest)
        return int(m.group(1)) if m else None


@dataclass
class Computation:
    name: str
    ops: list = field(default_factory=list)
    by_name: dict = field(default_factory=dict)


@dataclass
class HloModule:
    """Parsed module: computations by name plus the ENTRY computation."""

    comps: dict[str, Computation] = field(default_factory=dict)
    entry: str | None = None

    def entry_comp(self) -> Computation | None:
        return self.comps.get(self.entry) if self.entry else None


def parse_hlo(text: str) -> HloModule:
    """Parse ``compiled.as_text()`` into an :class:`HloModule`."""
    mod = HloModule()
    cur: Computation | None = None
    for line in text.splitlines():
        if "/*" in line:  # strip /*index=N*/ tuple comments ('=' breaks _OP_RE)
            line = _COMMENT_RE.sub("", line)
        if cur is None:
            m = _COMP_RE.match(line)
            if m and ("->" in line):
                cur = Computation(name=m.group(1))
                if line.lstrip().startswith("ENTRY"):
                    mod.entry = cur.name
            continue
        if line.startswith("}"):
            mod.comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            op = Op(name=m.group(1), type_str=m.group(2), kind=m.group(3), rest=m.group(4))
            cur.ops.append(op)
            cur.by_name[op.name] = op
    if mod.entry is None and mod.comps:
        mod.entry = list(mod.comps)[-1]
    return mod


def group_size(attrs: str, num_partitions: int) -> int:
    """Participant count of a collective from its replica_groups attr."""
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
    if m:
        return int(m.group(2))
    return max(num_partitions, 1)


def reachable(comps: dict[str, Computation], root: str) -> set[str]:
    """Names of every computation reachable from ``root`` via call edges."""
    seen: set[str] = set()
    stack = [root]
    while stack:
        cn = stack.pop()
        if cn in seen or cn not in comps:
            continue
        seen.add(cn)
        for op in comps[cn].ops:
            stack.extend(op.called_comps())
    return seen


def _fuses_compare(comps: dict[str, Computation], op: Op) -> bool:
    """A ``fusion`` whose fused computation holds a compare.

    XLA wraps a loop condition's compare in a fusion (``%wrapped_compare``)
    whose operands are the induction variable and the bound constant, so
    the fusion stands for the compare in the caller.
    """
    return op.kind == "fusion" and any(
        o.kind == "compare"
        for cn in op.called_comps()
        for o in comps.get(cn, Computation(cn)).ops
    )


def trip_count(comps: dict[str, Computation], cond_name: str) -> int | None:
    """Loop bound recovered from a ``while`` condition computation.

    Only integer constants on an operand path *into a compare op* count
    (the loop-bound test is always a compare against the bound constant,
    possibly inside a fused condition). An unrelated large integer
    literal elsewhere in the condition — a gather dimension, an address
    constant — therefore cannot inflate the estimate, which the previous
    max-literal-anywhere heuristic allowed.

    Returns ``None`` when no compare-fed constant exists (a condition
    comparing two loop-carried values is a genuinely data-dependent
    loop) — callers that need a multiplier must choose their own
    fallback (``trip_count(...) or 1``) instead of this function
    fabricating a bogus bound of 1.
    """
    best: int | None = None
    for cn in reachable(comps, cond_name):
        comp = comps[cn]
        for op in comp.ops:
            if op.kind != "compare" and not _fuses_compare(comps, op):
                continue
            stack = list(op.operands)
            seen: set[str] = set()
            while stack:
                nm = stack.pop()
                if nm in seen:
                    continue
                seen.add(nm)
                src = comp.by_name.get(nm)
                if src is None:
                    continue
                v = src.const_int()
                if v is not None:
                    best = v if best is None else max(best, v)
                    continue
                stack.extend(src.operands)
    return best


def while_ops(mod: HloModule) -> list[dict]:
    """Every ``while`` op in the module, with its wiring and nesting level.

    Returns dicts of ``op``, ``comp`` (owning computation name),
    ``cond`` / ``body`` (computation names or None), and ``top_level``
    (True when the while sits in a computation reachable from ENTRY
    *without* passing through another while's body — i.e. the outer
    loop(s) of the program, for solvers the MWU iteration loop).
    """
    out = []
    body_comps: set[str] = set()
    for comp in mod.comps.values():
        for op in comp.ops:
            if op.kind != "while":
                continue
            body = re.search(r"body=%([\w.\-]+)", op.rest)
            if body:
                body_comps |= reachable(mod.comps, body.group(1))
    for comp in mod.comps.values():
        for op in comp.ops:
            if op.kind != "while":
                continue
            cond = re.search(r"condition=%([\w.\-]+)", op.rest)
            body = re.search(r"body=%([\w.\-]+)", op.rest)
            out.append(
                {
                    "op": op,
                    "comp": comp.name,
                    "cond": cond.group(1) if cond else None,
                    "body": body.group(1) if body else None,
                    "top_level": comp.name not in body_comps,
                }
            )
    return out


def custom_calls(mod: HloModule, within: set[str] | None = None) -> list[tuple[str, str]]:
    """(computation, custom_call_target) pairs, optionally restricted."""
    out = []
    for comp in mod.comps.values():
        if within is not None and comp.name not in within:
            continue
        for op in comp.ops:
            if op.kind != "custom-call":
                continue
            m = re.search(r'custom_call_target="([^"]*)"', op.rest)
            out.append((comp.name, m.group(1) if m else ""))
    return out
