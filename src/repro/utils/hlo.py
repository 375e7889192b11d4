"""Trip-count-aware HLO-text analyzer for the roofline terms.

Why this exists: ``compiled.cost_analysis()`` visits each ``while`` body
exactly once, so a model scanned over L layers under-reports FLOPs,
bytes and (entirely absent) collective traffic by ~L x. The dry-run's
roofline (EXPERIMENTS.md §Roofline) therefore derives its three terms
from the *scheduled HLO text* of the compiled executable:

  * FLOPs       — 2 * numel(out) * K for every dot (batch/contracting
                  dims decoded from the dot attributes), plus a
                  1-flop/element estimate for fusion outputs;
  * HBM bytes   — per top-level op: operand + output sizes, where
                  operands of slice-like access patterns (dynamic-slice
                  / dynamic-update-slice / gather, including when fused)
                  are charged at their slice size — this is post-fusion
                  HBM traffic, not intra-fusion register traffic;
  * collective wire bytes per device — ring formulas per op kind:
        all-reduce         2 (g-1)/g * size
        all-gather           (g-1)/g * size          (size = output)
        reduce-scatter       (g-1)   * size          (size = output)
        all-to-all           (g-1)/g * size
        collective-permute             size

  with every ``while(cond, body)`` contribution multiplied by the trip
  count recovered from the condition computation (the constants feeding
  its loop-bound compare — exact for lax.scan/fori loops).

The HLO text parser itself lives in :mod:`repro.tracecheck.hlo_ir`,
shared with the static-analysis gate so the roofline and the linter
read one IR. Validated against closed-form expectations in
tests/test_hlo_analyzer.py.

:func:`op_scopes` reads the same text for the ``jax.named_scope`` each
instruction came from, so a profiler trace's device ops can be summed by
scope (the solver's are ``incidence.scatter``, ``incidence.gather`` and
``mwu.linesearch``).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from ..tracecheck.hlo_ir import (
    Computation,
    Op,
    group_size,
    parse_hlo,
    shape_bytes,
    shape_dims,
    trip_count,
)

__all__ = ["analyze_hlo", "HloReport", "op_scopes"]

_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
_SKIP_BYTES = {
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast", "iota",
    "after-all", "broadcast", "reshape", "while", "conditional", "call",
    "custom-call", "partition-id", "replica-id", "domain", "opt-barrier",
}


@dataclass
class HloReport:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_wire_bytes: float = 0.0
    collective_breakdown: dict = field(default_factory=dict)  # kind -> bytes
    dot_flops: float = 0.0
    fusion_flops: float = 0.0
    n_collectives: int = 0
    while_trips: dict = field(default_factory=dict)
    # (kind, output type, group size, trip-multiplied wire bytes) top items
    top_collectives: list = field(default_factory=list)

    def as_dict(self):
        return {
            "flops": self.flops,
            "dot_flops": self.dot_flops,
            "fusion_flops": self.fusion_flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_wire_bytes": self.collective_wire_bytes,
            "collective_breakdown": dict(self.collective_breakdown),
            "n_collectives": self.n_collectives,
            "while_trips": dict(self.while_trips),
            "top_collectives": list(self.top_collectives),
        }


def _dot_flops(op: Op, comp: Computation) -> float:
    out_numel = 1
    for d in shape_dims(op.type_str):
        out_numel *= d
    # contraction size from lhs operand shape
    lhs_name = op.operands[0] if op.operands else None
    lhs = comp.by_name.get(lhs_name)
    k = 1
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.rest)
    if lhs is not None and m and m.group(1):
        dims = shape_dims(lhs.type_str)
        for ci in m.group(1).split(","):
            ci = int(ci)
            if ci < len(dims):
                k *= dims[ci]
    return 2.0 * out_numel * k


_PASS_THROUGH = {"bitcast", "reshape", "copy", "transpose", "convert", "bitcast-convert"}


def _fusion_param_charges(comps, fusion_comp: str) -> dict[int, float]:
    """Byte charge per fusion-parameter position for slice-accessed params.

    A parameter whose every use-path flows only through pass-through ops
    (bitcast/reshape/copy/transpose/convert) into the *sliced operand* of
    a dynamic-slice / gather / dynamic-update-slice is charged at the sum
    of the slice sizes (actual HBM traffic), not the full buffer — this
    is how scanned layer stacks read their per-iteration slice.
    Positions absent from the result are charged at full size.
    """
    comp = comps.get(fusion_comp)
    if comp is None:
        return {}
    param_pos: dict[str, int] = {}
    for op in comp.ops:
        if op.kind == "parameter":
            m = re.match(r"\s*(\d+)", op.rest)
            if m:
                param_pos[op.name] = int(m.group(1))
    # users map: name -> list[(op, operand_index)]
    users: dict[str, list] = {}
    for op in comp.ops:
        for i, o in enumerate(op.operands):
            users.setdefault(o, []).append((op, i))

    charges: dict[int, float] = {}
    for pname, pos in param_pos.items():
        ok = True
        slice_bytes = 0.0
        stack = [pname]
        seen = set()
        while stack and ok:
            cur = stack.pop()
            if cur in seen:
                continue
            seen.add(cur)
            for op, i in users.get(cur, []):
                if op.kind in _PASS_THROUGH:
                    stack.append(op.name)
                elif op.kind in ("dynamic-slice", "gather") and i == 0:
                    slice_bytes += shape_bytes(op.type_str)
                elif op.kind == "dynamic-update-slice" and i == 0:
                    # in-place window update: charged via the update operand
                    upd = comp.by_name.get(op.operands[1])
                    slice_bytes += shape_bytes(upd.type_str) if upd else shape_bytes(op.type_str)
                else:
                    ok = False
                    break
        if ok and slice_bytes > 0:
            charges[pos] = slice_bytes
    return charges


def _op_bytes(op: Op, comp: Computation, comps) -> float:
    """Post-fusion HBM bytes for one top-level op."""
    out_b = shape_bytes(op.type_str)
    if op.kind in ("dynamic-slice", "gather"):
        return 2.0 * out_b  # read slice + write output
    if op.kind == "dynamic-update-slice":
        upd = comp.by_name.get(op.operands[1]) if len(op.operands) > 1 else None
        ub = shape_bytes(upd.type_str) if upd is not None else out_b
        return 2.0 * ub  # in-place: read+write the updated window
    total = float(out_b)
    charges: dict[int, float] = {}
    if op.kind == "fusion":
        m = re.search(r"calls=%([\w.\-]+)", op.rest)
        if m:
            charges = _fusion_param_charges(comps, m.group(1))
            inner = comps.get(m.group(1))
            if inner is not None:
                # fusion rooted in an in-place window update (e.g. the
                # remat stash write of a scanned layer stack): the write
                # traffic is the update slice, not the whole buffer.
                for iop in inner.ops:
                    if iop.kind == "dynamic-update-slice" and shape_bytes(
                        iop.type_str
                    ) == out_b:
                        upd = inner.by_name.get(iop.operands[1]) if len(iop.operands) > 1 else None
                        if upd is not None:
                            total = float(shape_bytes(upd.type_str))
                        break
    for i, name in enumerate(op.operands):
        src = comp.by_name.get(name)
        if src is None:
            continue
        if i in charges:
            total += min(charges[i], shape_bytes(src.type_str))
            continue
        total += shape_bytes(src.type_str)
    return total


def analyze_hlo(text, num_partitions: int = 1, *, root: str | None = None) -> HloReport:
    """Cost accounting over ``text`` (HLO string or pre-parsed HloModule).

    ``root`` selects the computation to account from (default: ENTRY).
    The tracecheck cost model passes a ``while`` *body* computation here
    to get per-iteration cost — nested loops inside the body are still
    trip-multiplied, the selected loop itself is counted once.
    """
    mod = text if hasattr(text, "comps") else parse_hlo(text)
    comps = mod.comps
    rep = HloReport()
    memo: dict[str, tuple] = {}
    entry = root if root is not None else mod.entry

    ZERO = (0.0, 0.0, 0.0, 0.0, {}, 0, [])

    def analyze_comp(name: str):
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        if comp is None:
            return ZERO
        dflops = fflops = bytes_ = wire = 0.0
        coll: dict[str, float] = {}
        ncoll = 0
        items: list = []

        def absorb(res, mult=1):
            nonlocal dflops, fflops, bytes_, wire, ncoll
            df, ff, bb, bw, bc, bn, bi = res
            dflops += mult * df
            fflops += mult * ff
            bytes_ += mult * bb
            wire += mult * bw
            ncoll += mult * bn
            for k, v in bc.items():
                coll[k] = coll.get(k, 0.0) + mult * v
            for kind, ts, g, wb in bi:
                items.append((kind, ts, g, mult * wb))

        for op in comp.ops:
            kind = op.kind
            if kind == "while":
                cond = re.search(r"condition=%([\w.\-]+)", op.rest)
                body = re.search(r"body=%([\w.\-]+)", op.rest)
                # trip_count returns None for data-dependent loops; the
                # roofline then counts the body once (a lower bound)
                trips = trip_count(comps, cond.group(1)) if cond else None
                rep.while_trips[op.name] = trips
                if body:
                    absorb(analyze_comp(body.group(1)), trips or 1)
                continue
            if kind in ("call", "conditional", "async-start", "async-done"):
                for target in re.findall(r"(?:calls|to_apply|branch_computations)=\{?%?([\w.\-,% ]+)\}?", op.rest):
                    for t in re.findall(r"[\w.\-]+", target):
                        if t in comps:
                            absorb(analyze_comp(t))
                continue
            # collectives (match base kind; e.g. all-reduce-start)
            base = next((c for c in _COLLECTIVES if kind.startswith(c)), None)
            if base is not None:
                g = group_size(op.rest, num_partitions)
                size = shape_bytes(op.type_str)
                if base == "all-reduce":
                    w = 2.0 * (g - 1) / max(g, 1) * size
                elif base == "all-gather":
                    w = (g - 1) / max(g, 1) * size
                elif base == "reduce-scatter":
                    w = float(g - 1) * size
                elif base == "all-to-all":
                    w = (g - 1) / max(g, 1) * size
                else:
                    w = float(size)
                wire += w
                ncoll += 1
                coll[base] = coll.get(base, 0.0) + w
                items.append((base, op.type_str.strip(), g, w))
                bytes_ += _op_bytes(op, comp, comps)
                continue
            if kind == "dot":
                dflops += _dot_flops(op, comp)
                bytes_ += _op_bytes(op, comp, comps)
                continue
            if kind == "fusion":
                m2 = re.search(r"calls=%([\w.\-]+)", op.rest)
                if m2 and m2.group(1) in comps:
                    inner = comps[m2.group(1)]
                    for iop in inner.ops:
                        if iop.kind == "dot":
                            dflops += _dot_flops(iop, inner)
                        elif iop.kind not in _SKIP_BYTES:
                            n = 1
                            for d in shape_dims(iop.type_str):
                                n *= d
                            fflops += n  # 1 flop/element estimate
                bytes_ += _op_bytes(op, comp, comps)
                continue
            if kind in _SKIP_BYTES:
                continue
            bytes_ += _op_bytes(op, comp, comps)
        memo[name] = (dflops, fflops, bytes_, wire, coll, ncoll, items)
        return memo[name]

    df, ff, b, w, c, n, items = analyze_comp(entry)
    rep.dot_flops = df
    rep.fusion_flops = ff
    rep.flops = df + ff
    rep.hbm_bytes = b
    rep.collective_wire_bytes = w
    rep.collective_breakdown = c
    rep.n_collectives = n
    # aggregate identical (kind, type, group) and keep the heaviest 12
    agg: dict = {}
    cnt: dict = {}
    for kind, ts, g, wb in items:
        key = (kind, ts, g)
        agg[key] = agg.get(key, 0.0) + wb
        cnt[key] = cnt.get(key, 0) + 1
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:12]
    rep.top_collectives = [
        {"kind": k[0], "type": k[1][:60], "group": k[2], "wire_bytes": v,
         "count": cnt[k]}
        for k, v in top
    ]
    return rep


def op_scopes(text: str, scopes) -> dict[str, str]:
    """Instruction name -> the innermost of ``scopes`` in its ``op_name``.

    ``text`` is a compiled program (``compiled.as_text()``) and ``scopes``
    names of ``jax.named_scope``. A profiler trace names each device op by
    its instruction in the compiled program (``%fusion.118 = ...``), and a
    fusion keeps the metadata of its root, so this map sorts a trace's
    device time by scope. Instructions under none of ``scopes`` are left
    out. A transform can wrap a scope in the name (``vmap(incidence.scatter)``).
    """
    out = {}
    for comp in parse_hlo(text).comps.values():
        for op in comp.ops:
            m = _OP_NAME_RE.search(op.rest)
            inner = [part for part in re.split(r"[/()]", m.group(1)) if part in scopes] if m else []
            if inner:
                out[op.name] = inner[-1]
    return out
