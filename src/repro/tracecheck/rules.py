"""Rule/report framework + the solver's performance-invariant rule set.

A :class:`TraceArtifact` is one captured entry point (jaxpr, optionally
compiled HLO, plus the static context it was traced under: kernel
policy, solver options, mesh plan) with a dict of *expectations*
computed at capture time. A :class:`Rule` inspects one artifact and
yields :class:`Finding`s; :func:`run_rules` applies the default rule set.

The shipped rules (each guards one way the paper's per-iteration cost
model silently regresses):

``no-callbacks-in-loop``  no host callbacks / transfers inside the MWU
                          ``while`` (jaxpr prims + HLO custom-call
                          targets); traced artifacts must instead
                          contain their ``io_callback``.
``kernel-path``           ``pallas_call`` present in the loop exactly
                          when the resolved :class:`KernelPolicy` says
                          the kernel pack is active (and with the
                          matching interpret flag), absent under xla
                          and on vmapped lanes (custom_vmap batch rule).
``loop-collectives``      collective count/kind inside the loop body ==
                          the declared pod plan (two ``psum`` + one
                          ``pmax`` per iteration for pod-sharded plans,
                          none for identity plans).
``dtype-discipline``      no f64 avals / weak-type promotions beyond
                          the problem dtype (Python scalar closures are
                          the usual leak).
``trip-count``            the top-level ``while`` trip bound recovered
                          from compiled HLO == ``MWUOptions.max_iter``.
``vmem-footprint``        per-kernel VMEM block footprint (BlockSpecs:
                          resident blocks + double-buffered streaming
                          tiles) within the dispatch layer's budget.

Adding a rule: subclass :class:`Rule`, implement ``check(artifact)``,
append an instance to :data:`DEFAULT_RULES`. Give repeated findings a
stable ``key`` so one baseline entry (see :mod:`.report`) covers them.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp

from . import hlo_ir
from .jaxpr_scan import CALLBACK_PRIMS, COLLECTIVE_PRIMS, count_primitives, find_eqns

__all__ = [
    "ERROR",
    "WARNING",
    "Finding",
    "TraceArtifact",
    "Rule",
    "DEFAULT_RULES",
    "run_rules",
]

ERROR = "error"
WARNING = "warning"


@dataclass
class Finding:
    """One rule violation on one artifact.

    ``fingerprint`` identifies the violation *class* stably across runs
    (no counts or op names that drift with compiler versions), so a
    baseline allowlist entry keeps covering it.
    """

    rule: str
    severity: str
    artifact: str
    message: str
    key: str = ""
    detail: dict = field(default_factory=dict)

    @property
    def fingerprint(self) -> str:
        return f"{self.rule}::{self.artifact}" + (f"::{self.key}" if self.key else "")

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "severity": self.severity,
            "artifact": self.artifact,
            "message": self.message,
            "fingerprint": self.fingerprint,
            "detail": dict(self.detail),
        }


@dataclass
class TraceArtifact:
    """One captured entry point plus the expectations the rules enforce.

    ``expect`` keys consumed by the default rules:

    * ``traced``          — the io_callback trace hook is deliberately on;
    * ``pallas_in_loop``  — kernel pack must be active inside the while
      body (unbatched pallas paths); ``pallas_anywhere`` for loop-free
      kernel artifacts; absent/False -> no pallas_call may appear;
    * ``collectives``     — exact in-loop {prim: count} (missing = {});
    * ``dtype``           — the solve dtype; wider floats are leaks;
    * ``max_iter``        — expected top-level while trip bound.
    """

    name: str
    jaxpr: object | None = None  # ClosedJaxpr
    hlo_text: str | None = None
    policy: object | None = None  # kernels.dispatch.KernelPolicy
    opts: object | None = None  # core.mwu.MWUOptions
    plan: object | None = None  # dist.mesh.MeshPlan
    pod_mode: str | None = None
    expect: dict = field(default_factory=dict)

    _hlo_module: object | None = None

    @property
    def hlo(self) -> hlo_ir.HloModule | None:
        if self.hlo_text is None:
            return None
        if self._hlo_module is None:
            self._hlo_module = hlo_ir.parse_hlo(self.hlo_text)
        return self._hlo_module


class Rule:
    """Base class: one invariant, checked per artifact."""

    name: str = "rule"
    description: str = ""

    def check(self, art: TraceArtifact) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, art, message, *, key="", severity=ERROR, **detail) -> Finding:
        return Finding(
            rule=self.name, severity=severity, artifact=art.name,
            message=message, key=key, detail=detail,
        )


# ------------------------------------------------------------------ rules --
class HostCallbackRule(Rule):
    """No host round-trips inside the hot loop (unless the trace hook is on)."""

    name = "no-callbacks-in-loop"
    description = "no host callbacks / device-to-host transfers inside the MWU while body"

    # pallas custom-call targets are device kernels, not host calls
    _OK_TARGETS = ("tpu_custom_call", "mosaic", "Sharding", "SPMD", "annotate")

    def check(self, art):
        out = []
        traced = bool(art.expect.get("traced"))
        if art.jaxpr is not None:
            counts = count_primitives(art.jaxpr, CALLBACK_PRIMS, in_while_only=True)
            if traced:
                if not counts.get("io_callback"):
                    out.append(self.finding(
                        art, "trace hook expected but no io_callback traced into the loop",
                        key="missing-trace-hook", severity=WARNING,
                    ))
                counts.pop("io_callback", None)
            for prim, n in sorted(counts.items()):
                out.append(self.finding(
                    art,
                    f"{n} `{prim}` host round-trip(s) inside the while loop — "
                    "every MWU iteration now syncs with the host",
                    key=prim, count=n,
                ))
        if art.hlo is not None:
            loop_comps: set[str] = set()
            for w in hlo_ir.while_ops(art.hlo):
                for root in (w["cond"], w["body"]):
                    if root:
                        loop_comps |= hlo_ir.reachable(art.hlo.comps, root)
            for comp, target in hlo_ir.custom_calls(art.hlo, within=loop_comps):
                if any(okay in target for okay in self._OK_TARGETS):
                    continue
                out.append(self.finding(
                    art,
                    f"custom-call `{target}` inside loop computation `{comp}` "
                    "(host callback or un-vetted external call in the hot loop)",
                    key=f"custom-call:{target}", target=target,
                ))
        return out


class KernelPathRule(Rule):
    """The Pallas kernel pack is active exactly when the policy says so."""

    name = "kernel-path"
    description = "pallas_call presence/absence matches the resolved KernelPolicy"

    def check(self, art):
        if art.jaxpr is None:
            return []
        out = []
        in_loop = find_eqns(art.jaxpr, "pallas_call", in_while_only=True)
        anywhere = find_eqns(art.jaxpr, "pallas_call")
        if art.expect.get("pallas_in_loop"):
            if not in_loop:
                out.append(self.finding(
                    art,
                    "KernelPolicy resolves to pallas but no pallas_call was traced "
                    "into the while body — the fused kernel pack silently fell back",
                    key="missing",
                ))
        elif art.expect.get("pallas_anywhere"):
            if not anywhere:
                out.append(self.finding(
                    art, "kernel entry point traced without any pallas_call",
                    key="missing",
                ))
        elif anywhere:
            out.append(self.finding(
                art,
                f"{len(anywhere)} pallas_call(s) traced under an xla/batched policy "
                "(vmapped lanes and xla policies must take the reference path)",
                key="unexpected", count=len(anywhere),
            ))
        interp = getattr(art.policy, "interpret", None)
        if interp is not None:
            for eqn in anywhere:
                if bool(eqn.params.get("interpret")) != bool(interp):
                    out.append(self.finding(
                        art,
                        f"pallas_call interpret={eqn.params.get('interpret')} does not "
                        f"match the resolved policy interpret={interp}",
                        key="interpret-mismatch", severity=WARNING,
                    ))
                    break
        return out


class LoopCollectivesRule(Rule):
    """In-loop collective count/kind == what the pod plan declares."""

    name = "loop-collectives"
    description = "collectives inside the while body match the declared MeshPlan/pod mode"

    def check(self, art):
        if art.jaxpr is None:
            return []
        expected = {k: int(v) for k, v in art.expect.get("collectives", {}).items() if v}
        got = count_primitives(art.jaxpr, COLLECTIVE_PRIMS, in_while_only=True)
        if got == expected:
            return []
        mode = art.pod_mode or "identity"
        return [self.finding(
            art,
            f"in-loop collectives {got or '{}'} != declared {expected or '{}'} for "
            f"pod mode `{mode}` — per-iteration communication changed",
            expected=expected, got=got, pod_mode=mode,
        )]


class DtypeRule(Rule):
    """No f64 ops / weak-type promotions beyond the problem dtype."""

    name = "dtype-discipline"
    description = "no unexpected f64 ops or weak-type promotions in the trace"

    def check(self, art):
        expected = jnp.dtype(art.expect.get("dtype", "float32"))
        if expected.itemsize >= 8:  # f64 solve: nothing wider to leak into
            return []
        out = []
        if art.jaxpr is not None:
            leaks: dict[str, int] = {}
            from .jaxpr_scan import iter_eqns

            for eqn, _ in iter_eqns(art.jaxpr):
                for v in eqn.outvars:
                    dt = getattr(getattr(v, "aval", None), "dtype", None)
                    if dt is not None and jnp.issubdtype(dt, jnp.floating) and jnp.dtype(dt).itemsize > expected.itemsize:
                        leaks[eqn.primitive.name] = leaks.get(eqn.primitive.name, 0) + 1
            if leaks:
                out.append(self.finding(
                    art,
                    f"float ops wider than the {expected.name} problem dtype traced "
                    f"(weak-type promotion leak): {leaks}",
                    key="jaxpr", leaks=leaks,
                ))
        if art.hlo_text is not None:
            n64 = art.hlo_text.count("f64[")
            if n64:
                out.append(self.finding(
                    art,
                    f"{n64} f64 shape(s) survived into compiled HLO of a "
                    f"{expected.name} solve",
                    key="hlo", count=n64,
                ))
        return out


class TripCountRule(Rule):
    """Compiled while trip bound == MWUOptions.max_iter (compile-time check)."""

    name = "trip-count"
    description = "top-level while trip bound in compiled HLO matches MWUOptions.max_iter"

    def check(self, art):
        if art.hlo is None or art.opts is None:
            return []
        max_iter = int(art.expect.get("max_iter", getattr(art.opts, "max_iter", 0)))
        whiles = [w for w in hlo_ir.while_ops(art.hlo) if w["top_level"]]
        if not whiles:
            return [self.finding(
                art,
                "no top-level while loop in compiled HLO — the MWU loop was "
                "unrolled, hoisted or restructured",
                key="missing-loop", severity=WARNING,
            )]
        trips = [hlo_ir.trip_count(art.hlo.comps, w["cond"]) for w in whiles if w["cond"]]
        if max_iter not in trips:
            # None entries are data-dependent loops with no recoverable
            # bound — name them rather than reporting a fabricated 1
            shown = [t if t is not None else "unbounded" for t in trips]
            return [self.finding(
                art,
                f"top-level while trip bound(s) {shown} do not include the "
                f"configured max_iter={max_iter} — the compiled iteration cap "
                "drifted from MWUOptions",
                trips=shown, max_iter=max_iter,
            )]
        return []


class VmemFootprintRule(Rule):
    """Every pallas_call's block footprint fits the dispatch VMEM budget."""

    name = "vmem-footprint"
    description = "BlockSpec footprint (resident + double-buffered tiles) within dispatch headroom"

    def check(self, art):
        if art.jaxpr is None:
            return []
        from ..kernels import dispatch as _kd

        budget = _kd.vmem_budget_bytes()
        out = []
        for eqn in find_eqns(art.jaxpr, "pallas_call"):
            est = self._estimate(eqn)
            if est is None:
                continue
            if est > budget:
                kname = eqn.params.get("name_and_src_info")
                out.append(self.finding(
                    art,
                    f"pallas kernel `{kname}` estimated VMEM footprint "
                    f"{est / 2**20:.2f} MiB exceeds the dispatch budget "
                    f"{budget / 2**20:.2f} MiB "
                    f"(VMEM_BYTES_PER_CORE - VMEM_HEADROOM_BYTES)",
                    key=str(kname).split(" ")[0], bytes=est, budget=budget,
                ))
        return out

    @staticmethod
    def _estimate(eqn) -> int | None:
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            return None
        total = 0
        for bm in getattr(gm, "block_mappings", ()):
            block = tuple(int(d) for d in bm.transformed_block_aval.shape)
            array = bm.array_aval
            nbytes = math.prod(block) * jnp.dtype(array.dtype).itemsize
            # full-array blocks are VMEM-resident once; streamed tiles are
            # double-buffered by the Mosaic pipeline
            resident = block == tuple(int(d) for d in array.shape)
            total += nbytes if resident else 2 * nbytes
        return total


DEFAULT_RULES: list[Rule] = [
    HostCallbackRule(),
    KernelPathRule(),
    LoopCollectivesRule(),
    DtypeRule(),
    TripCountRule(),
    VmemFootprintRule(),
]


def run_rules(artifacts, rules=None) -> list[Finding]:
    """Apply ``rules`` (default: all) to every artifact; findings in order."""
    rules = DEFAULT_RULES if rules is None else rules
    findings: list[Finding] = []
    for art in artifacts:
        for rule in rules:
            findings.extend(rule.check(art))
    return findings
