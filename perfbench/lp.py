"""Build a configuration's graphs and LPs through the program's public entries."""
from __future__ import annotations

import dataclasses

import jax

from . import graph500, reference


def options(config: dict):
    from repro.api import MWUOptions

    return MWUOptions(eps=config["eps"], step_rule=config["step_rule"], max_iter=config["max_iter"])


def graph(config: dict, scale: int, seed: int):
    """(n, u, v) of the configuration's Graph500 graph at ``scale`` from ``seed``."""
    return graph500.kron(scale, seed, config["generator"]["edgefactor"])


def problem(config: dict, n: int, u, v, name: str):
    """The configuration's LP on the graph, as ``repro.graphs.build`` makes it."""
    from repro.graphs import Graph, build

    return build(config["lp"], Graph(n=n, u=u, v=v, name=name))


def easy_problem(problem, device):
    """``problem`` with its bracket moved to where every bound is trivially
    feasible. A whole search on it runs the program's own launches, lane
    splits and certificate at the problem's shapes, a few iterations each,
    so it warms every program a solve of ``problem`` runs."""
    if problem.feasible_side == "lo":
        lo = float(problem.lo) * 1e-6
        hi = 2.0 * lo
    else:
        hi = 4.0 * float(problem.hi)
        lo = hi / 2.0
    return dataclasses.replace(problem, lo=jax.device_put(lo, device), hi=jax.device_put(hi, device))


def bracket(launches: list[dict], bound: float, lo: float, hi: float, feasible_side: str) -> float:
    """How far the bound search stopped from resolving its certified ``bound``.

    The ratio, less 1, between ``bound`` and the nearest bound beyond it that
    the search left unresolved: the least probed bound above it that did not
    end FEASIBLE (or the problem's ``hi``) where bounds below are feasible
    (a maximum), the largest below it (or ``lo``) where bounds above are
    feasible (a minimum). The probes are read from the solve's ``launches``.
    """
    failed = [b for x in launches for b, ok in zip(x["bounds"], x["feasible"]) if not ok]
    if feasible_side == "lo":
        return min([hi] + [b for b in failed if b > bound]) / bound - 1.0
    return bound / max([lo] + [b for b in failed if b < bound]) - 1.0


def check_answers(config: dict, graphs: list, answers: list, answer=lambda x: x) -> tuple[dict, int, int]:
    """(checks, attempted, failed) of every answer against its graph's optimum.

    ``answers`` holds (index into ``graphs``, certified x or None, the
    numbers the driver read itself); None is an answer that never came or
    did not end FEASIBLE. ``answer`` maps each x before the check (the
    control rounds it). ``checks`` gives each number of ``reference`` and of
    the driver, worst over the answers, beside its limit.
    """
    limits = config["limits"]
    optima: dict[int, float] = {}
    worst = {k: 0.0 for k in limits}
    failed = 0
    for i, x, read in answers:
        if x is None:
            failed += 1
            continue
        n, u, v = graphs[i]
        if i not in optima:
            optima[i] = reference.lp_optimum(n, u, v)
        got = dict(reference.CHECKS[config["lp"]](n, u, v, answer(x), optima[i]), **read)
        for k in worst:
            worst[k] = max(worst[k], got[k])
        failed += any(got[k] > limits[k] for k in limits)
    return {k: {"value": worst[k], "limit": limits[k]} for k in limits}, len(answers), failed
