"""The plain reference and the certificate arithmetic, in float64 on the host.

Both LPs of the benchmark have the same optimum. The fractional matching
number of a graph G is half the size of a maximum matching of its bipartite
double cover (vertices V x {0, 1}, an edge (a0, b1) and (b0, a1) for each
edge ab of G), and by LP duality it is also the optimum of the fractional
vertex cover. ``lp_optimum`` computes it with SciPy's Hopcroft-Karp, which
shares no code with the solver under test.

The certificate arithmetic is that of ``chip_smoke.py``: a solver's x is read
back and checked in float64. Each check returns the numbers that decide
``correct``:

* ``violation`` -- how far x breaks its constraints: the largest vertex load
  above 1 (matching) or the largest shortfall of an edge's cover below 1
  (vertex cover), or a negative entry of x, whichever is largest; 0 when x
  is feasible.
* ``gap`` -- how far the certified objective lies from the optimum, as a
  ratio less 1: optimum / objective (matching, a maximum) or objective /
  optimum (vertex cover, a minimum).
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import maximum_bipartite_matching


def lp_optimum(n: int, u: np.ndarray, v: np.ndarray) -> float:
    """The fractional matching number (= fractional vertex cover number)."""
    rows = np.concatenate([u, v])
    cols = np.concatenate([v, u])
    biadj = sp.csr_matrix((np.ones(rows.shape[0], np.int8), (rows, cols)), shape=(n, n))
    mate = maximum_bipartite_matching(biadj, perm_type="column")
    return float((mate >= 0).sum()) / 2.0


def match_check(n: int, u: np.ndarray, v: np.ndarray, x, optimum: float) -> dict:
    """Fractional matching: incidence . x <= 1, x >= 0, against the optimum."""
    x = np.asarray(x, np.float64)
    load = np.bincount(u, x, minlength=n) + np.bincount(v, x, minlength=n)
    objective = float(x.sum())
    violation = max(float(load.max(initial=0.0)) - 1.0, -float(x.min(initial=0.0)), 0.0)
    gap = optimum / objective - 1.0 if objective > 0 else float("inf")
    return {"violation": violation, "gap": gap, "objective": objective}


def vcover_check(n: int, u: np.ndarray, v: np.ndarray, x, optimum: float) -> dict:
    """Fractional vertex cover: x_u + x_v >= 1 on every edge, x >= 0."""
    x = np.asarray(x, np.float64)
    cover = x[u] + x[v]
    objective = float(x.sum())
    violation = max(1.0 - float(cover.min(initial=1.0)), -float(x.min(initial=0.0)), 0.0)
    gap = objective / optimum - 1.0 if optimum > 0 else float("inf")
    return {"violation": violation, "gap": gap, "objective": objective}


CHECKS = {"match": match_check, "vcover": vcover_check}
