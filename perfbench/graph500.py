"""The Graph500 Kronecker generator, kept with the benchmark.

``kron`` is a copy of the program's ``repro.graphs.kron`` (Graph500
parameters A/B/C/D = .57/.19/.19/.05, edgefactor 16, vertices relabelled by
a random permutation) together with the canonicalisation of
``repro.graphs.Graph.from_edges`` (self loops dropped, each undirected edge
once as u < v, sorted). A test holds the copy to the program's generator, so
a change there cannot move the benchmark's graphs unseen.
"""
from __future__ import annotations

import numpy as np

A, B, C = 0.57, 0.19, 0.19  # D = 1 - A - B - C = 0.05


def canonical_edges(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(u, v) int32 with u < v, self loops and duplicates dropped, sorted."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    _, idx = np.unique(lo * n + hi, return_index=True)
    return lo[idx].astype(np.int32), hi[idx].astype(np.int32)


def kron(scale: int, seed: int, edgefactor: int = 16) -> tuple[int, np.ndarray, np.ndarray]:
    """(n, u, v) of the Graph500 Kronecker graph with 2**scale vertices."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edgefactor * n
    ij = np.zeros((2, m), np.int64)
    ab = A + B
    c_norm = C / (1 - ab)
    a_norm = A / ab
    for ib in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ij[0] += (1 << ib) * ii_bit
        ij[1] += (1 << ib) * jj_bit
    perm = rng.permutation(n)
    ij = perm[ij]
    u, v = canonical_edges(n, ij.T)
    return n, u, v
