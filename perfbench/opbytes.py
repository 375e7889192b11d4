"""The least bytes an incidence operator has to move, from the graph's shapes.

Both directions of the incidence operator of a graph with ``n_vertices``
vertices and ``n_edges`` edges touch the same data. The scatter direction
(``Incidence.matvec``: each edge's value added to both of its endpoints)
reads the two endpoint index arrays, reads one edge vector per lane and
writes one vertex vector per lane. The gather direction
(``Incidence.rmatvec``: each edge reads both endpoints' values) reads the
indices, reads one vertex vector per lane and writes one edge vector per
lane. Lanes that share one graph read its indices once; stacked graphs, one
per lane, read one index set each.

These counts depend on the shapes alone, not on how the program implements
the operation, so a roofline share built on them reads the same work
whatever kernel does it.
"""
from __future__ import annotations


def incidence_bytes(
    n_vertices: int,
    n_edges: int,
    lanes: int,
    index_sets: int = 1,
    index_bytes: int = 4,
    value_bytes: int = 4,
) -> int:
    """Minimum bytes of one call of either direction of the operator."""
    indices = index_sets * 2 * n_edges * index_bytes
    vectors = lanes * (n_edges + n_vertices) * value_bytes
    return indices + vectors
