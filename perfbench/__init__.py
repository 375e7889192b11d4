"""Chip benchmark of the MWU graph-LP solver, driven by ``BENCHMARK.json``.

Everything that decides a number lives here, apart from the program under
test: the Graph500 generator, the plain reference and the certificate
arithmetic, the traffic drivers, the trace reduction, the peak table, the
minimum-bytes functions and one reader per per-layer metric. ``run.py`` is
the entry point.
"""
