"""Seconds to a certified solution: the window's whole time over the solves completed in it."""


def read(run):
    return run.window_s / len(run.solves) if run.solves else None
