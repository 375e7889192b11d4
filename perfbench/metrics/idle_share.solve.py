"""Share of the traced window in which the device ran no operation (solve loop)."""


def read(run):
    if run.trace is None or not run.solves:
        return None
    return 100.0 * run.trace.idle_share
