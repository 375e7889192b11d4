"""Device busy milliseconds per batched MWU iteration in the traced window."""


def read(run):
    iters = sum(launch["batched_iters"] for launch in run.traced_launches)
    if run.trace is None or not run.solves or not iters:
        return None
    return 1000.0 * run.trace.busy_s / iters
