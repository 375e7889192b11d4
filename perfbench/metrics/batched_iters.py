"""Batched MWU iterations per solve: each launch costs its slowest lane's iterations."""


def read(run):
    if not run.solves:
        return None
    return sum(launch["batched_iters"] for launch in run.launches) / len(run.solves)
