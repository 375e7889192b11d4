"""Feasibility solves per solve (``Solution.feasibility_calls``), mean over the window's solves."""


def read(run):
    if not run.solves:
        return None
    return sum(s["feasibility_calls"] for s in run.solves) / len(run.solves)
