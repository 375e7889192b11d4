"""Share of launched lane-iterations that a lane still needed, in the bound search.

Lane-iterations done over (lanes x batched iterations) of every launch: a
vmapped while_loop runs every lane until its slowest one stops.
"""


def read(run):
    launched = sum(launch["lanes"] * launch["batched_iters"] for launch in run.launches)
    if not run.solves or not launched:
        return None
    return 100.0 * sum(launch["lane_iters"] for launch in run.launches) / launched
