"""Line-search probes per MWU iteration: ``ls_probes_total / mwu_iters_total`` over the solves."""


def read(run):
    iters = sum(s["lane_iters"] for s in run.solves)
    return sum(s["probes"] for s in run.solves) / iters if iters else None
