"""Share of the device's busy time spent in the pod path's collectives.

Device seconds of the ops that their own program's compiled HLO puts under
the ``pod.psum`` scope (``Summary.scope_seconds``: the psums that complete
each constraint image, and the pmax and pmin over the edge slabs), over
the busy seconds of the traced window, both the mean over the chips the
cell ran on. None where the run has no trace or no op reads under the
scope: a program without the scope, or a one-chip run.
"""


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    seconds = run.trace.scope_seconds("pod.psum")
    if not seconds:
        return None
    return 100.0 * seconds / run.trace.busy_s
