"""The incidence gather's share of the HBM roofline of the chips the cell ran on.

Minimum bytes of every gather-direction call (``opbytes``, from the shapes
alone) over the device time of the ops that their own program's compiled
HLO puts under the ``incidence.gather`` scope (``Summary.scope_seconds``),
against the aggregate HBM bandwidth of the chips the cell ran on
(``run.n_devices`` of them): the scope's time is the mean over those chips,
and the bytes are the whole graph's, which an edge-slab run splits among
them. The bytes depend on the shapes alone, so the share reads the same
work whatever code does the gather. A fusion takes the scope of its root: work fused into a
consumer outside the scope counts there. An op that the trace puts in
no program, and so has no scopes for, is matched by its HLO text against
``opnames/gather.txt``. Both LPs gather once per batched
iteration. (Vertex cover's z = Cx0 at a launch's start reads the uniform
x0, and XLA does not emit a gather for it: a v5e trace shows one gather
fusion per iteration.)
"""
from perfbench import harness, opbytes, peaks


def moved(x):
    per_call = opbytes.incidence_bytes(x["n_vertices"], x["n_edges"], x["lanes"], x["index_sets"])
    return x["batched_iters"] * per_call


def read(run):
    if run.trace is None or not run.solves:
        return None
    seconds = run.trace.scope_seconds("incidence.gather", harness.opnames(run, "gather"))
    if not seconds:
        return None
    total = sum(moved(x) for x in run.traced_launches)
    return 100.0 * total / seconds / (run.n_devices * peaks.peaks(run.device_kind)["hbm_bytes_per_s"])
