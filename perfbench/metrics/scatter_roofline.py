"""The incidence scatter's share of the HBM roofline of the chips the cell ran on.

Minimum bytes of every scatter-direction call (``opbytes``, from the shapes
alone) over the device time of the ops that their own program's compiled
HLO puts under the ``incidence.scatter`` scope (``Summary.scope_seconds``),
against the aggregate HBM bandwidth of the chips the cell ran on
(``run.n_devices`` of them): the scope's time is the mean over those chips,
and the bytes are the whole graph's, which an edge-slab run splits among
them. The bytes depend on the shapes alone, so the share reads the same
work whatever code does the scatter. A fusion takes the scope of its root: work fused into a
consumer outside the scope counts there. An op that the trace puts in
no program, and so has no scopes for, is matched by its HLO text against
``opnames/scatter.txt``. Both LPs scatter once per batched
iteration; match also once when a launch starts (y = Px0, on the one x0
that all its lanes share).
"""
from perfbench import harness, opbytes, peaks


def moved(run, x):
    per_call = opbytes.incidence_bytes(x["n_vertices"], x["n_edges"], x["lanes"], x["index_sets"])
    start = opbytes.incidence_bytes(x["n_vertices"], x["n_edges"], 1, x["index_sets"])
    return x["batched_iters"] * per_call + (start if run.cell.config["lp"] == "match" else 0)


def read(run):
    if run.trace is None or not run.solves:
        return None
    seconds = run.trace.scope_seconds("incidence.scatter", harness.opnames(run, "scatter"))
    if not seconds:
        return None
    total = sum(moved(run, x) for x in run.traced_launches)
    return 100.0 * total / seconds / (run.n_devices * peaks.peaks(run.device_kind)["hbm_bytes_per_s"])
