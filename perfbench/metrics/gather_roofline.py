"""The incidence gather's share of its HBM roofline.

Minimum bytes of every gather-direction call (``opbytes``) over the device
time of the ops that ``opnames/gather.txt`` names, against the chip's HBM
bandwidth. Both LPs gather once per batched iteration. (Vertex cover's
z = Cx0 at a launch's start reads the uniform x0, and XLA does not emit a
gather for it: a v5e trace shows one gather fusion per iteration.)
"""
from perfbench import harness, opbytes, peaks


def moved(x):
    per_call = opbytes.incidence_bytes(x["n_vertices"], x["n_edges"], x["lanes"], x["index_sets"])
    return x["batched_iters"] * per_call


def read(run):
    if run.trace is None or not run.solves:
        return None
    seconds = run.trace.op_seconds(harness.opnames(run, "gather"))
    if seconds <= 0:
        return None
    total = sum(moved(x) for x in run.traced_launches)
    return 100.0 * total / seconds / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
