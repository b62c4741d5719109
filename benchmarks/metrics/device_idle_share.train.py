"""Device: 1 - union of device-operation intervals over the traced
window, averaged over the chips used (training cells)."""
from benchmarks import trace_reduce


def read(run):
    if run.kind != "train":
        return None
    return trace_reduce.idle_share(run.trace)
