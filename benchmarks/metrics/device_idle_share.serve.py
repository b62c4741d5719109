"""Device: 1 - union of device-operation intervals over the traced
window, averaged over the chips used (serving cells). With 16 of 32
layers the host's share of a step is about twice a deployment's."""
from benchmarks import trace_reduce


def read(run):
    if run.kind != "serve":
        return None
    return trace_reduce.idle_share(run.trace)
