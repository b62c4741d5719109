"""Model step: milliseconds of one decode step under the delta-rule
layer's four scopes (the family's ``KDA_SCOPES``: kda_conv, kda_gates,
kda_recurrence, kda_out, which the program's linear-attention module
names around its parts; the q/k/v/o projections are not among them):
self time of jit_decode's operations by their metadata's scope, over the
steps decode_step_ms divides by (benchmarks/trace_parts.py). A part of
decode_dense_ms, as decode_moe_ms is. Needs the trace itself
(``run.trace_dir``, --trace 2); None for a family without such scopes
or a program that names none."""
from benchmarks import trace_parts


def read(run):
    scopes = getattr(getattr(run, "family", None), "KDA_SCOPES", ())
    if run.kind != "serve" or not scopes:
        return None
    got = trace_parts.for_run(run, "jit_decode")
    step = trace_parts.decode_step_parts(run)
    if not got or not step or not step["step_ms"]:
        return None
    under = [got["parts"][s] for s in scopes if s in got["parts"]]
    if not under:
        return None
    steps = 1e3 * got["module_s"] / step["step_ms"]
    return 1e3 * sum(under) / steps
