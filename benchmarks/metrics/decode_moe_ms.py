"""Model step: milliseconds of one decode step under the mixture's four
scopes (the family's ``MOE_SCOPES``: moe_router, moe_dispatch,
moe_experts, moe_combine, which the program's mixture module names
around its parts): self time of jit_decode's
operations by their metadata's scope, over the steps decode_step_ms
divides by (benchmarks/trace_parts.py). A part of decode_dense_ms.
Needs the trace itself (``run.trace_dir``, --trace 2); None for a
program that names no such scope."""
from benchmarks import trace_parts


def scopes_of(run):
    return getattr(getattr(run, "family", None), "MOE_SCOPES", ())


def read(run):
    if run.kind != "serve" or not scopes_of(run):
        return None
    got = trace_parts.for_run(run, "jit_decode")
    step = trace_parts.decode_step_parts(run)
    if not got or not step or not step["step_ms"]:
        return None
    under = [got["parts"][s] for s in scopes_of(run) if s in got["parts"]]
    if not under:
        return None
    steps = 1e3 * got["module_s"] / step["step_ms"]
    return 1e3 * sum(under) / steps
