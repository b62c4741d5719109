"""Model step: of the device time jit_decode and jit_prefill spend under
the mixture's four scopes, the share that is NOT the experts' matmuls
(moe_experts): the router, the sort and gather that group the rows
(moe_dispatch) and the way back (moe_combine). What a grouped kernel
that takes rows where they lie would save. Needs the trace itself
(--trace 2); None for a program that names no such scope."""
from benchmarks import trace_parts


def read(run):
    scopes = getattr(getattr(run, "family", None), "MOE_SCOPES", ())
    if run.kind != "serve" or not scopes:
        return None
    total = experts = 0.0
    for module in ("jit_decode", "jit_prefill"):
        got = trace_parts.for_run(run, module)
        if got:
            total += sum(got["parts"].get(s, 0.0) for s in scopes)
            experts += got["parts"].get("moe_experts", 0.0)
    return 100.0 * (total - experts) / total if total > 0 else None
