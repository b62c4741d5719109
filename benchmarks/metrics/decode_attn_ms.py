"""Model step: milliseconds of one decode step under the scopes
LlamaAttention names around the KV window (kv_append, kv_gather,
attn_scores, attn_pv; attn_kernel on the Pallas path): self time of
jit_decode's operations by their metadata's scope, over the steps
decode_step_ms divides by (benchmarks/trace_parts.py). Layout copies
count under the scope their metadata names. Needs the trace itself
(``run.trace_dir``, --trace 2)."""
from benchmarks import trace_parts


def read(run):
    if run.kind != "serve":
        return None
    parts = trace_parts.decode_step_parts(run)
    return parts["attn_ms"] if parts else None
