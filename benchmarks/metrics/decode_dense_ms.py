"""Model step: milliseconds of one decode step under the projections
(wq, wk, wv, wo), the MLP, the norms, the head, sampling and rope: the
work that streams the weights. With decode_attn_ms and the rest the
``[parts]`` line logs (operations that name neither scope, such as the
whole-pool copies) it adds up to decode_step_ms. Needs the trace itself
(``run.trace_dir``, --trace 2)."""
from benchmarks import trace_parts


def read(run):
    if run.kind != "serve":
        return None
    parts = trace_parts.decode_step_parts(run)
    return parts["dense_ms"] if parts else None
