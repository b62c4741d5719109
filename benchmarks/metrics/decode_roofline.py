"""Model step: the least time one decode step could take on this chip
over the time it took. The least time is the bytes the step must move
(the family's ``decode_step_bytes``, counted in benchmarks/costs.py:
weights once, the head once, the K/V of the tokens really in context;
one chip's share on a sharded mesh) over the chip's published HBM
bandwidth: at 32-64 rows a decode step is bound by bytes, not FLOPs.
Tokens in context are the mean of load_report()'s kv_bytes_in_use over
the traced seconds (whole pages, so at most half a page a slot too
many)."""
from benchmarks import trace_reduce


def read(run):
    if run.kind != "serve" or run.peaks is None:
        return None
    step_s = trace_reduce.loop_step_seconds(run.trace, "jit_decode")
    if not step_s or not run.trace_span or None in run.trace_span:
        return None
    t0, t1 = run.trace_span
    used = [s["kv_bytes_in_use"] for s in run.samples
            if t0 - 1.0 <= s["t"] <= t1 + 1.0]
    if not used:
        return None
    per_token = run.family.kv_bytes_per_token(run.cfg)
    context_tokens = (sum(used) / len(used)) / per_token
    total = run.family.decode_step_bytes(
        run.cfg, context_tokens, run.deployment["max_slots"])
    least_s = total / run.chips / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
