"""Model step: of the device time of jit_prefill (the chunked-prefill
program: four rows of a chunk each), the share under the delta-rule
layer's four scopes (the family's ``KDA_SCOPES``: kda_conv, kda_gates,
kda_recurrence, kda_out, as decode_linear_attn_ms; the q/k/v/o
projections are not among them): what the form a chunk's recurrence is
solved in costs beside the projections and the feed-forwards of the
call's tokens (ops/linear_attention.py ``kda_chunked``: matmuls under a
[C, C] decay mask where the gate is one a head, a [C, C, dk] product on
the vector unit where it is one a channel). Lower is better. Needs the
trace itself (``run.trace_dir``, --trace 2); None for a family without
such scopes or a program that names none."""
from benchmarks import trace_parts


def read(run):
    scopes = getattr(getattr(run, "family", None), "KDA_SCOPES", ())
    if run.kind != "serve" or not scopes:
        return None
    got = trace_parts.for_run(run, "jit_prefill")
    if not got or not got["module_s"]:
        return None
    under = [got["parts"][s] for s in scopes if s in got["parts"]]
    return 100.0 * sum(under) / got["module_s"] if under else None
