"""Model step: milliseconds of one decode step under the latent
attention's scopes (the family's ``LATENT_ATTN_SCOPES``: mla_q, mla_kv
and mla_absorb, which the program's latent-attention module names
around its low-rank query, its latent entry and the absorbed up-
projections, and the shared kv_append, kv_gather, attn_scores and
attn_pv around the page window; the output projection is not among
them): self time of jit_decode's operations by their metadata's scope
(benchmarks/trace_parts.py), over the decode steps the traced runs took
as the family counts them (``decode_steps_traced``: the head's
executions; the page window's loop runs many blocks a step here, which
misleads the count decode_step_ms divides by). Needs the trace itself
(``run.trace_dir``, --trace 2); None for a family without such scopes
or a program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    scopes = getattr(fam, "LATENT_ATTN_SCOPES", ())
    if run.kind != "serve" or not scopes:
        return None
    got = fam.latent_parts(run, "jit_decode")
    steps = got and fam.decode_steps_traced(run)
    if not steps:
        return None
    return 1e3 * sum(got["parts"].get(s, 0.0) for s in scopes) / steps
