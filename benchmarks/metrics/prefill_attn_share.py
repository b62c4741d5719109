"""Model step: of the device time of jit_prefill (the chunked-prefill
program: four rows of a chunk each, appended to the rows' pages and
attended over them), the share under the latent attention's scopes (the
family's ``LATENT_ATTN_SCOPES``, as decode_latent_attn_ms): what the
form the prefill call attends in costs beside the projections and the
feed-forwards of its 1,024 tokens. Needs the trace itself
(``run.trace_dir``, --trace 2); None for a family without such scopes
or a program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    scopes = getattr(fam, "LATENT_ATTN_SCOPES", ())
    if run.kind != "serve" or not scopes:
        return None
    got = fam.latent_parts(run, "jit_prefill")
    if not got or not got["module_s"]:
        return None
    under = sum(got["parts"].get(s, 0.0) for s in scopes)
    return 100.0 * under / got["module_s"]
