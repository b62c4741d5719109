"""Model step: of the device time of jit_prefill, the share under the
FULL-attention layers' attention (the family's ``FULL_PARTS``: the chunk
appended to the rows' pages and the block loop over them up to the
longest row's context): two layers of eight, and the part of a call
that grows with the context. Beside prefill_sliding_attn_share. Needs
the trace itself (--trace 2); None for a family without such parts or a
program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "FULL_PARTS"):
        return None
    got = fam.typed_parts(run, "jit_prefill")
    if not got or not got["module_s"]:
        return None
    return 100.0 * fam.under(got, fam.FULL_PARTS) / got["module_s"]
