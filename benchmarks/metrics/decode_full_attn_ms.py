"""Model step: milliseconds of ONE decode step that jit_decode spends in
its FULL-attention layers' attention: self time under the ``attn_full``
scope and the parts named inside it (the family's ``FULL_PARTS``:
kv_append, kv_gather, attn_scores, attn_pv: the block loop over the
pages), all the full layers together, over the same executions and
steps as decode_sliding_attn_ms (the family's
``decode_parts_by_rounds``). Beside that metric it says what a layer
that keeps the whole context costs a step against one that keeps a
window. None without a joined trace, for a family without such parts
or a program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "FULL_PARTS"):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got:
        return None
    took = fam.under(got, fam.FULL_PARTS)
    return 1e3 * took / got["steps"] if took else None
