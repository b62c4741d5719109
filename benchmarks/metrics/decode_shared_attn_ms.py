"""Model step: milliseconds of ONE decode step that jit_decode spends in
the attention of the layers that READ the one layer's K/V pages: self
time under the ``attn_shared`` scope and the parts named inside it (the
family's ``SHARED_PARTS``: kv_append, the owner's alone; kv_gather,
attn_scores, attn_pv), the owner and its seven readers together, over
exactly the executions benchmarks/trace_dispatch.py matched to their
rounds and the decode steps those rounds dispatched (the family's
``decode_parts_by_rounds``). The projections and ``diff_merge`` (the
subtraction, the sub-norm) are not in it. None without a joined trace,
for a family without such parts or a program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "SHARED_PARTS"):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got:
        return None
    took = fam.under(got, fam.SHARED_PARTS)
    return 1e3 * took / got["steps"] if took else None
