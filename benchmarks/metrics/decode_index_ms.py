"""Model step: milliseconds of one decode step under the selector's two
scopes, all layers: ``dsa_index_scores`` (the walk over the riders'
index-key pages: 64 index heads against one 128-wide key a token, relu,
the weighted head sum) and ``dsa_topk`` (the exact choice of the
``index_topk`` best entries a rider). jit_decode's self time by scope
over exactly the executions benchmarks/trace_dispatch.py matched to
their rounds, over the steps those rounds dispatched (the family's
``decode_parts_by_rounds``). What choosing costs a step, beside what
attending the chosen entries costs (decode_sparse_attn_ms). None
without a joined trace, for a family without a selector or on a program
that names no such scope."""


def read(run):
    fam = getattr(run, "family", None)
    scopes = getattr(fam, "INDEX_SCOPES", ())
    if run.kind != "serve" or not scopes:
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got:
        return None
    return 1e3 * sum(got["parts"].get(s, 0.0) for s in scopes) / got["steps"]
