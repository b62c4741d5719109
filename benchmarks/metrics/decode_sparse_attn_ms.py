"""Model step: milliseconds of one decode step under ``dsa_attn``, all
layers: the absorbed latent attention of the entries the selector chose
(their fetch by (page, offset), the scores of every head against them,
the softmax and the read-out of the compressed part). jit_decode's self
time under the scope over exactly the executions
benchmarks/trace_dispatch.py matched to their rounds, over the steps
those rounds dispatched (the family's ``decode_parts_by_rounds``). A.X-K1's
decode_latent_attn_ms is the dense sibling: every entry of the context
where this reads ``index_topk`` a rider. None without a joined trace,
for a family without a selector or on a program that names no such
scope."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "sparse_attn_step_bytes"):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or not got["parts"].get("dsa_attn"):
        return None
    return 1e3 * got["parts"]["dsa_attn"] / got["steps"]
