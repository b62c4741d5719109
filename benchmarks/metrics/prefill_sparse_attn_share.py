"""Model step: of the device time of jit_prefill (the chunked-prefill
program: four rows of a chunk each), the share under the selection's
five scopes (the family's ``DSA_SCOPES``: dsa_index_q, dsa_index_k with
its append, dsa_index_scores, dsa_topk and dsa_attn, the chosen
entries' attention): what learned sparse attention costs a call beside
the latent projections, the feed-forwards and the head. prefill_attn_share
adds the latent attention's own scopes (mla_q, mla_kv, mla_absorb,
kv_append) to these. Needs the trace itself (``run.trace_dir``, --trace
2); None for a family without such scopes or a program that names
none."""


def read(run):
    fam = getattr(run, "family", None)
    scopes = getattr(fam, "DSA_SCOPES", ())
    if run.kind != "serve" or not scopes:
        return None
    got = fam.latent_parts(run, "jit_prefill")
    if not got or not got["module_s"]:
        return None
    under = sum(got["parts"].get(s, 0.0) for s in scopes)
    return 100.0 * under / got["module_s"]
