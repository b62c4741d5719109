"""Model step (a looped model's decode program): of the device time of
jit_decode, the share under the four attention scopes (the family's
``ATTENTION_PARTS``: kv_append, kv_gather, attn_scores, attn_pv), over
the executions that benchmarks/trace_dispatch.py matched to their
rounds (the family's ``decode_by_rounds``): what the passes x layers
cache entries cost a step, beside the weights streamed once a pass.
Lower is better. None without a joined trace or for a family without
such a join."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "decode_by_rounds"):
        return None
    got = fam.decode_by_rounds(run)
    if not got:
        return None
    return 100.0 * fam.attention_s(got) / got["module_s"]
