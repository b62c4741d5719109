"""Model step (a model that decodes by blocks): milliseconds of ONE
forward of the block program under the attention scopes (kv_append,
kv_gather, attn_scores, attn_pv: the XLA block loop at T = L queries a
rider under the block mask; no kernel serves it): ``jit_decode``'s self
time under them over the forwards the engine's own rounds dispatched
(the family's ``decode_parts_by_rounds``: the executions
benchmarks/trace_dispatch.py matched to their rounds). What a paged
kernel for T = L queries would move. None without a joined trace, or
for a family without the join or on a program without the block
program's counters."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or not hasattr(fam, "denoise_counters")
            or not fam.denoise_counters(run)):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got:
        return None
    under = sum(got["parts"].get(p, 0.0) for p in fam.parts["attention"])
    return 1e3 * under / got["steps"]
