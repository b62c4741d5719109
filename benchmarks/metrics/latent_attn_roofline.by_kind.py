"""Kernels: the least time one latent-attention layer's absorbed
attention of a decode step could take on this chip, over the time its
block loop over the pool took: jit_decode's self time under kv_gather,
attn_scores and attn_pv (the family's ``LATENT_WINDOW_SCOPES``) an MLA
layer-step, the layers counted BY KIND (``n_mla_layers``) and the steps
the engine's own (the family's ``decode_parts_by_rounds``: the rounds
that benchmarks/trace_dispatch.py matched to the executions the time is
summed over). The least time is the larger of bytes over the chip's
published HBM bandwidth and FLOPs over its bf16 peak, both counted by
the family (``latent_step_bytes``: each context token's one latent
entry, 1,152 B, read once; ``latent_step_flops``) for THE RIDERS' OWN
CONTEXTS: those rounds' ``decode_context_tokens``, the sum of every
rider's own length from the host's positions, where
``decode_window_tokens`` is only the longest rider's. It counts what
must be read: a program that gathers and attends whole blocks up to the
longest rider's context, for every slot, reads lower by that share.
None without a joined trace, without peaks, for a family that has no
such count, a program that names no such scope or whose rounds lack
the counter."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "decode_parts_by_rounds")):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or got["context_tokens"] <= 0:
        return None
    under = sum(got["parts"].get(s, 0.0) for s in fam.LATENT_WINDOW_SCOPES)
    if not under:
        return None
    took_s = under / got["steps"] / fam.n_mla_layers(run.cfg)
    tokens = got["context_tokens"]
    least_s = max(
        fam.latent_step_bytes(run.cfg, tokens)
        / run.peaks["hbm_bytes_per_s"],
        fam.latent_step_flops(run.cfg, tokens) / run.peaks["bf16_flops"])
    return 100.0 * least_s / took_s
