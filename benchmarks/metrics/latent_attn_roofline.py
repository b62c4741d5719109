"""Kernels: the least time one layer's absorbed latent attention of a
decode step could take on this chip, over the time its block loop over
the pool took (jit_decode's self time under kv_gather, attn_scores and
attn_pv, the family's ``LATENT_WINDOW_SCOPES``, a layer-step). The
least time is the larger of bytes over the chip's published HBM
bandwidth and FLOPs over its bf16 peak, both counted by the family
(``latent_step_bytes``: each context token's one latent entry read
once; ``latent_step_flops``: every head's score against the entry and
its read-out) for THE RIDERS' contexts: the ``round`` events'
decode_riders x decode_window_tokens, weighted by their decode_steps,
over the traced seconds (the window's, where the traced seconds hold
none). decode_window_tokens is the longest rider's context rounded up
to a whole block of the loop, so a rider counts for at most a block
more than it holds. The layer-steps are the family's count of the
traced decode steps (``decode_steps_traced``: the head's executions)
times the layers. It counts riders: a program that gathers and
attends the rows of slots that carry no request reads lower by that
share. None without a trace, without peaks, for a family that counts no
such bytes or a program that names no such scope."""


def _rider_context_tokens(run, span):
    """Mean over the decode steps of riders x window tokens."""
    t0, t1 = span
    tokens = steps = 0
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1:
            n = e[5].get("decode_steps", 0)
            tokens += (e[5].get("decode_riders", 0)
                       * e[5].get("decode_window_tokens", 0) * n)
            steps += n
    return tokens / steps if steps else None


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "latent_step_bytes")):
        return None
    got = fam.latent_parts(run, "jit_decode")
    if not got:
        return None
    under = sum(got["parts"].get(s, 0.0) for s in fam.LATENT_WINDOW_SCOPES)
    steps = fam.decode_steps_traced(run)
    if not under or not steps:
        return None
    tokens = None
    if run.trace_span and None not in run.trace_span:
        tokens = _rider_context_tokens(run, run.trace_span)
    if tokens is None:
        tokens = _rider_context_tokens(run, run.window)
    if not tokens:
        return None
    took_s = under / steps / run.cfg["num_hidden_layers"]
    least_s = max(
        fam.latent_step_bytes(run.cfg, tokens)
        / run.peaks["hbm_bytes_per_s"],
        fam.latent_step_flops(run.cfg, tokens) / run.peaks["bf16_flops"])
    return 100.0 * least_s / took_s
