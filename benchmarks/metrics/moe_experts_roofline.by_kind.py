"""Kernels: the least time the held experts' matmuls of one mixture
layer's decode step could take on this chip over the time they took:
jit_decode's self time under ``moe_experts`` a MIXTURE layer-step, the
layers counted BY KIND (the family's ``n_moe_layers``: a leading dense
layer has no mixture) and the steps the engine's own (the family's
``decode_parts_by_rounds``). The least time is the larger of bytes over
the chip's published HBM bandwidth and FLOPs over its bf16 peak, both
counted by the family (``experts_step_bytes``: the three matrices of
every held expert REALLY touched, once, plus the routed rows in and
out; ``experts_step_flops``) from the program's own counters: the
``round`` events' moe_decode_experts_touched, moe_decode_pairs and
moe_decode_layer_steps over the traced seconds (the window's, where the
traced seconds hold none; they reach the log a round late, so they are
taken as a rate, not round by round). None without a joined trace,
without peaks or without the counters."""


def _decode_counters(run, span):
    t0, t1 = span
    touched = pairs = layer_steps = 0
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1:
            touched += e[5].get("moe_decode_experts_touched", 0)
            pairs += e[5].get("moe_decode_pairs", 0)
            layer_steps += e[5].get("moe_decode_layer_steps", 0)
    return touched, pairs, layer_steps


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "decode_parts_by_rounds")):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or not got["parts"].get("moe_experts"):
        return None
    touched = pairs = layer_steps = 0
    if run.trace_span and None not in run.trace_span:
        touched, pairs, layer_steps = _decode_counters(run, run.trace_span)
    if not layer_steps:
        touched, pairs, layer_steps = _decode_counters(run, run.window)
    if not layer_steps:
        return None
    took_s = (got["parts"]["moe_experts"] / got["steps"]
              / fam.n_moe_layers(run.cfg))
    least_s = max(
        fam.experts_step_bytes(run.cfg, touched / layer_steps,
                               pairs / layer_steps)
        / run.peaks["hbm_bytes_per_s"],
        fam.experts_step_flops(run.cfg, pairs / layer_steps)
        / run.peaks["bf16_flops"])
    return 100.0 * least_s / took_s
