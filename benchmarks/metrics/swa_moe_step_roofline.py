"""Model step (a decode program with K/V pages, rings and a mixture that
holds every expert side by side): the least time ONE decode step could
take on this chip over the time it took. The least time is the larger
of the bytes the step must move over the chip's published HBM bandwidth
and its FLOPs over the bf16 peak, both by the family's count
(``decode_step_bytes``: the experts the step REALLY touched a mixture
layer and each routed pair's row in and out, the shared expert, the
float32 routers, the projections and gate by each layer type's query
heads, the dense layer, the head, an embedding row a rider, each
rider's window in the sliding layers and its whole context in the full
ones with the step's own writes; ``decode_step_flops``). Bytes bound
it. The experts touched are the program's own counters a mixture
layer-step over the traced seconds (the family's ``decode_counters``:
the ``round`` events' moe_decode_experts_touched over
moe_decode_layer_steps), the time is the device time of the
``jit_decode`` executions that benchmarks/trace_dispatch.py matched to
their rounds over the ``decode_steps`` those rounds dispatched, and the
riders, their contexts and their windows' keys are the rounds' own
(``decode_riders``, ``decode_context_tokens``, ``decode_sliding_keys``;
the family's ``decode_parts_by_rounds``): the engine's count of the
steps, never ``trace_reduce.loop_steps`` nor ``max_slots``. The cell's
whole-step share, which a later claim in the cell is bounded by: it
cannot pass 100 % unless a count is wrong. None without a joined trace,
without peaks, without the mixture's counters, or for a family without
such counts."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "decode_step_flops")
            or not hasattr(fam, "decode_counters")):
        return None
    got = fam.decode_parts_by_rounds(run)
    counters = fam.decode_counters(run)
    if not got or not got.get("module_s") or not counters:
        return None
    took_s = got["module_s"] / got["steps"]
    least_s = max(
        fam.decode_step_bytes(
            run.cfg, got["context_tokens"], got["riders"],
            experts_touched=counters["experts_touched"],
            sliding_keys=got["sliding_keys"])
        / run.peaks["hbm_bytes_per_s"],
        fam.decode_step_flops(run.cfg, got["context_tokens"],
                              got["riders"], got["sliding_keys"])
        / run.peaks["bf16_flops"]) / run.chips
    return 100.0 * least_s / took_s
