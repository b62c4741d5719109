"""Model step (a decode program with a recurrent state a slot, K/V pages
and a mixture that holds a share of its experts side by side): the least
time ONE decode step could take on this chip over the time it took. The
least time is the larger of the bytes the step must move over the chip's
published HBM bandwidth and its FLOPs over the bf16 peak, both by the
family's count (``decode_step_bytes``: the held experts the step REALLY
touched a mixture layer, the shared SwiGLU, the float32 routers, every
layer's token-mixing matrices once, every rider's state and convolution
tail read once and written once in the Mamba-2 layers, the riders' K/V
with the step's own writes in the attention layers, the vocabulary's
slice once as the head; ``decode_step_flops``). Bytes bound it. The
experts touched and the routed pairs are the program's own counters a
mixture layer-step over the traced seconds (the family's
``decode_counters``), the time is the device time of the ``jit_decode``
executions that benchmarks/trace_dispatch.py matched to their rounds
over the ``decode_steps`` those rounds dispatched, and the riders and
their contexts are the rounds' own (the family's
``decode_parts_by_rounds``): the engine's count of the steps, never
``trace_reduce.loop_steps`` nor ``max_slots``. The cell's whole-step
share, which a later claim in the cell is bounded by
(swa_moe_step_roofline's form): it cannot pass 100 % unless a count is
wrong. None without a joined trace, without peaks, without the mixture's
counters, or for a family without a state step's count."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "state_step_flops")
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
            experts_touched=counters["experts_touched"])
        / run.peaks["hbm_bytes_per_s"],
        fam.decode_step_flops(run.cfg, got["context_tokens"],
                              got["riders"], pairs=counters["pairs"])
        / run.peaks["bf16_flops"]) / run.chips
    return 100.0 * least_s / took_s
