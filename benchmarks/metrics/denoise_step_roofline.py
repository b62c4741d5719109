"""Model step (a model that decodes by blocks): the least time ONE
forward of the block program could take on this chip over the time it
took. The least time is the larger of the bytes the forward must move
over the chip's published HBM bandwidth and its FLOPs over the bf16
peak, both by the family's count, kept with the benchmark
(``decode_step_bytes``: the attention matrices, the float32 routers,
the experts the forward REALLY touched a layer with each routed pair's
row in and out, the head, an embedding row a position, the riders'
contexts read and the blocks' own K/V written and read;
``decode_step_flops``). The experts touched are the program's own
counters a layer-step (the family's ``decode_counters``), the time is
the device time of the ``jit_decode`` executions that
benchmarks/trace_dispatch.py matched to their rounds over the forwards
those rounds dispatched, the riders the rounds' own (the family's
``decode_parts_by_rounds``), and the tokens in context the mean of
load_report()'s kv_bytes_in_use over the traced seconds, as
decode_roofline takes it (whole pages, the prompts mid-prefill among
them: at most the pool). The cell's whole-step share: it cannot pass
100 % unless a count is wrong. None without a joined trace, without
peaks, without the counters, or for a family without such counts."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "denoise_counters")
            or not fam.denoise_counters(run)):
        return None
    got = fam.decode_parts_by_rounds(run)
    counters = fam.decode_counters(run)
    if not got or not got.get("module_s") or not counters \
            or not run.trace_span or None in run.trace_span:
        return None
    t0, t1 = run.trace_span
    used = [s["kv_bytes_in_use"] for s in run.samples
            if t0 - 1.0 <= s["t"] <= t1 + 1.0]
    if not used:
        return None
    context = (sum(used) / len(used)) / fam.kv_bytes_per_token(run.cfg)
    took_s = got["module_s"] / got["steps"]
    least_s = max(
        fam.decode_step_bytes(
            run.cfg, context, got["riders"],
            experts_touched=counters["experts_touched"])
        / run.peaks["hbm_bytes_per_s"],
        fam.decode_step_flops(run.cfg, context, got["riders"])
        / run.peaks["bf16_flops"]) / run.chips
    return 100.0 * least_s / took_s
