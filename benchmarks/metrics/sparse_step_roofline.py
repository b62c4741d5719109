"""Model step (a decode program that chooses the entries it attends):
the least time ONE decode step could take on this chip over the time it
took. The least time is the bytes the step must move by the family's
count (``decode_step_bytes``: every layer's attention and indexer
matrices, the dense layer, the held experts the step REALLY touched a
mixture layer with the shared expert and the float32 router, the index
keys of the riders' whole contexts, the latent entries CHOSEN, the
step's own two entries a rider, the head and an embedding row a rider)
over the chip's published HBM bandwidth. The experts touched and the
entries chosen are the program's own counters over the traced seconds
(``decode_counters``, ``selection_counters``), the time is the device
time of the ``jit_decode`` executions benchmarks/trace_dispatch.py
matched to their rounds over the ``decode_steps`` those rounds
dispatched, and the riders and their contexts are the rounds' own
(``decode_parts_by_rounds``). The cell's whole-step share, which a later
claim in the cell is bounded by: it cannot pass 100 % unless a count is
wrong; a step that reads entries it did not choose reads low. None
without a joined trace, without peaks, without the counters, or for a
family without such counts."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "selection_counters")):
        return None
    got = fam.decode_parts_by_rounds(run)
    counters = got and fam.decode_counters(run)
    counted = counters and fam.selection_counters(run)
    if not counted or not got.get("module_s"):
        return None
    took_s = got["module_s"] / got["steps"]
    least_s = fam.decode_step_bytes(
        run.cfg, got["context_tokens"], got["riders"],
        experts_touched=counters["experts_touched"],
        chosen=counted["sparse_entries_chosen"] / counted["layer_steps"]
    ) / run.peaks["hbm_bytes_per_s"] / run.chips
    return 100.0 * least_s / took_s
