"""Model step (a hybrid's decode program: weights, recurrent state and
K/V side by side): the least time ONE decode step could take on this
chip over the time it took. The least time is the bytes the step must
move by the family's count (``decode_step_bytes``: every layer's
matrices once, the head once, an embedding row a rider, the riders' K/V
in the layers that have K/V with the step's own writes, and every
rider's recurrent state read once and written once in the layers that
keep one: what the arithmetic needs, not the padded bytes a chip keeps)
over the chip's published HBM bandwidth. The time is the device time of
the ``jit_decode`` executions that benchmarks/trace_dispatch.py matched
to their rounds over the ``decode_steps`` those rounds dispatched, and
the riders and their contexts are the rounds' own (``decode_riders``,
``decode_context_tokens``; the family's ``decode_parts_by_rounds``):
the engine's count of the steps and of the riders, never
``trace_reduce.loop_steps`` nor ``max_slots``. The cell's whole-step
share: it cannot pass 100 % unless a count is wrong. None without a
joined trace, without peaks, or for a family without such a join."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "decode_parts_by_rounds")):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or not got.get("module_s"):
        return None
    took_s = got["module_s"] / got["steps"]
    least_s = fam.decode_step_bytes(
        run.cfg, got["context_tokens"], got["riders"]
    ) / run.chips / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / took_s
