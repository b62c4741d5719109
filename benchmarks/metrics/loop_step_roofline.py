"""Model step (a looped model's decode program): the least time ONE
decode step could take on this chip over the time it took. The least
time is the bytes the step must move by the family's count
(``decode_step_bytes``: the one stack's weights once a PASS, since they
cannot stay on the chip between passes; the head once; an embedding row
a rider; the riders' K/V in every one of the passes x layers cache
entries, read once, and the step's own writes) over the chip's
published HBM bandwidth. The time is the device time of the
``jit_decode`` executions that benchmarks/trace_dispatch.py matched to
their rounds over the ``decode_steps`` those rounds dispatched, and the
riders and their contexts are the rounds' own (``decode_riders``,
``decode_context_tokens``; the family's ``decode_by_rounds``): the
engine's count of the steps, never ``trace_reduce.loop_steps``, which
reads a nested loop's passes as steps. The cell's whole-step share: it
cannot pass 100 % unless a count is wrong. None without a joined trace,
without peaks, or for a family without such a join."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "decode_by_rounds")):
        return None
    got = fam.decode_by_rounds(run)
    if not got:
        return None
    took_s = got["module_s"] / got["steps"]
    least_s = fam.decode_step_bytes(
        run.cfg, got["context_tokens"], got["riders"]
    ) / run.chips / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / took_s
