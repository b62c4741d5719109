"""Model step: milliseconds of ONE decode step that jit_decode spends in
its SLIDING-WINDOW layers' attention: self time under the ``attn_sliding``
scope and the parts named inside it (the family's ``SLIDING_PARTS``:
ring_append, ring_scores, ring_pv), all the sliding layers together,
over exactly the executions benchmarks/trace_dispatch.py matched to
their rounds and the decode steps those rounds dispatched (the family's
``decode_parts_by_rounds``; never trace_reduce.loop_steps), and with
them the whole-ring operations that the compiler leaves WITHOUT a scope
(the family's ``ring_copies``: a ring moved out of the chip's fast
memory and back with asynchronous copies every step; the core waits in
them, and a split by scope alone read the sliding layers at a quarter
of their cost). The projections, rope and the mixture are not in it.
None without a joined trace, for a family without such parts or a
program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "SLIDING_PARTS"):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got:
        return None
    took = fam.sliding_s(got)
    return 1e3 * took / got["steps"] if took else None
