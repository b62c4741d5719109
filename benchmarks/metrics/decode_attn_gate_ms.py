"""Model step: milliseconds of ONE decode step that jit_decode spends on
the attention's output gate (``attn_gate``: the gate's projection
[hidden, query heads], its sigmoid and the multiply a head, inside
``attn_sliding`` and ``attn_full`` alike), all layers together, over
exactly the executions benchmarks/trace_dispatch.py matched to their
rounds and the decode steps those rounds dispatched (the family's
``decode_parts_by_rounds``). What a gate fused into the attention's
read-out or into W_o would save. None without a joined trace, for a
family without such a part or a program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    gate = getattr(fam, "GATE", None)
    if run.kind != "serve" or not gate:
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or not got["parts"].get(gate):
        return None
    return 1e3 * got["parts"][gate] / got["steps"]
