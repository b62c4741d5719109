"""Model step: rows ONE touched expert's matmuls get in a decode step:
the ``round`` events' moe_decode_pairs over their
moe_decode_experts_touched, over the window (pairs a mixture layer-step
over experts touched a mixture layer-step). It says how far a grouped
matmul's row tile is filled: 128 riders x 8 experts a token over ~250
touched experts are ~4 rows against a tile of 128, where a mixture of 64
experts sees 16 and a prefill call of 1,024 tokens 32. None on a
program whose ``round`` events lack the keys (a dense model, or a
program older than the decode counters)."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    pairs = touched = 0
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1:
            pairs += e[5].get("moe_decode_pairs", 0)
            touched += e[5].get("moe_decode_experts_touched", 0)
    return pairs / touched if touched else None
