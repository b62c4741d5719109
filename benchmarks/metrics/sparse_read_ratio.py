"""Model step: latent entries the attention FETCHED over entries the
selector CHOSE, over the window: the ``round`` events'
sparse_entries_read over their sparse_entries_chosen, both summed by the
program over its live tokens and its layers (decode steps and prefill
calls together; the same keys after ``decode_`` are the decode
dispatches' alone, and the ``[selection]`` line prints both apart). 1.0
is an attention that reads only what was chosen (a gather); a walk over
the whole context under a mask reads context / chosen. None on a
program whose ``round`` events lack the keys (no selector)."""
from benchmarks.common import log


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    keys = ("sparse_entries_read", "sparse_entries_chosen",
            "decode_sparse_entries_read", "decode_sparse_entries_chosen",
            "index_keys_scored", "decode_index_keys_scored")
    sums = dict.fromkeys(keys, 0)
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1:
            for k in keys:
                sums[k] += e[5].get(k, 0)
    if not sums["sparse_entries_chosen"]:
        return None
    d_read, d_chosen = (sums["decode_sparse_entries_read"],
                        sums["decode_sparse_entries_chosen"])
    p_read = sums["sparse_entries_read"] - d_read
    p_chosen = sums["sparse_entries_chosen"] - d_chosen
    log(f"[selection] window: decode steps read {d_read} of {d_chosen} "
        f"chosen = {d_read / max(1, d_chosen):.4f}; prefill calls read "
        f"{p_read} of {p_chosen} chosen = {p_read / max(1, p_chosen):.4f};"
        f" index keys scored {sums['index_keys_scored']} (decode "
        f"{sums['decode_index_keys_scored']})")
    return sums["sparse_entries_read"] / sums["sparse_entries_chosen"]
