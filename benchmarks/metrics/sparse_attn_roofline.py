"""Kernels: the least time one layer's attention of a decode step could
take on this chip IF IT READ THE CHOSEN ENTRIES ALONE, over the time it
took (jit_decode's self time under ``dsa_attn`` a layer-step, over the
matched executions: the family's ``decode_parts_by_rounds``). The least
time is the larger of bytes over the chip's published HBM bandwidth and
FLOPs over its bf16 peak, both by the family's count
(``sparse_attn_step_bytes``: each CHOSEN entry read once, 1,280 B as
stored; ``sparse_attn_step_flops``) for the entries the program itself
says its riders chose a layer-step (the ``round`` events'
decode_sparse_entries_chosen over the traced seconds: min(context,
index_topk) a rider). The same work whatever implements it: a walk over
the whole context under a mask moves more and reads LOW, never over
100 %. None without a joined trace, without peaks, without the counters
or on a program that names no such scope."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "sparse_attn_step_bytes")):
        return None
    got = fam.decode_parts_by_rounds(run)
    counted = got and fam.selection_counters(run)
    if not counted or not got["parts"].get("dsa_attn"):
        return None
    chosen = counted["sparse_entries_chosen"] / counted["layer_steps"]
    took_s = (got["parts"]["dsa_attn"] / got["steps"]
              / run.cfg["num_hidden_layers"])
    least_s = max(
        fam.sparse_attn_step_bytes(run.cfg, chosen)
        / run.peaks["hbm_bytes_per_s"],
        fam.sparse_attn_step_flops(run.cfg, chosen)
        / run.peaks["bf16_flops"])
    return 100.0 * least_s / took_s
