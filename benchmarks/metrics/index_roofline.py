"""Kernels: the least time one layer's index scoring of a decode step
could take on this chip over the time it took (jit_decode's self time
under ``dsa_index_scores`` a layer-step, over the matched executions:
the family's ``decode_parts_by_rounds``). The least time is the larger
of bytes over the chip's published HBM bandwidth and FLOPs over its
bf16 peak, both by the family's count (``index_step_bytes``: each index
key the riders' queries can see read once, 256 B a token;
``index_step_flops``) for the keys the program itself says it scored a
layer-step (the ``round`` events' decode_index_keys_scored over the
traced seconds, the family's ``selection_counters``). It counts riders:
a walk that gathers the blocks of rows without a request, or a whole
block where a rider holds a page of it, reads low by that share. None
without a joined trace, without peaks, without the counters or on a
program that names no such scope."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "index_step_bytes")):
        return None
    got = fam.decode_parts_by_rounds(run)
    counted = got and fam.selection_counters(run)
    if not counted or not got["parts"].get("dsa_index_scores"):
        return None
    keys = counted["index_keys_scored"] / counted["layer_steps"]
    took_s = (got["parts"]["dsa_index_scores"] / got["steps"]
              / run.cfg["num_hidden_layers"])
    least_s = max(
        fam.index_step_bytes(run.cfg, keys) / run.peaks["hbm_bytes_per_s"],
        fam.index_step_flops(run.cfg, keys) / run.peaks["bf16_flops"])
    return 100.0 * least_s / took_s
