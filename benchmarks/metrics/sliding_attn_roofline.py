"""Kernels: the least time one sliding-window layer's attention of a
decode step could take on this chip, over the time it took: jit_decode's
self time under the family's ``SLIDING_PARTS`` (the ring's append, its
scores and its read-out, and the unnamed whole-ring copies the compiler
adds: decode_sliding_attn_ms says why they count) a SLIDING layer-step,
the layers counted BY KIND (``n_sliding_layers``) and the steps the
engine's own (the family's ``decode_parts_by_rounds``). The least time is the larger of bytes over
the chip's published HBM bandwidth and FLOPs over its bf16 peak, both
counted by the family (``sliding_step_bytes``: each rider's
min(context, window) keys and values, 2,048 B a position, read once;
``sliding_step_flops``) from the rounds' ``decode_sliding_keys``. Bytes
bound it. It counts what MUST be read: a program that reads every
slot's whole ring (the window, a prefill chunk and the slack; free
slots' too) reads lower by that share. None without a joined trace,
without peaks, for a family that has no such count, a program that
names no such scope or whose rounds lack the counter."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "sliding_step_bytes")):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or got["sliding_keys"] <= 0:
        return None
    took = fam.sliding_s(got)
    if not took:
        return None
    took_s = took / got["steps"] / fam.n_sliding_layers(run.cfg)
    keys = got["sliding_keys"]
    least_s = max(
        fam.sliding_step_bytes(run.cfg, keys)
        / run.peaks["hbm_bytes_per_s"],
        fam.sliding_step_flops(run.cfg, keys) / run.peaks["bf16_flops"])
    return 100.0 * least_s / took_s
