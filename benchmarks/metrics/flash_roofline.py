"""Kernels (ops/flash_attention.py): the least time the chip could take
for the causal attention of the traced steps, forward and backward, over
the device time of the Pallas calls in the trace. The least time is the
larger of FLOPs over the bf16 peak and bytes over HBM bandwidth
(benchmarks/costs); at T=1024, D=64 the FLOP bound applies (about
146 FLOPs a byte against the chip's 240: close, so both are computed).
Pallas calls appear in the trace as the step's custom-call operations
(three a layer: forward, dq, dk/dv)."""
from benchmarks import costs

MODULE = "jit_step_fn"


def read(run):
    tr = run.trace or {}
    if run.kind != "train" or run.peaks is None:
        return None
    ops = tr.get("module_ops", {}).get(MODULE, {})
    mod = tr.get("modules", {}).get(MODULE)
    kernel_s = sum(rec[1] for rec in ops.values()
                   if rec[2] == "custom-call")
    if not mod or not mod["runs"] or kernel_s <= 0:
        return None
    layers, heads, head_dim = run.family.attention_shape(run.cfg)
    shape = (run.batch / run.chips, run.seq, heads, head_dim)
    flops = layers * costs.flash_causal_flops(*shape)
    nbytes = layers * costs.flash_bytes(*shape)
    least_s = mod["runs"] * max(flops / run.peaks["bf16_flops"],
                                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
