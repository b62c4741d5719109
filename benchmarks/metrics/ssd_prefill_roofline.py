"""Kernels: the least time one Mamba-2 layer's chunked recurrence of a
prefill call could take on this chip, over the time it took:
jit_prefill's self time under ``ssm_scan`` (the chunk's ``ssd_intra``
and ``ssd_carry`` inside it: the family's ``SCAN_PARTS``) a Mamba-2
layer (the family's ``n_ssm_layers``) and a call. The least time is the
LARGER of the recurrence's matrix products over the chip's bf16 peak
(the family's ``scan_call_flops``: a position against the positions of
its chunk at or before it, its read-out of the carried state and its
write into the state handed on) and its operands over the published HBM
bandwidth (``scan_call_bytes``: the live rows' states in and out, a
token's x', B, C and dt in and its y out), for the rows and tokens the
``round`` events say a call carried (the family's ``prefill_calls``).
The program multiplies in float32 at ``HIGHEST`` (six bf16 passes a
product) and builds the whole ``[chunk, chunk]`` mask of every head, so
this share reads low until a kernel keeps the chunk in fast memory
(PERF.md section 7). None without a trace, without peaks, for a family
that has no such counts or a program that names no such scope."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "scan_call_flops")):
        return None
    got = fam.typed_parts(run, "jit_prefill")
    calls = fam.prefill_calls(run)
    if not got or not calls or not got.get("runs"):
        return None
    took_s = (fam.under(got, fam.SCAN_PARTS) / got["runs"]
              / fam.n_ssm_layers(run.cfg))
    if not took_s:
        return None
    rows, tokens = (calls[k] / calls["calls"] for k in ("rows", "tokens"))
    least_s = max(
        fam.scan_call_flops(run.cfg, rows, tokens) / run.peaks["bf16_flops"],
        fam.scan_call_bytes(run.cfg, rows, tokens)
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / took_s
