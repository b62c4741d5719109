"""Kernels: the least time one state-space layer's scan of a prefill
call could take to move its operands on this chip, over the time it
took: jit_prefill's self time under ``ssm_scan`` a state-space layer
(the family's ``n_ssm_layers``) and a call (the ``round`` events'
calls of the traced seconds: the family's ``prefill_calls``). The least
time is BYTES ALONE (the family's ``scan_call_bytes``: the live rows'
states in and out, a token's u', delta, B and C in and its y out) over
the chip's published HBM bandwidth. benchmarks/peaks.json states no
peak for the vector unit from a published source, and the scan is 16 x
5,120 elementwise multiply-adds and an exponential a token, bound by
the vector unit and by the latency of a chain of T steps: this share
says how far the scan is from moving its operands once, NOT how well it
uses the unit that bounds it, and reads low for any form that walks
positions one at a time (PERF.md section 3). None without a trace,
without peaks, for a family that has no such count or a program that
names no such scope."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "scan_call_bytes")):
        return None
    got = fam.typed_parts(run, "jit_prefill")
    calls = fam.prefill_calls(run)
    if not got or not calls or not got["parts"].get("ssm_scan") \
            or not got.get("runs"):
        return None
    took_s = got["parts"]["ssm_scan"] / got["runs"] / fam.n_ssm_layers(
        run.cfg)
    least_s = fam.scan_call_bytes(
        run.cfg, calls["rows"] / calls["calls"],
        calls["tokens"] / calls["calls"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / took_s
