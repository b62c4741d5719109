"""Model step: of the device time of jit_prefill (the chunked-prefill
program: four rows of a chunk each), the share under the state-space
layers' four scopes (the family's ``SSM_SCOPES``, as decode_ssm_ms): what
walking a chunk's positions one after the other (ops/selective_scan.py
``ssm_chunked``: a diagonal transition a channel, a state and a token,
so no matrix product) costs beside the projections and the
feed-forwards of the call's tokens. Lower is better. Needs the trace
itself (``run.trace_dir``, --trace 2); None for a family without such
scopes or a program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "SSM_SCOPES"):
        return None
    got = fam.typed_parts(run, "jit_prefill")
    if not got or not got["module_s"]:
        return None
    took = fam.under(got, fam.SSM_SCOPES)
    return 100.0 * took / got["module_s"] if took else None
