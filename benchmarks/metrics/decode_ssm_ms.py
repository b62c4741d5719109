"""Model step: milliseconds of ONE decode step that jit_decode spends in
its STATE-SPACE layers' token mixing: self time under the family's
``SSM_SCOPES`` (ssm_conv: the convolution and its tail; ssm_gates: the x
and dt projections, the softplus; ssm_scan: the state read, stepped and
written, ops/selective_scan.py ``ssm_step``; ssm_out: the gate and the
output projection; the input projection ``w_in`` is not among them), all
the state-space layers together, over exactly the executions
benchmarks/trace_dispatch.py matched to their rounds and the decode
steps those rounds dispatched (the family's ``decode_parts_by_rounds``;
never trace_reduce.loop_steps). None without a joined trace, for a
family without such scopes or a program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "SSM_SCOPES"):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got:
        return None
    took = fam.under(got, fam.SSM_SCOPES)
    return 1e3 * took / got["steps"] if took else None
