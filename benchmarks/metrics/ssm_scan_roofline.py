"""Kernels: the least time one state-space layer's decode step could
take to move its recurrent state on this chip, over the time its state
update and read-out took: jit_decode's self time under ``ssm_scan`` a
state-space layer-step, the layers counted BY KIND (the family's
``n_ssm_layers``) and the steps the engine's own (the family's
``decode_parts_by_rounds``). The least time is the bytes the step MUST
move, counted by the family (``state_step_bytes``: each rider's float32
``[d_state, d_inner]`` state read once and written once, and its
convolution tail; the riders are those rounds' ``decode_riders``), over
the chip's published HBM bandwidth: the recurrence is elementwise over
the state, so bytes bound it. A program that moves the state of slots
that carry no request, or passes over it more than once, reads lower.
None without a joined trace, without peaks, for a family that has no
such count or a program that names no such scope."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "n_ssm_layers")):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or not got["parts"].get("ssm_scan"):
        return None
    took_s = (got["parts"]["ssm_scan"] / got["steps"]
              / fam.n_ssm_layers(run.cfg))
    least_s = (fam.state_step_bytes(run.cfg, got["riders"])
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / took_s
