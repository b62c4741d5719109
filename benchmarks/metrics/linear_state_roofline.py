"""Kernels: the least time one linear-attention layer's decode step
could take to move its recurrent state on this chip, over the time its
state update and read-out took (jit_decode's self time under
``kda_recurrence``, a layer-step). The least time is the bytes the step
MUST move, counted by the family (``state_step_bytes``: each rider's
float32 state read once and written once, and its convolution tail),
over the chip's published HBM bandwidth; the riders are the ``round``
events' decode_riders over the traced seconds (the window's, where the
traced seconds hold none). The recurrence is elementwise over the
state, so bytes bound it. A program that moves the state of slots that
carry no request, or passes over it more than once, reads lower. None
without a trace, without peaks, for a family that counts no such bytes
or a program that names no such scope."""
from benchmarks import trace_parts


def _riders(run, span):
    t0, t1 = span
    riders = steps = 0
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1:
            n = e[5].get("decode_steps", 0)
            riders += e[5].get("decode_riders", 0) * n
            steps += n
    return riders / steps if steps else None


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "state_step_bytes")):
        return None
    got = trace_parts.for_run(run, "jit_decode")
    step = trace_parts.decode_step_parts(run)
    if not got or not step or not got["parts"].get("kda_recurrence"):
        return None
    riders = None
    if run.trace_span and None not in run.trace_span:
        riders = _riders(run, run.trace_span)
    if riders is None:
        riders = _riders(run, run.window)
    if riders is None:
        return None
    steps = 1e3 * got["module_s"] / step["step_ms"]
    took_s = got["parts"]["kda_recurrence"] / steps / fam.n_kda_layers(
        run.cfg)
    least_s = (fam.state_step_bytes(run.cfg, riders)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / took_s
