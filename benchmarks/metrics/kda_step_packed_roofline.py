"""Kernels (ops/linear_attention.py ``kda_step_packed_kernel``: the
one-token delta rule over a state stored several heads side by side,
under ONE decay a head): the least time one linear layer's decode step
could take in the kernel over the time its calls took. The least time
is what a call MUST move and compute by the family's count
(``step_kernel_bytes``: each rider's float32 state read once and written
once; ``step_kernel_flops``) over the chip's HBM bandwidth or its peak,
the larger (bytes bound it). The time is the self time of the
operations that carry the kernel's name (the family's ``STEP_KERNEL``)
inside the ``jit_decode`` executions benchmarks/trace_dispatch.py
matched to their rounds, a layer-step by the rounds' own
``decode_steps`` and the family's ``n_kda_layers``; the riders are the
rounds' ``decode_riders``. ``linear_state_roofline.by_kind`` reads the
whole ``kda_recurrence`` scope (the kernel, what surrounds it, and the
tails' bytes); this one the kernel alone. None without a joined trace,
without peaks, for a family that names no such kernel, and for a
program that holds no call of it (the CPU, a mesh, a parent of PR
49)."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "step_kernel_bytes")):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or not got.get("kernel_s"):
        return None
    took_s = got["kernel_s"] / got["steps"] / fam.n_kda_layers(run.cfg)
    least_s = max(
        fam.step_kernel_bytes(run.cfg, got["riders"])
        / run.peaks["hbm_bytes_per_s"],
        fam.step_kernel_flops(run.cfg, got["riders"])
        / run.peaks["bf16_flops"])
    return 100.0 * least_s / took_s
