"""Model step / train step (ray_tpu/util/compile_cache.py, the build
log): seconds tracing and lowering the PROGRAM's step programs
(``jit_prefill``, ``jit_decode``, ``jit_seed``, ``jit_verify``, the
page copies, ``jit_step_fn``) before the window opened: the part of a
start that no compile cache saves. A build's ``trace_s`` is its own
trace event alone; the inner jitted functions' events inside it
(``nested_trace_s``) are not added in. Logs one ``[setup]`` line, a
step program a clause. None on a program without the log."""
from benchmarks import setup_parts
from benchmarks.common import log


def read(run):
    mine = setup_parts.step_program_builds(run)
    if mine is None:
        return None
    log("[setup] step programs: " + "; ".join(
        f"{r['program']} trace {r['trace_s']:.2f} (nested "
        f"{r['nested_trace_s']:.2f}) lower {r['lower_s']:.2f} backend "
        f"{r['backend_s']:.2f}" for r in mine))
    return sum(r["trace_s"] + r["lower_s"] for r in mine)
