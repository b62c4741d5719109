"""Model step / train step (ray_tpu/util/compile_cache.py, the build
log): seconds JAX spent tracing, lowering and in the backend (compiling,
or loading from the compile cache) over every build that ended before
the window opened, the harness's own programs (weights, reference)
among them: what of ``setup_s`` is building. Logs the ``[setup]``
lines: the five largest builds by program with their parts and
hit/miss, ``setup_s`` less this less the traffic's ``ramp_s``, and the
log's whole-run totals beside the harness's ``[compile]`` line. None on
a program without the log."""
from benchmarks import setup_parts


def read(run):
    value = setup_parts.setup_build_s(run)
    if value is not None:
        setup_parts.log_largest(run)
    return value
