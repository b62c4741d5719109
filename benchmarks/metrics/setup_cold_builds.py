"""Model step / train step (ray_tpu/util/compile_cache.py, the build
log): builds before the window opened that the compile cache did not
have (``cache_hit`` not true) and that took the backend a second or
more: 0 on a warm machine, and what tells a run whose programs were
evicted from a slow one. Logs one ``[setup]`` line naming them. None
on a program without the log."""
from benchmarks import setup_parts
from benchmarks.common import log


def read(run):
    cold = setup_parts.cold_builds(run)
    if cold is None:
        return None
    log(f"[setup] cold builds {len(cold)}: " + "; ".join(
        f"{r['program']} backend {r['backend_s']:.1f} s" for r in cold))
    return len(cold)
