"""What of ``setup_s`` is building, read from the program's own build
log (ray_tpu/util/compile_cache.py ``build_log()``: one record a build,
by name, with its seconds tracing, lowering and in the backend, whether
the compile cache had it, and its end on time.monotonic(), the clock of
``run.window``). The four ``setup_*`` / ``engine_init_s`` readers of
benchmarks/metrics/ share this file as the trace's readers share
trace_parts.py. Readers run in the served process after the window, so
the log is read where it lives; a test hands a recorded one as
``run.builds`` (``{"records": [...], "totals": {...}}``). On a program
without the log (a parent of PR 51) everything here returns None and
the readers report nothing.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks.common import log

# the PROGRAM's step programs, as a device trace names them: the
# engine's (serve/step_programs.py; the page copy and write are
# ``jit_copy`` and ``jit_write`` there) and the train step
# (train/spmd.py)
STEP_PROGRAMS = ("jit_prefill", "jit_decode", "jit_seed", "jit_verify",
                 "jit_copy", "jit_write", "jit_step_fn")
# a backend span from this on, on a miss, is a compile worth the name
# (a miss of an eager operation's program costs milliseconds)
COLD_BACKEND_S = 1.0
_PARTS = ("trace_s", "lower_s", "backend_s")


def builds(run) -> Optional[Dict[str, Any]]:
    """``{"records": [...], "totals": {...}}`` of the process's build
    log (or the recorded one a test put on ``run``); None where the
    program has none."""
    given = getattr(run, "builds", None)
    if given is not None:
        return given
    try:
        from ray_tpu.util.compile_cache import build_log
    except ImportError:
        return None
    blog = build_log()
    # read once: the window has closed, and every reader sees one log
    run.builds = {"records": blog.snapshot(), "totals": blog.totals()}
    return run.builds


def before_window(run) -> Optional[List[Dict[str, Any]]]:
    """The records of builds that ended before the window opened."""
    got = builds(run)
    if got is None:
        return None
    return [r for r in got["records"] if r["t"] < run.window[0]]


def build_seconds(record: Dict[str, Any]) -> float:
    return sum(record[k] for k in _PARTS)


def setup_build_s(run) -> Optional[float]:
    """Seconds tracing, lowering and in the backend over every build
    before the window, the harness's own programs among them. The
    builds too small for a record (``other`` in the totals) carry no
    time of their own: a window builds nothing (the run asserts it), so
    they are all set-up's."""
    records = before_window(run)
    if records is None:
        return None
    other = builds(run)["totals"]["programs"].get("other", {})
    return (sum(build_seconds(r) for r in records)
            + sum(other.get(k, 0.0) for k in _PARTS))


def step_program_builds(run) -> Optional[List[Dict[str, Any]]]:
    """The builds of the program's step programs before the window:
    their tracing and lowering is what no compile cache saves."""
    records = before_window(run)
    if records is None:
        return None
    return [r for r in records if r["program"] in STEP_PROGRAMS]


def cold_builds(run) -> Optional[List[Dict[str, Any]]]:
    """The builds before the window that the compile cache did not have
    and that took the backend ``COLD_BACKEND_S`` or more."""
    records = before_window(run)
    if records is None:
        return None
    return [r for r in records if r["cache_hit"] is not True
            and r["backend_s"] >= COLD_BACKEND_S]


def engine_init(run) -> Optional[Dict[str, float]]:
    """The payload of the engine's ``engine_init`` event (its
    construction by part), summed where the run built several; None
    where the events hold none."""
    inits = [e[5] for e in getattr(run, "events", ())
             if e[2] == "engine_init" and e[1] < run.window[0]]
    if not inits:
        return None
    out: Dict[str, float] = {}
    for data in inits:
        for k, v in data.items():
            out[k] = out.get(k, 0.0) + v
    return out


def _hit(record) -> str:
    return {True: "hit", False: "miss", None: "no cache"}[
        record["cache_hit"]]


def log_largest(run, top: int = 5) -> None:
    """The ``[setup]`` lines of ``setup_build_s``: the largest builds
    by program, what of ``setup_s`` is no build and no ramp, and the
    whole run's totals in the order of the harness's ``[compile]``
    line, to be read beside it."""
    records = before_window(run)
    by_program: Dict[str, Dict[str, Any]] = {}
    for r in records:
        p = by_program.setdefault(r["program"], {
            "n": 0, "hits": 0, "misses": 0, "cache_read_s": 0.0,
            **dict.fromkeys(_PARTS, 0.0)})
        p["n"] += 1
        p["hits"] += r["cache_hit"] is True
        p["misses"] += r["cache_hit"] is False
        for k in _PARTS + ("cache_read_s",):
            p[k] += r[k]
    largest = sorted(by_program.items(), reverse=True,
                     key=lambda kv: build_seconds(kv[1]))[:top]
    built = setup_build_s(run)
    ramp = float(run.traffic.get("ramp_s", 0.0))
    log(f"[setup] builds before the window {built:.2f} s of setup_s "
        f"{run.e2e['setup_s']:.2f}; ramp {ramp:.1f}; the rest (imports, "
        f"the chip, weights, warm-up and parity executing) "
        f"{run.e2e['setup_s'] - built - ramp:.2f} s; largest: "
        + "; ".join(
            f"{name} x{p['n']} trace {p['trace_s']:.2f} lower "
            f"{p['lower_s']:.2f} backend {p['backend_s']:.2f} "
            f"cache-read {p['cache_read_s']:.2f} ({p['hits']} hit "
            f"{p['misses']} miss)" for name, p in largest))
    t = builds(run)["totals"]
    log(f"[setup] build log, whole run: trace {t['trace_s']:.3f} + "
        f"nested {t['nested_trace_s']:.3f} + unbuilt "
        f"{t.get('unbuilt_trace_s', 0.0):.3f} = "
        f"{t['trace_s'] + t['nested_trace_s'] + t.get('unbuilt_trace_s', 0.0):.3f}"
        f" lower {t['lower_s']:.3f} backend {t['backend_s']:.3f} "
        f"cache-read {t['cache_read_s']:.3f} s; cache hits "
        f"{t['cache_hits']} misses {t['cache_misses']}; programs "
        f"{t['builds']} ({t.get('records', len(records))} records + "
        f"{t['programs'].get('other', {}).get('builds', 0)} small); "
        f"listeners {t.get('events', 0)} events "
        f"{1e3 * t.get('listener_s', 0.0):.2f} ms")
