"""Task timeline profiling.

Capability parity with the reference's profile-event pipeline
(src/ray/core_worker/profiling.h, python/ray/_private/profiling.py,
GlobalState.chrome_tracing_dump in python/ray/_private/state.py:413): every
runtime records named events/spans; ``timeline()`` dumps a Chrome
``chrome://tracing`` JSON.

The device's own timeline is ``jax.profiler``'s, and this module holds the
one control over it: ``start_device_trace`` / ``stop_device_trace`` start
and stop the profiler in the process that holds the chip. Host
``TraceAnnotation``s (the serving engine's ``engine.*`` phases) land on the
trace's host plane, which shares the trace's clock with the device planes.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

_lock = threading.Lock()
_events: List[Dict[str, Any]] = []
_enabled = True
_open_spans: Dict[tuple, float] = {}


def set_enabled(flag: bool):
    global _enabled
    _enabled = flag


def clear():
    with _lock:
        _events.clear()
        _open_spans.clear()


def record(category: str, name: str, **meta):
    if not _enabled:
        return
    with _lock:
        _events.append({
            "cat": category, "name": name, "ph": "i",
            "ts": time.time() * 1e6,
            "pid": 0, "tid": threading.get_ident() % 100000,
            "args": meta or {},
        })


def record_span_start(category: str, name: str, key=None):
    if not _enabled:
        return
    with _lock:
        _open_spans[(category, name, key,
                     threading.get_ident())] = time.time() * 1e6


def record_span_end(category: str, name: str, key=None):
    if not _enabled:
        return
    tid = threading.get_ident()
    with _lock:
        start = _open_spans.pop((category, name, key, tid), None)
        if start is None:
            return
        now = time.time() * 1e6
        _events.append({
            "cat": category, "name": name, "ph": "X",
            "ts": start, "dur": now - start,
            "pid": 0, "tid": tid % 100000, "args": {},
        })


@contextmanager
def profile(name: str, category: str = "user"):
    record_span_start(category, name)
    try:
        yield
    finally:
        record_span_end(category, name)


def chrome_trace(filename: Optional[str] = None) -> List[Dict[str, Any]]:
    with _lock:
        events = list(_events)
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events


# ------------------------------------------------------ device trace

_trace_lock = threading.Lock()
_trace_t0: Optional[float] = None      # time.monotonic() at the start
_trace_dir: Optional[str] = None


def start_device_trace(log_dir: str) -> float:
    """Start ``jax.profiler`` in this process (the one that holds the
    chip), writing under ``log_dir``: device planes and host TraceMes,
    no Python tracer. One trace at a time: a second start raises
    ``RuntimeError``. Returns ``time.monotonic()`` at the start."""
    global _trace_t0, _trace_dir
    import jax
    with _trace_lock:
        if _trace_t0 is not None:
            raise RuntimeError(
                f"a device trace is already running (into {_trace_dir})")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # device and host TraceMes only
        opts.host_tracer_level = 2
        t0 = time.monotonic()
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        _trace_t0, _trace_dir = t0, log_dir
    return t0


def stop_device_trace() -> Tuple[float, float]:
    """Stop the running trace and write it out. Returns the traced span
    ``(t0, t1)`` on ``time.monotonic()`` (``t1`` is taken before the
    trace is written). ``RuntimeError`` when none is running."""
    global _trace_t0, _trace_dir
    import jax
    with _trace_lock:
        if _trace_t0 is None:
            raise RuntimeError("no device trace is running")
        t0, t1 = _trace_t0, time.monotonic()
        try:
            jax.profiler.stop_trace()
        finally:
            _trace_t0 = _trace_dir = None
    return t0, t1
