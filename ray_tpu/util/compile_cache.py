"""Where JAX's persistent compilation cache lives.

A directory that moves never hits (the path is part of how a run finds
its entries), so there is exactly one rule: ``JAX_COMPILATION_CACHE_DIR``
if the environment sets it — JAX reads that variable itself, and this
module then sets nothing — else one fixed directory at the root of the
checkout. Never a temp dir, a pid or a timestamp.

And what every build cost: ``build_log()`` is the process's one record
of the programs JAX traced, lowered and compiled or loaded from that
cache, by name (``BuildLog``). It is on as the engine's event log is on:
no switch, no environment variable.
"""
from __future__ import annotations

import contextlib
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_DEFAULT_DIR = os.path.join(_ROOT, ".jax_cache")
_ROOT_RE = "^" + re.escape(_ROOT + os.sep)


def enable_compile_cache() -> str:
    """Point JAX at the persistent compile cache and return its
    directory. Idempotent; called wherever the program first makes JAX
    compile for a device (engine build, make_train_step, the bench and
    smoke scripts, the tests' conftest)."""
    import jax
    build_log()
    # keep every executable, not only those that took over a second:
    # a serving step at test size compiles in well under that, and
    # equal programs built by different jit objects (one per engine,
    # per worker process) then meet in the cache even inside one run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if jax.config.jax_compilation_cache_dir != _DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR


@contextlib.contextmanager
def metadata_keyed():
    """Programs first compiled inside this context are keyed in the
    persistent cache by their op metadata too (named scopes, module
    paths, source lines), with this checkout's root taken off the file
    names so that a copy elsewhere still hits.

    JAX strips debug information from the key by default, so an
    executable loaded from the cache names its operations as whoever
    compiled it did: a program whose ``jax.named_scope``s are read from
    a device trace (the serving engine's step programs, PERF.md section
    3) would otherwise show the scopes of an older checkout, or none.
    The price is a recompile when a file on the program's traceback
    shifts lines, which programs with Pallas kernels pay already (the
    serialized kernel carries its traceback). Thread-local; a no-op on
    a JAX without these options."""
    try:
        from jax._src import config as jcfg
        keyed = jcfg.compilation_cache_include_metadata_in_key(True)
        rooted = jcfg.hlo_source_file_canonicalization_regex(_ROOT_RE)
    except (ImportError, AttributeError):
        yield
        return
    with keyed, rooted:
        yield


# ------------------------------------------------------- the build log

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_DURATIONS = frozenset((_TRACE, _LOWER, _BACKEND, _CACHE_READ))
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_SECONDS = ("trace_s", "nested_trace_s", "lower_s", "backend_s",
            "cache_read_s")
OTHER = "other"


_WRAPPED = re.compile(r"(\w+)\((.*)\)")     # JAX's "jit(<name>)"


def program_name(fun_name: str) -> str:
    """JAX's name of a build, ``jit(<name>)``, as a device trace and the
    engine's ``_track_program`` spell it: ``jit_<name>``."""
    m = _WRAPPED.fullmatch(fun_name)
    return f"{m.group(1)}_{m.group(2)}" if m else fun_name


def _new_totals() -> Dict[str, Any]:
    return {"builds": 0, "cache_hits": 0, "cache_misses": 0,
            **dict.fromkeys(_SECONDS, 0.0)}


class BuildLog:
    """One record a build, from JAX's own ``jax.monitoring`` events.

    A build of ``jax.jit(f)`` arrives on the thread that called it as
    ``jaxpr_trace_duration`` (``fun_name="f"``), then
    ``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
    (``fun_name="jit(f)"``); between the last two, where the persistent
    cache is asked, ``compile_requests_use_cache`` and on a hit
    ``cache_hits`` and ``cache_retrieval_time_sec`` (the backend span is
    then the load). Jitted functions that ``f`` calls send their own
    trace events BEFORE ``f``'s and inside its time, so a build's
    ``trace_s`` is the one trace event whose name its lowering carries,
    the last before it on the thread; the inner ones that ended inside
    it, at every depth, are ``nested_trace_s``, kept apart and never
    added in. A record:

        {seq, program: "jit_f", t: time.monotonic() at the build's end
         (the clock of the engine's EventLog), trace_s, nested_trace_s,
         lower_s, backend_s, cache_read_s, cache_hit: True | False |
         None (no persistent cache to ask), thread: the builder's ident}

    A build under ``SMALL_S`` in all (an eager operation's
    ``jit(broadcast_in_dim)``) goes into the totals under ``other`` and
    takes no record, unless its program is ``watch``ed. The ring keeps
    the last ``capacity`` records; the totals by program run on.
    Appends take this log's own lock, which nothing on a serving or
    training hot path takes: a warm step builds nothing, so JAX calls
    no listener there."""

    SMALL_S = 0.010
    PENDING = 8192      # traces a thread keeps for the one that encloses them

    def __init__(self, capacity: int = 1024):
        self.capacity = int(capacity)
        self._ring: List[Optional[Dict[str, Any]]] = [None] * capacity
        self._seq = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        self._watched: set = set()
        self._by_program: Dict[str, Dict[str, Any]] = {}
        self._all_trace_s = 0.0
        self._listener_s = 0.0
        self._events = 0
        self._registered = False

    def register(self) -> "BuildLog":
        """Hand JAX the two listeners, once a process."""
        with self._lock:
            if self._registered:
                return self
            self._registered = True
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_seconds)
        monitoring.register_event_listener(self._on_event)
        return self

    def watch(self, program: str) -> None:
        """Every build of ``program`` takes a record, however small (the
        engine's ``jit_seed`` loads in a few milliseconds)."""
        self._watched.add(program)

    # -- the listeners (JAX calls them on the building thread) --------

    def _state(self):
        st = self._local
        if not hasattr(st, "traces"):
            st.traces, st.build = [], None
        return st

    def _on_seconds(self, event: str, seconds: float, **kw) -> None:
        if event not in _DURATIONS:
            return
        t0 = time.perf_counter()
        st = self._state()
        traced = 0.0
        if event == _TRACE:
            self._traced(st, kw.get("fun_name", ""), seconds)
            traced = seconds
        elif event == _LOWER:
            self._lowered(st, kw.get("fun_name", ""), seconds)
        elif event == _CACHE_READ:
            if st.build is not None:
                st.build["cache_read_s"] += seconds
        else:
            self._built(st, kw.get("fun_name", ""), seconds)
        with self._lock:
            self._all_trace_s += traced
            self._events += 1
            self._listener_s += time.perf_counter() - t0

    def _on_event(self, event: str, **_kw) -> None:
        build = self._state().build
        if build is None:
            return
        if event == _CACHE_ASKED:
            # JAX makes a key and "asks" even with no directory to ask
            import jax
            if jax.config.jax_compilation_cache_dir:
                build["cache_hit"] = False
        elif event == _CACHE_HIT:
            build["cache_hit"] = True

    def _traced(self, st, name: str, seconds: float) -> None:
        """A trace that ended now takes in every pending one that ended
        inside it: what stays pending are the outermost traces so far
        (while a program is tracing, the functions it has called), each
        with the seconds of all it enclosed, at every depth (so
        ``nested_trace_s`` can pass ``trace_s``: a function inside a
        function inside the build counts twice, as JAX sent it)."""
        now = time.monotonic()
        nested, pending = 0.0, st.traces
        while pending and pending[-1][2] >= now - seconds:
            _name, own, _end, inner = pending.pop()
            nested += own + inner
        pending.append((name, seconds, now, nested))
        if len(pending) > self.PENDING:
            # a thread that traces and never builds (eval_shape in a
            # loop), or a program that calls more functions than this:
            # the older half becomes one nameless entry that ended when
            # its first did, so a trace takes it in only if it enclosed
            # them all (else they end up in ``unbuilt_trace_s``)
            half = pending[:self.PENDING // 2]
            pending[:len(half)] = [("", sum(e[1] + e[3] for e in half),
                                    half[0][2], 0.0)]

    def _lowered(self, st, fun_name: str, seconds: float) -> None:
        if st.build is not None:        # lowered and never compiled
            self._fold(st.build, counts=False)
        m = _WRAPPED.fullmatch(fun_name)
        name = m.group(2) if m else fun_name
        # the build's own trace: the last pending one of its name. The
        # rest stay: a build can happen INSIDE another program's trace
        # (an eager operation on concrete values), whose pending inner
        # traces are not this build's to drop
        trace_s = nested = 0.0
        for i in range(len(st.traces) - 1, -1, -1):
            if st.traces[i][0] == name:
                _name, trace_s, _end, nested = st.traces.pop(i)
                break
        st.build = {"program": program_name(fun_name), "trace_s": trace_s,
                    "nested_trace_s": nested, "lower_s": seconds,
                    "backend_s": 0.0, "cache_read_s": 0.0,
                    "cache_hit": None}

    def _built(self, st, fun_name: str, seconds: float) -> None:
        build, st.build = st.build, None
        program = program_name(fun_name)
        if build is None or build["program"] != program:
            if build is not None:
                self._fold(build, counts=False)
            build = {"program": program, "cache_hit": None,
                     **dict.fromkeys(_SECONDS, 0.0)}
        build["backend_s"] = seconds
        build["t"] = time.monotonic()
        build["thread"] = threading.get_ident()
        small = (build["trace_s"] + build["lower_s"] + seconds
                 < self.SMALL_S and program not in self._watched)
        self._fold(build, counts=True, record=not small)

    def _fold(self, build, counts: bool, record: bool = False) -> None:
        with self._lock:
            key = build["program"] if record else OTHER
            tot = self._by_program.setdefault(key, _new_totals())
            for k in _SECONDS:
                tot[k] += build[k]
            if counts:
                tot["builds"] += 1
                if build["cache_hit"] is not None:
                    tot["cache_hits" if build["cache_hit"]
                        else "cache_misses"] += 1
            if record:
                build["seq"] = self._seq
                self._ring[self._seq % self.capacity] = build
                self._seq += 1

    # -- the readers --------------------------------------------------

    @property
    def total(self) -> int:
        """Records ever kept: the cursor ``since`` resumes from."""
        return self._seq

    def snapshot(self) -> List[Dict[str, Any]]:
        """The retained records, oldest first (copies)."""
        return self.since(0)

    def since(self, cursor: int) -> List[Dict[str, Any]]:
        """The retained records with ``seq >= cursor``, oldest first."""
        with self._lock:
            lo = max(int(cursor), self._seq - self.capacity, 0)
            return [dict(self._ring[i % self.capacity])
                    for i in range(lo, self._seq)]

    def totals(self) -> Dict[str, Any]:
        """Seconds and counts since the process began: ``programs`` by
        name (``other``: the small builds, and what was lowered and
        never compiled), their sums at the top level, ``unbuilt_trace_s``
        (trace events no build claimed: ``jax.eval_shape``, a jitted
        function traced outside any build) and what listening cost
        (``events``, ``listener_s``). The sum of ``trace_s``,
        ``nested_trace_s`` and ``unbuilt_trace_s`` is every trace event
        JAX sent."""
        with self._lock:
            programs = {k: dict(v) for k, v in self._by_program.items()}
            out = _new_totals()
            for tot in programs.values():
                for k in out:
                    out[k] += tot[k]
            out["unbuilt_trace_s"] = max(
                0.0, self._all_trace_s - out["trace_s"]
                - out["nested_trace_s"])
            out.update(programs=programs, records=self._seq,
                       events=self._events, listener_s=self._listener_s)
            return out


def summarize_builds(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Several builds as one: their seconds summed by part, and
    ``cache_hit`` True where every build that asked the cache hit,
    False where one missed, None where none asked."""
    out: Dict[str, Any] = {k: round(sum(r[k] for r in records), 6)
                           for k in _SECONDS if k != "nested_trace_s"}
    asked = [r["cache_hit"] for r in records if r["cache_hit"] is not None]
    out["cache_hit"] = all(asked) if asked else None
    return out


_BUILD_LOG = BuildLog()


def build_log() -> BuildLog:
    """The process's build log, its listeners registered (idempotent).
    ``enable_compile_cache()`` calls it, so the engine and
    ``make_train_step`` do."""
    return _BUILD_LOG.register()
