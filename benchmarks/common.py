"""What every runner shares: where the files are, the table of peaks,
the compile meter, the compile-cache report, seeds, percentiles and the
result line. Nothing here touches a device until a function is called.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    """Everything that is not the result goes to stdout BEFORE the last
    line (the driver reads only that line)."""
    print(msg, flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_named(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmarks: no {what} named {name!r}; have "
                     f"{[e['name'] for e in entries]}")


def load_rehearsal_cell(name: str) -> Dict[str, Any]:
    """benchmarks/rehearsal/cells/<name>.json: a toy cell for
    ``--rehearse``, one file each, with a ``workloads`` entry's keys,
    ``metrics_as`` (the real cell whose metric lists it borrows) and
    ``reports`` (the per-layer metrics a CPU run of it must show: the
    tests' expectation, read by nothing else)."""
    path = os.path.join(HERE, "rehearsal", "cells", name + ".json")
    if not os.path.exists(path):
        have = sorted(f[:-5] for f in os.listdir(os.path.dirname(path))
                      if f.endswith(".json"))
        raise SystemExit(f"benchmarks: no rehearsal cell named {name!r};"
                         f" have {have}")
    with open(path) as f:
        return json.load(f)


def metrics_of_cell(bench: Dict[str, Any], section: str,
                    cell: str) -> List[Dict[str, Any]]:
    """The metrics of ``section`` that this cell reports: those with no
    ``workloads`` key, and those that list the cell."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def _load_by_path(subdir: str, name: str, what: str):
    """The module benchmarks/<subdir>/<name>.py. The name may hold
    dots, so the file is loaded by path."""
    path = os.path.join(HERE, subdir, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmarks: {what} {name!r} has no file at "
                         f"{path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{subdir}." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric_reader(name: str):
    """benchmarks/metrics/<name>.py's ``read``, found by the metric's
    name."""
    return _load_by_path("metrics", name, "per-layer metric").read


# what a family file must hold, by the ``kind`` of the configurations
# that name it (benchmarks/README.md, "A family", says what each is)
FAMILY_ATTRS = {
    "serve": ("program_config", "model", "init_params",
              "reference_weights", "reference_logits",
              "kv_bytes_per_token", "decode_step_bytes"),
    "train": ("program_config", "model", "init_params", "loss_fn",
              "sharding_rules", "reference_loss_and_grad_norm",
              "train_flops_per_token", "attention_shape"),
}


def load_family(name: str, kind: str):
    """benchmarks/families/<name>.py, found by a configuration file's
    ``family`` key as a reader is found by its metric's name: the one
    place that knows the model's classes, its seeded weights, its plain
    reference and its byte and FLOP counts. A file that lacks what its
    ``kind`` of runner asks for is refused here, not in the window."""
    if kind not in FAMILY_ATTRS:
        raise SystemExit(f"benchmarks: unknown kind {kind!r}")
    mod = _load_by_path("families", name, "family")
    missing = [a for a in FAMILY_ATTRS[kind] if not hasattr(mod, a)]
    if missing:
        raise SystemExit(f"benchmarks: family {name!r} serves no "
                         f"{kind!r} configuration: its file lacks "
                         f"{missing}")
    return mod


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Published peaks of the chip; an unknown kind is an error, never
    a default."""
    table = load_json("peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(
            f"benchmarks: no peaks on record for device kind "
            f"{device_kind!r}; add it to benchmarks/peaks.json with its "
            f"source")
    return table[device_kind]


def refuse_selectors() -> None:
    """Cells measure the program's defaults: any RAY_TPU_* variable
    (kernel, KV dtype, overlap selectors, cluster addresses) would
    make this run measure something else."""
    bad = sorted(k for k in os.environ if k.startswith("RAY_TPU_"))
    if bad:
        raise SystemExit(f"benchmarks: unset {bad}; cells measure the "
                         f"defaults")


# ----------------------------------------------------------- seeds

def jax_key(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed. --seed may exceed 31 bits
    and jax.random.PRNGKey refuses more, so it goes in as two words;
    ``stream`` separates independent uses."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise SystemExit("benchmarks: --seed must be >= 0")
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, stream)


# ------------------------------------------------------- statistics

def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in 0..100."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def whole_rounds_rate(times, t_open: float, t_close: float,
                      guard: float):
    """Tokens a second over a window of whole engine rounds.

    The engine hands its clients a round's tokens in one burst (a
    decode chunk of every live slot, about half a second of work), so
    the tokens counted between two fixed instants jump by a whole burst
    (1.3 % of a 40 s window) when either instant falls a millisecond to
    the other side of one. As the train runner ends its window at a
    step's end, this window opens at the first arrival at or after
    ``t_open`` and closes at the first at or after ``t_close``: it
    counts every token after the opening burst up to and with the
    closing one (each burst's tokens were made in the round before it)
    over the time between the two. ``guard`` lets a burst's stragglers
    in: it only has to outlast a burst; where tokens flow without
    bursts the count is that of a window ``guard`` later, as long.

    Returns (rate, seconds, tokens, opening burst's tokens), or None
    where no token came at or after either instant.
    """
    import bisect
    ts = sorted(times)
    i, j = bisect.bisect_left(ts, t_open), bisect.bisect_left(ts, t_close)
    if j >= len(ts) or ts[j] <= ts[i]:
        return None
    first = bisect.bisect_left(ts, ts[i] + guard)
    tokens = bisect.bisect_left(ts, ts[j] + guard) - first
    seconds = ts[j] - ts[i]
    return tokens / seconds, seconds, tokens, first - i


# ---------------------------------------------------- compile meter

_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileMeter:
    """What JAX itself reports about compilation (jax.monitoring),
    summed since ``start()``: seconds tracing, lowering, in the backend
    compiler and reading the persistent cache; cache hits and misses;
    and ``programs``, the number of executables built or loaded (one
    backend_compile_duration event each, cached or not). The window
    must add nothing to ``programs``. Copied from chip_smoke.py."""

    def __init__(self):
        self.totals = dict.fromkeys(_JAX_EVENTS.values(), 0.0)
        self.totals["programs"] = 0.0

    def start(self) -> "CompileMeter":
        from jax import monitoring

        def on_duration(event, seconds, **_):
            if event in _JAX_EVENTS:
                self.totals[_JAX_EVENTS[event]] += seconds
                if _JAX_EVENTS[event] == "compile_s":
                    self.totals["programs"] += 1

        def on_event(event, **_):
            if event in _JAX_EVENTS:
                self.totals[_JAX_EVENTS[event]] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        return self

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)

    def since(self, snap: Dict[str, float]) -> Dict[str, float]:
        return {k: self.totals[k] - v for k, v in snap.items()}


def cache_report(path: str, top: int = 0) -> str:
    """Entries and bytes in the compile cache, the size JAX may evict
    down to, and the largest entries: a cache smaller than a cell's
    working set evicts in a cycle and never hits (PERF.md, PR 23).
    Copied from chip_smoke.py."""
    import jax
    sizes = {}
    if os.path.isdir(path):
        for name in os.listdir(path):
            if name.endswith("-cache"):
                sizes[name] = os.path.getsize(os.path.join(path, name))
    cap = jax.config.jax_compilation_cache_max_size
    out = (f"{len(sizes)} entries, {sum(sizes.values()) / 2**20:.1f} "
           f"MiB (max size "
           f"{'unlimited' if cap < 0 else f'{cap / 2**20:.0f} MiB'})")
    for name in sorted(sizes, key=sizes.get, reverse=True)[:top]:
        out += f"\n[cache]   {sizes[name] / 2**20:7.1f} MiB  {name[:60]}"
    return out


# ------------------------------------------------------------ device

def device_block(devices, chips: int) -> Dict[str, Any]:
    """The ``device`` object of the result line: as JAX reports it, and
    the peak bytes on the fullest chip used. The TPU's allocator counts
    live arrays under ``peak_bytes_in_use`` and the compiled programs'
    temporaries under ``peak_bytes_reserved``; the chip held both."""
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class Timer:
    """``with Timer("phase") as t`` logs and keeps the seconds, and
    what part of them JAX spent compiling."""

    def __init__(self, name: str, meter: Optional[CompileMeter] = None):
        self.name, self.meter = name, meter

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.m0 = self.meter.snapshot() if self.meter else None
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        if exc[0] is None:
            extra = ""
            if self.meter:
                d = self.meter.since(self.m0)
                if any(d.values()):
                    extra = (f" (trace {d['trace_s']:.1f} lower "
                             f"{d['lower_s']:.1f} backend "
                             f"{d['compile_s']:.1f} cache-read "
                             f"{d['cache_read_s']:.1f} s; hits "
                             f"{d['cache_hits']:.0f} misses "
                             f"{d['cache_misses']:.0f})")
            log(f"[{self.name}] {self.seconds:.2f} s{extra}")


class Tracer(threading.Thread):
    """jax.profiler over a few seconds of the steady window: starts at
    30 % of the window and runs ``seconds`` (at most 40 % of it).
    ``t0``/``t1`` are the traced span on time.monotonic()."""

    def __init__(self, out_dir: str, t_open: float, window_s: float,
                 seconds: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.out_dir = out_dir
        self.start_at = t_open + 0.3 * window_s
        self.seconds = min(seconds, 0.4 * window_s)
        self.t0 = self.t1 = None

    def run(self):
        import jax
        time.sleep(max(0.0, self.start_at - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # device and host TraceMes only
        opts.host_tracer_level = 2
        self.t0 = time.monotonic()
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        time.sleep(self.seconds)
        self.t1 = time.monotonic()
        jax.profiler.stop_trace()

    @property
    def span(self):
        return (self.t0, self.t1)


def prepare_trace(trace_dir: str, start, stop) -> None:
    """--trace 2, once the window's numbers are taken: start and stop
    the profiler once through the program's control (``start(dir)``,
    ``stop()``) and throw that trace away, so that the cost of the
    first start falls into no number; then leave ``trace_dir`` empty
    for the trace that counts."""
    import shutil
    scrap = trace_dir + ".first"
    shutil.rmtree(scrap, ignore_errors=True)
    start(scrap)
    stop()
    shutil.rmtree(scrap, ignore_errors=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)


def emit_result(result: Dict[str, Any]) -> None:
    """The contract's one JSON object, last on standard output."""
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
