"""ctypes binding for the C++ shared-memory metrics core
(src/metrics/shm_metrics.cc — the native stats substrate, N20).

Worker processes record counters/gauges/histograms with lock-free
atomics into a shm segment created by the node; the head reads the
whole segment for aggregation/Prometheus export without any RPC on the
metrics hot path (reference: src/ray/stats/metric.h DEFINE_stats +
metric_exporter.cc, re-designed for one-host shm instead of the
opencensus-to-agent pipeline).
"""
from __future__ import annotations

import ctypes
import os
import threading
from typing import Dict, List, Optional

from ray_tpu._private.native_build import ensure_built

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))
_SRC = os.path.join(_REPO_ROOT, "src", "metrics", "shm_metrics.cc")
_BUILD_DIR = os.path.join(_REPO_ROOT, "build")
_LIB = os.path.join(_BUILD_DIR, "libshm_metrics.so")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None

TYPE_COUNTER = 1
TYPE_GAUGE = 2
TYPE_HISTOGRAM = 3


def _load() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(ensure_built(_SRC, _LIB))
        lib.metrics_create.restype = ctypes.c_void_p
        lib.metrics_create.argtypes = [ctypes.c_char_p]
        lib.metrics_attach.restype = ctypes.c_void_p
        lib.metrics_attach.argtypes = [ctypes.c_char_p]
        lib.metrics_detach.argtypes = [ctypes.c_void_p]
        lib.metrics_destroy.argtypes = [ctypes.c_void_p,
                                        ctypes.c_char_p]
        for fn in ("metrics_counter_add", "metrics_gauge_set",
                   "metrics_histogram_observe"):
            f = getattr(lib, fn)
            f.restype = ctypes.c_int
            f.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                          ctypes.c_double]
        lib.metrics_num_slots.restype = ctypes.c_int
        lib.metrics_num_slots.argtypes = [ctypes.c_void_p]
        lib.metrics_read_slot.restype = ctypes.c_int
        lib.metrics_read_slot.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.metrics_name_size.restype = ctypes.c_int
        lib.metrics_num_buckets.restype = ctypes.c_int
        _lib = lib
        return lib


class ShmMetricsRegistry:
    """One node-wide metrics segment; create() on the node, attach()
    from workers."""

    def __init__(self, handle: int, name: str, owner: bool):
        self._lib = _load()
        self._h = handle
        self.name = name
        self._owner = owner
        self._name_size = self._lib.metrics_name_size()
        self._num_buckets = self._lib.metrics_num_buckets()

    @classmethod
    def create(cls, name: str) -> "ShmMetricsRegistry":
        lib = _load()
        h = lib.metrics_create(name.encode())
        if not h:
            raise OSError(f"metrics_create({name!r}) failed")
        return cls(h, name, owner=True)

    @classmethod
    def attach(cls, name: str) -> "ShmMetricsRegistry":
        lib = _load()
        h = lib.metrics_attach(name.encode())
        if not h:
            raise OSError(f"metrics_attach({name!r}) failed")
        return cls(h, name, owner=False)

    def close(self):
        if self._h:
            if self._owner:
                self._lib.metrics_destroy(self._h, self.name.encode())
            else:
                self._lib.metrics_detach(self._h)
            self._h = None

    # --- recording (lock-free in C++) -------------------------------------

    def counter_add(self, key: str, delta: float = 1.0):
        self._lib.metrics_counter_add(self._h, key.encode(), delta)

    def gauge_set(self, key: str, value: float):
        self._lib.metrics_gauge_set(self._h, key.encode(), value)

    def histogram_observe(self, key: str, value: float):
        self._lib.metrics_histogram_observe(self._h, key.encode(),
                                            value)

    # --- aggregation (head side) ------------------------------------------

    def read_all(self) -> Dict[str, Dict]:
        out: Dict[str, Dict] = {}
        n = self._lib.metrics_num_slots(self._h)
        name_buf = ctypes.create_string_buffer(self._name_size)
        value = ctypes.c_double()
        count = ctypes.c_uint64()
        total = ctypes.c_double()
        buckets = (ctypes.c_uint64 * self._num_buckets)()
        for i in range(n):
            t = self._lib.metrics_read_slot(
                self._h, i, name_buf, ctypes.byref(value),
                ctypes.byref(count), ctypes.byref(total), buckets)
            if t == 0:
                continue
            key = name_buf.value.decode(errors="replace")
            rec: Dict = {"type": {1: "counter", 2: "gauge",
                                  3: "histogram"}[t]}
            if t == TYPE_COUNTER:
                rec["value"] = value.value
                rec["num_samples"] = count.value
            elif t == TYPE_GAUGE:
                rec["value"] = value.value
            else:
                rec["count"] = count.value
                rec["sum"] = total.value
                rec["buckets"] = list(buckets)
            out[key] = rec
        return out

    def prometheus_text(self) -> str:
        """Prometheus exposition of the whole segment."""
        lines: List[str] = []
        for key, rec in sorted(self.read_all().items()):
            name = key.split("|", 1)[0]
            tags = ""
            if "|" in key:
                raw = key.split("|", 1)[1]
                pairs = [p.split("=", 1) for p in raw.split(",") if p]
                tags = "{" + ",".join(
                    f'{k}="{v}"' for k, v in pairs) + "}"
            if rec["type"] == "histogram":
                lines.append(f"# TYPE {name} histogram")
                lines.append(f"{name}_count{tags} {rec['count']}")
                lines.append(f"{name}_sum{tags} {rec['sum']}")
            else:
                lines.append(f"# TYPE {name} {rec['type']}")
                lines.append(f"{name}{tags} {rec['value']}")
        return "\n".join(lines) + "\n"


def metric_key(name: str, tags: Optional[Dict[str, str]] = None) -> str:
    if not tags:
        return name
    return name + "|" + ",".join(
        f"{k}={v}" for k, v in sorted(tags.items()))
