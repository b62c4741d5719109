"""Global worker: process-wide connection to a runtime.

Capability parity with the reference's Worker singleton + ``ray.init``
bootstrapping (python/ray/_private/worker.py:404,1022). The runtime behind it
is pluggable: LocalRuntime (in-process, default and test fake) or the
distributed node runtime (ray_tpu.runtime, multi-process).
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, Optional

from ray_tpu._private.config import GlobalConfig
from ray_tpu._private.ids import JobID
from ray_tpu._private.local_runtime import LocalRuntime
from ray_tpu._private.object_ref import set_global_reference_counter

logger = logging.getLogger(__name__)


class Worker:
    def __init__(self, runtime, mode: str):
        self.runtime = runtime
        self.mode = mode  # "local" | "node" | "driver" | "worker" | "client"
        self.namespace = "default"


_lock = threading.Lock()
_worker: Optional[Worker] = None


def is_initialized() -> bool:
    return _worker is not None


def global_worker() -> Worker:
    if _worker is None:
        # Auto-init like the reference does on first API use. Two threads
        # may race here; init() resolves it under its lock.
        init(ignore_reinit_error=True)
    return _worker


def _detect_tpu_chips() -> int:
    """Count local TPU chips. ``JAX_PLATFORMS=cpu`` answers 0 without
    touching JAX; otherwise whatever JAX raises while opening its
    backend is raised here too — a node whose chip failed to open must
    not come up quietly with no ``TPU`` resource."""
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return 0
    import jax
    return sum(1 for d in jax.devices() if d.platform != "cpu")


def init(address: Optional[str] = None,
         num_cpus: Optional[int] = None,
         num_tpus: Optional[int] = None,
         resources: Optional[Dict[str, float]] = None,
         namespace: Optional[str] = None,
         ignore_reinit_error: bool = False,
         _system_config: Optional[Dict[str, Any]] = None,
         log_to_driver: bool = True) -> Worker:
    """Connect this process to a runtime (starting one if needed)."""
    global _worker
    with _lock:
        if _worker is not None:
            if ignore_reinit_error:
                return _worker
            raise RuntimeError(
                "ray_tpu.init() called twice; pass "
                "ignore_reinit_error=True to ignore")
        if _system_config:
            GlobalConfig.apply_system_config(_system_config)

        if address in (None, "local"):
            res: Dict[str, float] = dict(resources or {})
            res.setdefault("CPU", float(num_cpus if num_cpus is not None
                                        else max(4, os.cpu_count() or 4)))
            tpus = (num_tpus if num_tpus is not None
                    else _detect_tpu_chips())
            if tpus:
                res.setdefault("TPU", float(tpus))
            res.setdefault("memory", 8 * 1024 ** 3)
            runtime = LocalRuntime(res, job_id=JobID.next())
            _worker = Worker(runtime, mode="local")
        elif address.startswith("ray://"):
            # Proxied remote driver (Ray Client parity): one endpoint,
            # no cluster network/shm access needed on this machine.
            from ray_tpu.runtime.client_proxy import ProxyRuntime
            runtime = ProxyRuntime(address[len("ray://"):])
            _worker = Worker(runtime, mode="client")
        else:
            # Distributed attach (node runtime); implemented in
            # ray_tpu.runtime.client.
            from ray_tpu.runtime.client import connect_to_cluster
            runtime = connect_to_cluster(address)
            if log_to_driver:
                runtime.start_log_streaming()
            _worker = Worker(runtime, mode="driver")
        if namespace:
            _worker.namespace = namespace
        set_global_reference_counter(runtime.ref_counter)
        plane = getattr(runtime, "plane", None)
        if plane is not None:
            from ray_tpu._private.object_ref import set_borrow_notifier
            set_borrow_notifier(plane.note_borrow)
        return _worker


def shutdown():
    global _worker
    with _lock:
        if _worker is None:
            return
        set_global_reference_counter(None)
        from ray_tpu._private.object_ref import set_borrow_notifier
        set_borrow_notifier(None)
        try:
            _worker.runtime.shutdown()
        finally:
            _worker = None
