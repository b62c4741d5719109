"""Per-node hardware/resource reporter.

Role parity with the reference's reporter agent
(dashboard/modules/reporter/reporter_agent.py — psutil snapshots per
node shipped with heartbeats and surfaced by the dashboard). TPU
metrics come from already-initialized jax backends only: the reporter
must never be the one to open a chip (the process that opens it owns
it), so a node that never touched the TPU simply reports none.
"""
from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, Optional


def collect_hw_stats(store=None) -> Dict[str, Any]:
    """One snapshot of this node's hardware state; cheap enough to
    ride every heartbeat."""
    import psutil
    vm = psutil.virtual_memory()
    try:
        disk = psutil.disk_usage("/")
        disk_stats = {"total": disk.total, "used": disk.used,
                      "percent": disk.percent}
    except OSError:
        disk_stats = {}
    stats: Dict[str, Any] = {
        "ts": time.time(),
        # interval=None: non-blocking delta since the previous call
        # (the first call returns 0.0 — fine for a periodic reporter)
        "cpu_percent": psutil.cpu_percent(interval=None),
        "cpu_count": psutil.cpu_count(),
        "load_avg": list(os.getloadavg()),
        "mem": {"total": vm.total, "used": vm.used,
                "percent": vm.percent},
        "disk": disk_stats,
        "pid_count": len(psutil.pids()),
    }
    if store is not None:
        try:
            stats["object_store"] = store.stats()
        except Exception:
            pass
    tpu = _tpu_stats()
    if tpu:
        stats["tpu"] = tpu
    return stats


def _tpu_stats() -> Optional[list]:
    """Per-device HBM stats, ONLY if a jax TPU backend already exists
    in this process (never trigger device initialization here)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return None
    out = []
    for dev in jax.local_devices():
        if dev.platform != "tpu":
            continue
        ms = dev.memory_stats() or {}
        out.append({"id": dev.id, "kind": dev.device_kind,
                    "hbm_bytes_in_use": ms.get("bytes_in_use"),
                    "hbm_bytes_limit": ms.get("bytes_limit")})
    return out or None
