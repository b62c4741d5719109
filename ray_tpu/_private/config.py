"""Central config/flag system.

Capability parity with the reference's RAY_CONFIG macro table
(src/ray/common/ray_config_def.h: typed defaults, env-var override
``RAY_<name>``, init-time ``_system_config`` dict override). Here flags are a
typed registry with ``RAY_TPU_<name>`` env override and
``init(_system_config={...})`` runtime override; the same table is exported to
native components via environment when worker processes are spawned.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Callable, Dict

_ENV_PREFIX = "RAY_TPU_"


def _parse_bool(v: str) -> bool:
    return v.strip().lower() in ("1", "true", "yes", "on")


_PARSERS: Dict[type, Callable[[str], Any]] = {
    bool: _parse_bool,
    int: int,
    float: float,
    str: str,
}

# name -> (type, default, doc)
_CONFIG_DEFS: Dict[str, tuple] = {}


def define_flag(name: str, typ: type, default: Any, doc: str = "") -> None:
    _CONFIG_DEFS[name] = (typ, default, doc)


# --- Core runtime flags (analogues of ray_config_def.h entries) ------------
define_flag("max_direct_call_object_size", int, 100 * 1024,
            "Results <= this many serialized bytes are inlined into the "
            "caller's in-process store instead of the shared-memory store.")
define_flag("task_retry_delay_ms", int, 0,
            "Delay before the owner resubmits a failed task.")
define_flag("bulk_pull_threshold_bytes", int, 64 * 1024 * 1024,
            "Cross-node pulls at or above this size go through head "
            "pull-slot admission (reference: push_manager.h in-flight "
            "caps); smaller pulls run unthrottled.")
define_flag("bulk_pull_slots_per_source", int, 2,
            "Concurrent bulk pulls one replica serves before new "
            "pullers are told to back off.")
define_flag("transfer_prewarm_mb", int, 128,
            "Scratch bytes each node's transfer daemon moves through "
            "its own socket+arena path at startup (background): the "
            "first bulk receive of a cold process runs ~13x slower "
            "than steady state on shared hosts. Capped at 1/8 of the "
            "store; <16MB disables.")
define_flag("borrow_grace_s", float, 3.0,
            "Window the head waits after an escaped object's owner "
            "drop (or its last borrow drop) before freeing: covers "
            "refs pickled but not yet deserialized/registered by "
            "their receiver.")
define_flag("bulk_pull_global_slots", int, 2,
            "Cluster-wide cap on concurrent bulk pulls. On shared/"
            "virtualized hosts concurrent bulk memory traffic "
            "degrades superlinearly (originally 0.8s solo vs 28s x4 "
            "for a 1 GiB copy), so transfers are "
            "serialized near the host's effective bandwidth; raise "
            "on real multi-host clusters where each node has its own "
            "memory bus.")
define_flag("default_max_retries", int, 3,
            "Default max_retries for normal tasks.")
define_flag("actor_restart_backoff_ms", int, 0,
            "Backoff before restarting a failed actor.")
define_flag("heartbeat_period_ms", int, 1000,
            "Node heartbeat period to the control plane.")
define_flag("num_heartbeats_timeout", int, 30,
            "Heartbeats missed before a node is marked dead.")
define_flag("object_store_memory_bytes", int, 2 * 1024 ** 3,
            "Capacity of the per-node shared-memory object store.")
define_flag("object_spill_threshold", float, 0.8,
            "Fill fraction of the object store above which primary copies "
            "spill to disk.")
define_flag("object_spill_dir", str, "/tmp/ray_tpu_spill",
            "Directory for spilled objects.")
define_flag("worker_pool_prestart", bool, True,
            "Prestart workers based on scheduling backlog.")
define_flag("env_worker_idle_timeout_s", float, 60.0,
            "Idle seconds before a dedicated runtime-env worker "
            "process is reaped (worker_pool idle reaping analogue).")
define_flag("max_pending_actor_calls", int, 10000,
            "Client-side cap on in-flight calls per actor handle.")
define_flag("memory_monitor_threshold", float, 0.0,
            "Node memory used-fraction above which task dispatch pauses "
            "(0 disables; analogue of memory_monitor in the raylet).")
define_flag("memory_monitor_interval_ms", int, 250,
            "Memory monitor poll interval.")
define_flag("testing_delay_us_max", int, 0,
            "Chaos: max random delay injected into every runtime event "
            "handler (analogue of testing_asio_delay_us).")
define_flag("testing_delay_us_min", int, 0,
            "Chaos: min random delay for event handlers.")
define_flag("enable_timeline", bool, True,
            "Record per-task profile events for the timeline dump.")
define_flag("scheduler_spread_threshold", float, 0.5,
            "Hybrid policy: below this node utilization prefer packing "
            "on the local node; above it spread.")
define_flag("lineage_max_bytes", int, 64 * 1024 * 1024,
            "Cap on lineage kept for object reconstruction.")
define_flag("gang_restart_max_attempts", int, 3,
            "Max gang restarts for SPMD mesh actors before giving up.")
define_flag("mesh_checkpoint_interval_s", float, 600.0,
            "Default async-checkpoint interval for gang fault tolerance.")
define_flag("dcn_axis_name", str, "dcn",
            "Mesh axis name used for the cross-slice (DCN) dimension.")
define_flag("log_dir", str, "/tmp/ray_tpu/session_latest/logs",
            "Per-session log directory.")
define_flag("metrics_export_port", int, 0,
            "Prometheus export port (0 = disabled).")
define_flag("cluster_token", str, "",
            "Shared secret authenticating every RPC connection "
            "(redis-password analogue). Auto-generated by the node "
            "manager and propagated to child processes; set "
            "RAY_TPU_cluster_token to attach an external driver. "
            "Empty = auth disabled (standalone/manual setups).")


def ensure_cluster_token() -> str:
    """Generate the cluster secret at the root of a process tree (the
    node manager) if none is configured; children inherit it via
    to_env()/os.environ."""
    tok = GlobalConfig.cluster_token
    if not tok:
        import secrets
        tok = secrets.token_hex(16)
        GlobalConfig.apply_system_config({"cluster_token": tok})
        os.environ[_ENV_PREFIX + "cluster_token"] = tok
    return tok


class _Config:
    """Singleton flag store with env + runtime overrides."""

    def __init__(self):
        self._lock = threading.Lock()
        self._values: Dict[str, Any] = {}
        self._load_defaults()

    def _load_defaults(self):
        for name, (typ, default, _doc) in _CONFIG_DEFS.items():
            env = os.environ.get(_ENV_PREFIX + name)
            if env is not None:
                self._values[name] = _PARSERS[typ](env)
            else:
                self._values[name] = default

    def get(self, name: str) -> Any:
        try:
            return self._values[name]
        except KeyError:
            raise KeyError(f"Unknown config flag: {name}") from None

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        return self.get(name)

    def apply_system_config(self, overrides: Dict[str, Any]) -> None:
        """Runtime override, the ``ray.init(_system_config=...)`` analogue."""
        with self._lock:
            for name, value in overrides.items():
                if name not in _CONFIG_DEFS:
                    raise KeyError(f"Unknown config flag: {name}")
                typ = _CONFIG_DEFS[name][0]
                if isinstance(value, str) and typ is not str:
                    value = _PARSERS[typ](value)
                if not isinstance(value, typ):
                    # bool is an int subclass; order of checks handles it.
                    raise TypeError(
                        f"Flag {name} expects {typ.__name__}, "
                        f"got {type(value).__name__}")
                self._values[name] = value

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._values)

    def to_env(self) -> Dict[str, str]:
        """Serialize non-default flags for child worker processes."""
        out = {}
        for name, (typ, default, _doc) in _CONFIG_DEFS.items():
            v = self._values[name]
            if v != default:
                out[_ENV_PREFIX + name] = json.dumps(v) if typ not in (
                    str,) else v
        return out

    def reset(self):
        with self._lock:
            self._values.clear()
            self._load_defaults()


GlobalConfig = _Config()


def chaos_delay():
    """Shared chaos hook: random delay injected into runtime event
    handlers (N22, common/asio/asio_chaos.cc analogue). Controlled by
    the testing_delay_us_{min,max} flags."""
    hi = GlobalConfig.testing_delay_us_max
    if hi:
        import random
        import time
        lo = GlobalConfig.testing_delay_us_min
        time.sleep(random.uniform(lo, hi) / 1e6)
