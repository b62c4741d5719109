"""Node manager: process lifecycle for the multiprocess runtime.

Capability parity with the reference's node/process management
(python/ray/_private/node.py start_head_processes + services.py
start_raylet, and the raylet WorkerPool worker_pool.h:149): creates the
node's C++ shm store, serves the head, serves this node's object-plane
endpoint (chunked cross-node reads, see runtime/object_plane.py),
spawns/monitors/kills worker processes (the chaos NodeKiller hook used by
fault-tolerance tests). Secondary machines join with NodeAgent
(runtime/node_agent.py), which reuses the same worker-spawn path.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import uuid
from typing import Dict, List, Optional


from ray_tpu.runtime.rpc import RpcServer

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def spawn_worker_process(head_address: str, store_name: str,
                         worker_id: str, resources: Dict[str, float],
                         node_id: str = "head",
                         force_cpu_backend: bool = False,
                         runtime_env: Optional[Dict] = None
                         ) -> subprocess.Popen:
    """Start one worker process (shared by NodeManager and NodeAgent)."""
    env = dict(os.environ)
    # Propagate driver-side flag overrides (chaos delays, spill
    # settings, …) to the worker, reference `_system_config` style.
    from ray_tpu._private.config import GlobalConfig
    env.update(GlobalConfig.to_env())
    if force_cpu_backend:
        env["JAX_PLATFORMS"] = "cpu"
    # The worker watches this pid and exits when it dies (see
    # worker_main._watch_parent) — even on SIGKILL of the spawner no
    # orphan keeps holding RPC ports and the shm segment.
    # (PR_SET_PDEATHSIG is unsuitable: it fires when the spawning
    # THREAD exits, and RPC handler threads spawn workers too.)
    env["RAY_TPU_PARENT_PID"] = str(os.getpid())
    cmd = [sys.executable, "-m", "ray_tpu.runtime.worker_main",
           "--head", head_address,
           "--store", store_name,
           "--worker-id", worker_id,
           "--node-id", node_id,
           "--resources", json.dumps(resources)]
    if runtime_env:
        # Dedicated env-keyed worker (worker_pool.h:149 parity): the
        # env is applied once at startup; the process IS the env.
        cmd += ["--runtime-env", json.dumps(runtime_env)]
        if runtime_env.get("container"):
            # Container env: the worker runs inside the image with
            # host networking + /dev/shm + the repo mounted through
            # (reference: runtime_env/container.py wraps the worker
            # command in podman run).
            from ray_tpu._private.runtime_env import \
                container_command_prefix
            pass_env = {k: v for k, v in env.items()
                        if k.startswith(("RAY_TPU_", "JAX_", "XLA_"))}
            prefix = container_command_prefix(runtime_env,
                                              env_vars=pass_env)
            cmd = prefix + ["python", "-m",
                            "ray_tpu.runtime.worker_main"] + cmd[3:]
    return subprocess.Popen(cmd, cwd=_REPO_ROOT, env=env)


class _NodeService:
    """Worker-process lifecycle RPC served by the node manager — the
    head (its own process, like the reference's gcs_server) calls back
    into it for request_worker/stop_worker."""

    def __init__(self, nm: "NodeManager"):
        self._nm = nm

    def start_worker(self, index: int,
                     resources: Optional[Dict[str, float]] = None,
                     runtime_env: Optional[Dict] = None) -> str:
        return self._nm.start_worker(index, resources, runtime_env)

    def kill_worker(self, worker_id: str) -> None:
        self._nm.kill_worker(worker_id)

    def num_workers(self) -> int:
        return len(self._nm.procs)


class _HeadProxy:
    """Method-call proxy so in-process consumers (tests, fixtures) can
    keep calling `node.head_service.X(...)` with the head in its own
    process."""

    def __init__(self, client):
        self._client = client

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)

        def call(*args, **kwargs):
            return self._client.call(name, *args, **kwargs)
        return call


class NodeManager:
    def __init__(self, num_workers: int = 2,
                 resources_per_worker: Optional[Dict[str, float]] = None,
                 store_capacity: int = 256 * 1024 * 1024,
                 tpu_owner_worker: Optional[int] = None):
        self.resources_per_worker = resources_per_worker or {"CPU": 2}
        # Root of the cluster's process tree: mint the shared RPC
        # secret here so every spawned process (head, workers, node
        # agents) authenticates; external drivers attach by setting
        # RAY_TPU_cluster_token.
        from ray_tpu._private.config import ensure_cluster_token
        ensure_cluster_token()
        self.store_name = f"/raytpu_{os.getpid()}_{uuid.uuid4().hex[:8]}"
        from ray_tpu._private.shm_store import ShmObjectStore
        self.store = ShmObjectStore.create(self.store_name,
                                           store_capacity)
        # Native metrics segment: workers record with lock-free atomics,
        # the head aggregates without RPC (N20, src/metrics/).
        from ray_tpu._private.shm_metrics import ShmMetricsRegistry
        self.metrics = ShmMetricsRegistry.create(self.store_name + "_m")
        # The head is its own PROCESS (gcs_server parity): scheduler
        # loops and dispatch senders don't share the driver's GIL. Its
        # durable tables snapshot into _state_dir for restart recovery.
        self._state_dir: Optional[str] = None
        self.head_proc = self._spawn_head()
        from ray_tpu.runtime.rpc import RpcClient
        self.head_client = RpcClient(self._head_address)
        self.head_service = _HeadProxy(self.head_client)
        # Serve worker-lifecycle callbacks for the head.
        self.node_server = RpcServer(_NodeService(self))
        self.head_client.call("attach_node_service",
                              self.node_server.address)
        # This node's object-plane endpoint + membership entry. The
        # service owns the node's TRANSFER plane: workers delegate
        # bulk fetches to it (ObjectService.fetch_object).
        from ray_tpu.runtime.object_plane import (ObjectPlane,
                                                  ObjectService,
                                                  prewarm_transfer_path)
        self._service_plane = ObjectPlane(
            self.store, RpcClient(self._head_address), node_id="head",
            is_node_service=True)
        self.object_service = ObjectService(self.store,
                                            plane=self._service_plane)
        self.object_server = RpcServer(self.object_service)
        self.head_client.call("register_node", "head",
                              self.object_server.address,
                              self.store_name)
        self._service_plane.refresh_multinode()
        prewarm_transfer_path(self.store, self.object_server.address)
        # Owner-driven eager free: the head broadcasts freed ids on
        # `object_free` (including borrower-protocol frees of escaped
        # objects) — the HEAD node's copies drop here, same as every
        # agent node (node_agent.py does the same for its store).
        try:
            from ray_tpu._private.ids import ObjectID
            from ray_tpu.runtime.pubsub import Subscriber
            self._free_sub = Subscriber(RpcClient(self._head_address))

            def _on_free(_seq, item):
                for oid_hex in item.get("oids", ()):
                    try:
                        self.store.delete(ObjectID.from_hex(oid_hex))
                    except Exception:
                        pass      # not on this node: fine
            self._free_sub.subscribe_stream("object_free", _on_free)
        except Exception:
            self._free_sub = None
        self.procs: Dict[str, subprocess.Popen] = {}
        self.tpu_owner_worker = tpu_owner_worker
        self._stopped = False
        for i in range(num_workers):
            self.start_worker(i)
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         daemon=True, name="node-monitor")
        self._monitor.start()

    def _spawn_head(self, port: int = 0) -> subprocess.Popen:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"     # the head never touches a TPU
        from ray_tpu._private.config import GlobalConfig
        env.update(GlobalConfig.to_env())
        if self._state_dir is None:
            import tempfile
            self._state_dir = tempfile.mkdtemp(prefix="raytpu_head_")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.runtime.head_main",
             "--store", self.store_name,
             "--port", str(port),
             "--state-dir", self._state_dir],
            cwd=_REPO_ROOT, env=env, stdout=subprocess.PIPE, text=True)
        line = proc.stdout.readline()
        if "address=" not in line:
            raise RuntimeError(f"head failed to start: {line!r}")
        self._head_address = line.split("address=")[1].strip()
        return proc

    def restart_head(self):
        """Respawn the head at the SAME address from its persisted
        snapshot (head fault tolerance: clients keep their address;
        workers re-attach via heartbeats). Also the chaos hook for
        kill-the-head tests."""
        try:
            self.head_proc.kill()
            self.head_proc.wait(timeout=10)
        except Exception:
            pass
        port = int(self._head_address.rsplit(":", 1)[1])
        # The old socket may linger in TIME_WAIT; retry binding briefly.
        deadline = time.time() + 15
        while True:
            try:
                self.head_proc = self._spawn_head(port=port)
                break
            except RuntimeError:
                if time.time() > deadline:
                    raise
                time.sleep(0.5)
        # Drop stale pooled connections to the dead head, then
        # re-attach head-node services (retry while it boots).
        self.head_client.close()
        deadline = time.time() + 15
        while True:
            try:
                self.head_client.call("attach_node_service",
                                      self.node_server.address)
                self.head_client.call("register_node", "head",
                                      self.object_server.address,
                                      self.store_name)
                return
            except Exception:
                if time.time() > deadline:
                    raise
                time.sleep(0.2)

    @property
    def head_address(self) -> str:
        return self._head_address

    def start_worker(self, index: int,
                     resources: Optional[Dict[str, float]] = None,
                     runtime_env: Optional[Dict] = None
                     ) -> str:
        worker_id = f"worker-{index}-{uuid.uuid4().hex[:6]}"
        res = dict(resources or self.resources_per_worker)
        # Only a designated worker may own the TPU; everyone else
        # (including ALL workers when no owner is designated) is forced
        # onto the CPU backend so they can't grab the chip — two
        # workers initializing the TPU backend deadlock on libtpu's
        # single-process lock.
        is_owner = (self.tpu_owner_worker is not None and
                    index == self.tpu_owner_worker)
        if is_owner:
            res.setdefault("TPU", 1.0)
        proc = spawn_worker_process(
            self.head_address, self.store_name, worker_id, res,
            node_id="head", force_cpu_backend=not is_owner,
            runtime_env=runtime_env)
        self.procs[worker_id] = proc
        return worker_id

    def wait_for_workers(self, n: Optional[int] = None,
                         timeout: float = 30) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if n is None:
                # Wait for every live worker process to be registered.
                target = sum(1 for p in self.procs.values()
                             if p.poll() is None)
            else:
                target = n
            alive = [w for w in self.head_client.call("list_workers")
                     if w["alive"]]
            if len(alive) >= target:
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"Only {len(self.head_client.call('list_workers'))} of "
            f"{target} workers registered in {timeout}s")

    def kill_worker(self, worker_id: str):
        """Chaos hook: SIGKILL a worker process (the NodeKillerActor
        analogue, python/ray/_private/test_utils.py:1089)."""
        proc = self.procs.get(worker_id)
        if proc is not None:
            proc.kill()
            proc.wait(timeout=10)

    def _monitor_loop(self):
        import traceback

        from ray_tpu.runtime.rpc import RpcError
        while not self._stopped:
            try:
                for worker_id, proc in list(self.procs.items()):
                    if proc.poll() is not None:
                        self.procs.pop(worker_id, None)
                        self.head_client.call("mark_worker_dead",
                                              worker_id)
            except RpcError:
                pass    # head down/restarting: report on next pass
            except Exception:  # noqa: BLE001 — keep monitoring
                traceback.print_exc()
            time.sleep(0.05)

    def stop(self):
        self._stopped = True
        try:
            self.head_client.call("shutdown", timeout=5)
        except Exception:
            pass
        try:
            self.metrics.close()
        except Exception:
            pass
        deadline = time.time() + 3
        for proc in self.procs.values():
            try:
                if proc.poll() is None and time.time() < deadline:
                    proc.terminate()
            except Exception:
                pass
        for proc in self.procs.values():
            try:
                proc.wait(timeout=3)
            except Exception:
                proc.kill()
        try:
            self.head_proc.wait(timeout=3)
        except Exception:
            self.head_proc.kill()
        self.node_server.stop()
        self.object_server.stop()
        self.store.close()
