"""Cluster: the multiprocess test/launch fixture.

Capability parity with the reference's ray.cluster_utils.Cluster
(python/ray/cluster_utils.py:99 add_node — multiple real raylets on one
machine as the primary multi-node test vehicle, SURVEY.md §4.2): real
worker PROCESSES + the C++ shm store + the head scheduler, with
kill-a-worker chaos for fault-tolerance tests.
"""
from __future__ import annotations

from typing import Dict, Optional

from ray_tpu.runtime.client import DistributedRuntime
from ray_tpu.runtime.node import NodeManager


class Cluster:
    def __init__(self, num_workers: int = 2,
                 resources_per_worker: Optional[Dict[str, float]] = None,
                 store_capacity: int = 256 * 1024 * 1024,
                 connect: bool = True):
        self.node = NodeManager(num_workers=num_workers,
                                resources_per_worker=resources_per_worker,
                                store_capacity=store_capacity)
        self.agent_procs: Dict[str, object] = {}
        self.node.wait_for_workers(num_workers)
        self.runtime = DistributedRuntime(
            self.node.head_address, self.node.store_name,
            node_manager=self.node)
        self._connected = False
        if connect:
            self.connect()

    def connect(self) -> DistributedRuntime:
        """Install this cluster as the process-global runtime."""
        from ray_tpu._private import worker as worker_mod
        from ray_tpu._private.object_ref import \
            set_global_reference_counter
        if worker_mod.is_initialized():
            if worker_mod._worker.runtime is self.runtime:
                return self.runtime   # already connected: no-op
            worker_mod.shutdown()
        worker_mod._worker = worker_mod.Worker(self.runtime,
                                               mode="driver")
        set_global_reference_counter(self.runtime.ref_counter)
        from ray_tpu._private.object_ref import set_borrow_notifier
        set_borrow_notifier(self.runtime.plane.note_borrow)
        self._connected = True
        return self.runtime

    def add_worker(self, resources: Optional[Dict[str, float]] = None
                   ) -> str:
        index = len(self.node.procs)
        wid = self.node.start_worker(index, resources)
        self.node.wait_for_workers()   # all live processes registered
        return wid

    def add_node(self, num_workers: int = 2,
                 resources_per_worker: Optional[Dict[str, float]] = None,
                 store_capacity: int = 256 * 1024 * 1024,
                 timeout: float = 60.0) -> str:
        """Join a SECOND node as a separate process tree with its own
        shm store segment (the multi-raylet `Cluster.add_node` analogue,
        python/ray/cluster_utils.py:165 — here it exercises the real
        cross-node object plane)."""
        import json
        import os
        import subprocess
        import sys
        import time
        env = dict(os.environ)
        from ray_tpu._private.config import GlobalConfig
        env.update(GlobalConfig.to_env())
        env["JAX_PLATFORMS"] = "cpu"
        alive_before = len([w for w in self.runtime.list_workers()
                            if w["alive"]])
        repo = os.path.abspath(os.path.join(
            os.path.dirname(__file__), "..", ".."))
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.runtime.node_agent",
             "--head", self.node.head_address,
             "--workers", str(num_workers),
             "--resources", json.dumps(resources_per_worker or
                                       {"CPU": 2}),
             "--store-capacity", str(store_capacity)],
            cwd=repo, env=env, stdout=subprocess.PIPE, text=True)
        line = proc.stdout.readline()   # "node_agent ready node_id=..."
        if "node_id=" not in line:
            raise RuntimeError(f"node agent failed to start: {line!r}")
        node_id = line.split("node_id=")[1].split()[0]
        self.agent_procs[node_id] = proc
        deadline = time.time() + timeout
        # Wait for THIS node's workers on top of whatever was already
        # registered cluster-wide (not just the head node's procs —
        # a second add_node would otherwise return early).
        want = num_workers + alive_before
        while time.time() < deadline:
            if len([w for w in self.runtime.list_workers()
                    if w["alive"]]) >= want:
                return node_id
            time.sleep(0.05)
        raise TimeoutError(f"node {node_id}: workers not registered")

    def kill_node(self, node_id: str):
        """SIGKILL a secondary node's whole process tree (agent +
        workers die with it via the agent monitor being gone; worker
        processes are killed explicitly through the head's node table)."""
        proc = self.agent_procs.pop(node_id, None)
        if proc is not None:
            proc.kill()
            proc.wait(timeout=10)
        # The head notices via missed heartbeats; tests shorten the
        # heartbeat config or call mark_node_dead directly for speed.

    def nodes(self):
        return self.runtime.list_nodes()

    def kill_worker(self, worker_id: str):
        self.node.kill_worker(worker_id)

    def start_node_killer(self, interval_s: float = 1.0,
                          max_kills: int = 3,
                          respawn: bool = True) -> "NodeKiller":
        """Chaos: kill a random worker every interval (NodeKillerActor
        analogue, python/ray/_private/test_utils.py:1089)."""
        return NodeKiller(self, interval_s, max_kills, respawn).start()

    def workers(self):
        return self.runtime.list_workers()

    def shutdown(self):
        from ray_tpu._private import worker as worker_mod
        from ray_tpu._private.object_ref import \
            set_global_reference_counter
        if self._connected:
            worker_mod._worker = None
            set_global_reference_counter(None)
            from ray_tpu._private.object_ref import set_borrow_notifier
            set_borrow_notifier(None)
            self._connected = False
        for proc in self.agent_procs.values():
            try:
                proc.terminate()
            except Exception:
                pass
        for proc in self.agent_procs.values():
            try:
                proc.wait(timeout=5)
            except Exception:
                try:
                    proc.kill()
                except Exception:
                    pass
        self.agent_procs.clear()
        self.runtime.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()


class NodeKiller:
    """Kills a random live worker every ``interval_s`` until ``max_kills``
    is reached, optionally respawning a replacement — the chaos vehicle
    for fault-tolerance tests (reference: NodeKillerActor + chaos_test/)."""

    def __init__(self, cluster: Cluster, interval_s: float,
                 max_kills: int, respawn: bool):
        import threading
        self.cluster = cluster
        self.interval_s = interval_s
        self.max_kills = max_kills
        self.respawn = respawn
        self.num_kills = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="node-killer")

    def start(self) -> "NodeKiller":
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10)

    def _run(self):
        import random
        while not self._stop.is_set() and self.num_kills < self.max_kills:
            if self._stop.wait(self.interval_s):
                return
            alive = [w["worker_id"]
                     for w in self.cluster.node.head_service.list_workers()
                     if w["alive"]]
            if not alive:
                continue
            victim = random.choice(alive)
            self.cluster.kill_worker(victim)
            self.num_kills += 1
            if self.respawn:
                try:
                    self.cluster.add_worker()
                except Exception:
                    pass
