"""Fleet-integrated capacity providers.

``FleetCapacityProvider`` closes the loop between the PR 6 serve-pool
autoscaler and the PR 11 process fleet: a ticket is a REPLICA ID, and
granting it means spawning a real ``ReplicaAgent`` OS process that
registers itself with the (replicated) directory and warms its
engine. ``ready()`` flips only after the agent printed ``READY`` —
i.e. after register + warm — so the autoscaler's harvest step adds a
member that can serve its first request immediately. ``release()``
retires the process; the health-gated drain (engine drained,
in-flight requests finished, lease deregistered, tombstone written)
already happened through ``FleetRouter.scale_down`` by the time the
autoscaler releases the ticket, so reaping here is just process
hygiene — and stays idempotent for the paths where it is not.

``LoopbackAgentProvider`` is the in-process twin used by
``llm.deployment(fleet=..., autoscale=...)``: provisioning constructs
and starts a loopback ``ReplicaAgent`` instead of forking one, with
an optional modeled delay so the ETA plumbing is exercised even
without process spawn latency.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu.autoscaler.node_provider import (CapacityUnavailable,
                                              ReplicaCapacityProvider)

__all__ = ["FleetCapacityProvider", "LoopbackAgentProvider"]


def _addr_pair(ep: Any) -> Tuple[str, int]:
    if isinstance(ep, str):
        host, _, port = ep.rpartition(":")
        return (host or "127.0.0.1", int(port))
    return (str(ep[0]), int(ep[1]))


class FleetNeedsCpuBackend(RuntimeError):
    """Agent processes were asked for on a host whose JAX is not held
    to the CPU. Raised instead of quietly defaulting the agents to the
    CPU backend while the caller believes they serve from chips."""


def require_cpu_backend(env) -> None:
    """Gate every launcher of fleet agent processes (this provider,
    tools/chaos_serve.py --fleet)."""
    if env.get("JAX_PLATFORMS", "").strip() != "cpu":
        raise FleetNeedsCpuBackend(
            "the multi-process fleet is a CPU-tested control plane, "
            "not yet brought up on chips: a chip belongs to one "
            "process and agent processes get no chip assignment, so "
            "an agent spawned here would fight its parent for the "
            "device. Set JAX_PLATFORMS=cpu to run the control plane, "
            "or serve from one process "
            "(LlamaDeployment(num_engine_replicas=N)).")


class FleetCapacityProvider(ReplicaCapacityProvider):
    """Capacity == a warm agent process registered in the directory.

    ``request()`` forks ``python -m ray_tpu.serve.fleet.agent`` aimed
    at the ordered directory endpoint list and returns the replica id
    as the ticket; a waiter thread marks the ticket ready when the
    agent prints ``READY <port>`` (register + engine warm both behind
    it). ``eta_s`` is an EWMA of observed spawn->ready times minus
    elapsed, floored while pending so Retry-After never promises
    capacity that doesn't exist yet.
    """

    def __init__(self, directory_addrs: List[Any], *,
                 model: str = "fake",
                 token_delay_s: float = 0.002,
                 rid_prefix: str = "auto",
                 max_agents: Optional[int] = None,
                 spawn_timeout_s: float = 120.0,
                 initial_eta_s: float = 2.0,
                 extra_args: Optional[List[str]] = None,
                 env: Optional[Dict[str, str]] = None):
        self._dirs = [_addr_pair(e) for e in directory_addrs]
        if not self._dirs:
            raise ValueError("need at least one directory endpoint")
        self._model = model
        self._token_delay_s = token_delay_s
        self._prefix = rid_prefix
        self._max = max_agents
        self._spawn_timeout_s = spawn_timeout_s
        self._eta_ewma = float(initial_eta_s)
        self._extra_args = list(extra_args or [])
        self._env = env
        self._lock = threading.Lock()
        self._n = 0
        # ticket -> {"proc", "t_spawn", "ready", "port", "failed"}
        self._agents: Dict[str, Dict[str, Any]] = {}
        self.stats = {"spawned": 0, "ready": 0, "released": 0,
                      "denied": 0, "spawn_failures": 0}

    # ------------------------------------------------ provider ABC

    def request(self) -> str:
        with self._lock:
            if (self._max is not None
                    and len(self._agents) >= self._max):
                self.stats["denied"] += 1
                raise CapacityUnavailable(
                    f"agent ceiling {self._max} reached")
            self._n += 1
            rid = f"{self._prefix}-{self._n}"
            rec = self._spawn(rid)
            self._agents[rid] = rec
            self.stats["spawned"] += 1
        return rid

    def ready(self, ticket: str) -> bool:
        with self._lock:
            rec = self._agents.get(ticket)
        if rec is None:
            return False
        if rec["failed"]:
            # surface the dead spawn instead of pending forever: the
            # autoscaler treats a vanished ticket as never-ready and
            # its release() reaps what's left
            raise CapacityUnavailable(
                f"agent {ticket} died before READY")
        return bool(rec["ready"])

    def eta_s(self, ticket: str) -> float:
        with self._lock:
            rec = self._agents.get(ticket)
            ewma = self._eta_ewma
        if rec is None or rec["ready"]:
            return 0.0
        remaining = ewma - (time.monotonic() - rec["t_spawn"])
        # never promise sub-250ms while the process is still warming
        return max(remaining, 0.25)

    def release(self, ticket: str) -> None:
        with self._lock:
            rec = self._agents.pop(ticket, None)
        if rec is None:
            return
        self.stats["released"] += 1
        self._reap(rec)

    # ----------------------------------------------------- helpers

    def agent_port(self, ticket: str) -> Optional[int]:
        with self._lock:
            rec = self._agents.get(ticket)
        return rec["port"] if rec else None

    def live_count(self) -> int:
        with self._lock:
            return len(self._agents)

    def stop_all(self) -> None:
        with self._lock:
            recs = list(self._agents.values())
            self._agents.clear()
        for rec in recs:
            self._reap(rec)

    def _spawn(self, rid: str) -> Dict[str, Any]:
        cmd = [sys.executable, "-m", "ray_tpu.serve.fleet.agent",
               "--replica-id", rid, "--model", self._model,
               "--token-delay-s", str(self._token_delay_s)]
        for host, port in self._dirs:
            cmd += ["--directory", f"{host}:{port}"]
        cmd += self._extra_args
        env = dict(self._env if self._env is not None
                   else os.environ)
        require_cpu_backend(env)
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL,
                                env=env, text=True)
        rec = {"proc": proc, "t_spawn": time.monotonic(),
               "ready": False, "port": None, "failed": False}
        threading.Thread(target=self._wait_ready,
                         args=(rid, rec),
                         name=f"provider-wait-{rid}",
                         daemon=True).start()
        return rec

    def _wait_ready(self, rid: str, rec: Dict[str, Any]) -> None:
        deadline = rec["t_spawn"] + self._spawn_timeout_s
        out = rec["proc"].stdout
        while time.monotonic() < deadline:
            line = out.readline()
            if not line:            # EOF: process died pre-READY
                break
            if line.startswith("READY"):
                took = time.monotonic() - rec["t_spawn"]
                with self._lock:
                    rec["port"] = int(line.split()[1])
                    rec["ready"] = True
                    self._eta_ewma = (0.5 * self._eta_ewma
                                      + 0.5 * took)
                    self.stats["ready"] += 1
                # keep draining so the agent never blocks on a full
                # stdout pipe
                for _ in out:
                    pass
                return
        with self._lock:
            rec["failed"] = True
            self.stats["spawn_failures"] += 1

    @staticmethod
    def _reap(rec: Dict[str, Any]) -> None:
        proc = rec["proc"]
        if proc.poll() is None:
            # polite first: rpc_shutdown makes the agent deregister
            # cleanly if it's still serving (release() after a
            # scale_down drain finds it already deregistered — the
            # RPC is then a no-op shutdown)
            port = rec.get("port")
            if port:
                try:
                    from ray_tpu.serve.fleet.agent import AgentClient
                    from ray_tpu.serve.fleet.transport import (
                        SocketTransport)
                    AgentClient(SocketTransport(
                        ("127.0.0.1", port)), timeout_s=2.0
                    ).shutdown()
                except Exception:
                    pass
            try:
                proc.wait(timeout=3.0)
            except Exception:
                proc.terminate()
                try:
                    proc.wait(timeout=3.0)
                except Exception:
                    proc.kill()
                    proc.wait(timeout=3.0)
        try:
            if rec["proc"].stdout is not None:
                rec["proc"].stdout.close()
        except Exception:
            pass


class LoopbackAgentProvider(ReplicaCapacityProvider):
    """In-process provisioning for loopback fleets: 'spawning a host'
    is constructing + starting a ``ReplicaAgent`` around a fresh
    engine. ``agent_factory(replica_id)`` must build, start, AND make
    the agent routable (llm.py registers it in the transport map the
    router resolves addrs against). ``provision_delay_s`` models
    spin-up so the ETA/Retry-After plumbing is exercised."""

    def __init__(self, agent_factory: Callable[[str], Any], *,
                 provision_delay_s: float = 0.0,
                 rid_prefix: str = "auto",
                 max_agents: Optional[int] = None,
                 time_fn: Callable[[], float] = time.monotonic):
        self._factory = agent_factory
        self._delay = float(provision_delay_s)
        self._prefix = rid_prefix
        self._max = max_agents
        self._now = time_fn
        self._lock = threading.Lock()
        self._n = 0
        # ticket -> {"t_request", "agent" | None}
        self._tickets: Dict[str, Dict[str, Any]] = {}
        self.agents: Dict[str, Any] = {}
        self.stats = {"granted": 0, "built": 0, "released": 0,
                      "denied": 0}

    def request(self) -> str:
        with self._lock:
            if (self._max is not None
                    and len(self._tickets) >= self._max):
                self.stats["denied"] += 1
                raise CapacityUnavailable(
                    f"agent ceiling {self._max} reached")
            self._n += 1
            rid = f"{self._prefix}-{self._n}"
            self._tickets[rid] = {"t_request": self._now(),
                                  "agent": None}
            self.stats["granted"] += 1
        return rid

    def ready(self, ticket: str) -> bool:
        with self._lock:
            rec = self._tickets.get(ticket)
            if rec is None:
                return False
            if self._now() - rec["t_request"] < self._delay:
                return False
            build = rec["agent"] is None
            if build:
                rec["agent"] = "building"   # bar re-entry
        if build:
            agent = self._factory(ticket)
            with self._lock:
                rec["agent"] = agent
                self.agents[ticket] = agent
                self.stats["built"] += 1
        return True

    def eta_s(self, ticket: str) -> float:
        with self._lock:
            rec = self._tickets.get(ticket)
            if rec is None or rec["agent"] is not None:
                return 0.0
            return max(self._delay
                       - (self._now() - rec["t_request"]), 0.0)

    def release(self, ticket: str) -> None:
        with self._lock:
            rec = self._tickets.pop(ticket, None)
            agent = self.agents.pop(ticket, None)
        if rec is None:
            return
        self.stats["released"] += 1
        if agent is not None and agent != "building":
            try:
                agent.shutdown()
            except Exception:
                pass
