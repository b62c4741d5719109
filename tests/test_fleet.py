"""Fleet control plane tests (serve/fleet/): lease-fenced
membership, the transport seam, and the router's at-most-once
resubmit contract.

Layering mirrors the modules:

- directory units: fencing-token monotonicity, tombstoned zombie
  rejection, lease expiry + confirm_dead adjudication, restart
  recovery via min_fence — all on a fake clock, zero sleeps.
- transport units: wire envelope round-trip, typed errors crossing
  BY NAME, socket framing limits, the partition gate.
- agent units: deterministic lease-lapse self-fence (manually driven
  renew_once on a fake clock), admission refusal while fenced,
  generation-bump re-registration.
- router e2e on loopback: token identity, session stickiness,
  zero-delivery resubmit exactly once, seeded FaultyTransport sweep
  proving duplicates/drops never double-deliver a token.
- the three-way race: directory-lease-expiry vs drain vs kill, all
  in one fleet, 0 lost / 0 mismatched.
- cross-process: a 2-agent mini chaos campaign (fake engines) in
  tier-1; the full tiny-model campaign behind ``slow``.
"""
import threading
import time

import pytest

from ray_tpu.serve.errors import (EngineDraining, EngineOverloaded,
                                  EngineShutdown)
from ray_tpu.serve.fleet import wire
from ray_tpu.serve.fleet.agent import (ReplicaAgent, ScriptedEngine,
                                       scripted_completion)
from ray_tpu.serve.fleet.directory import (DirectoryClient,
                                           FleetDirectory)
from ray_tpu.serve.fleet.router import FleetRouter
from ray_tpu.serve.fleet.transport import (FaultyTransport,
                                           LoopbackTransport,
                                           SocketServer,
                                           SocketTransport, Transport,
                                           TransportError,
                                           TransportTimeout)
from ray_tpu.serve.fleet.wire import (AgentFenced, StaleFencingToken,
                                      UnknownMember)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------- directory


def test_directory_fencing_and_tombstones():
    clock = FakeClock()
    d = FleetDirectory(lease_ttl_s=1.0, time_fn=clock)
    dc = DirectoryClient(LoopbackTransport(d.handle))

    r = dc.register("r0", ["loopback", "r0"], generation=0)
    fence0 = r["fence"]
    assert r["lease_ttl_s"] == 1.0

    # renewing with the wrong token is a zombie write
    with pytest.raises(StaleFencingToken):
        dc.renew("r0", fence0 + 99)
    # renewing an unknown member tells the agent to re-register
    with pytest.raises(UnknownMember):
        dc.renew("nope", 1)
    assert dc.renew("r0", fence0) == {"lease_ttl_s": 1.0}

    # a live lease is NOT dead, however the transport looked
    v = dc.confirm_dead("r0", fence0)
    assert v["dead"] is False and v["lease_remaining_s"] > 0

    # lease lapse -> death candidate; confirm_dead reaps + tombstones
    clock.advance(1.5)
    snap = dc.snapshot()["members"]
    assert snap[0]["expired"] is True
    v = dc.confirm_dead("r0", fence0)
    assert v["dead"] is True and v["reason"] == "lease_expired"

    # the dead generation can never register again (zombie)
    with pytest.raises(StaleFencingToken):
        dc.register("r0", ["loopback", "r0"], generation=0)
    # but the NEXT incarnation can, under a strictly newer fence
    r2 = dc.register("r0", ["loopback", "r0"], generation=1,
                     min_fence=fence0)
    assert r2["fence"] > fence0

    # a superseded fence is dead even while the new lease is live
    v = dc.confirm_dead("r0", fence0)
    assert v["dead"] is True and v["reason"] == "superseded"
    stats = dc.stats()
    assert stats["tombstones"] == {"r0": 0}
    assert stats["counters"]["zombie_register_rejects"] == 1


def test_directory_restart_fence_monotonic_via_min_fence():
    # an agent re-registering into a FRESH directory quotes its last
    # token as min_fence, so monotonicity survives the lost table
    d2 = FleetDirectory(lease_ttl_s=1.0)
    dc2 = DirectoryClient(LoopbackTransport(d2.handle))
    r = dc2.register("r0", ["loopback", "r0"], generation=3,
                     min_fence=42)
    assert r["fence"] == 43
    # same generation (a directory restart is invisible to clients)
    assert r["generation"] == 3


def test_directory_deregister_tombstones():
    d = FleetDirectory(lease_ttl_s=1.0)
    dc = DirectoryClient(LoopbackTransport(d.handle))
    f = dc.register("r1", ["loopback", "r1"], generation=2)["fence"]
    with pytest.raises(StaleFencingToken):
        dc.deregister("r1", f + 1)
    assert dc.deregister("r1", f) == {"ok": True}
    # drained generations are retired for good
    with pytest.raises(StaleFencingToken):
        dc.register("r1", ["loopback", "r1"], generation=2)
    assert dc.register("r1", ["loopback", "r1"],
                       generation=3)["fence"] > f


# ---------------------------------------------------------- transport


def test_wire_envelope_and_typed_errors():
    req = wire.request("submit", {"key": "k"}, trace_id="t1")
    assert wire.decode(wire.encode(req)) == req

    e = EngineOverloaded("full")
    e.retry_after_s = 0.25
    env = wire.err(e)
    with pytest.raises(EngineOverloaded) as ei:
        wire.raise_error(env["error"])
    assert ei.value.retry_after_s == 0.25

    # unknown remote types degrade to WireError, never silence
    with pytest.raises(wire.WireError):
        wire.raise_error({"type": "SomethingElse", "msg": "x"})

    # fleet errors subclass the serving error hierarchy (proxy status map)
    assert issubclass(StaleFencingToken, EngineShutdown)
    assert issubclass(UnknownMember, EngineShutdown)
    assert issubclass(AgentFenced, EngineDraining)


def test_socket_transport_roundtrip_and_gate():
    open_gate = {"open": True}

    def handler(method, args, trace_id):
        if method == "boom":
            raise StaleFencingToken("zombie write")
        if method == "sleep":
            time.sleep(args["s"])
        return {"method": method, "args": args, "trace_id": trace_id}

    srv = SocketServer(handler, gate=lambda: open_gate["open"])
    try:
        t = SocketTransport(srv.addr)
        out = t.call("echo", {"a": 1}, trace_id="tid")
        assert out == {"method": "echo", "args": {"a": 1},
                       "trace_id": "tid"}
        # typed errors cross the socket by name
        with pytest.raises(StaleFencingToken):
            t.call("boom", {})
        # a slow peer is a TransportTimeout, never a typed error
        with pytest.raises(TransportTimeout):
            t.call("sleep", {"s": 1.0}, timeout_s=0.05)
        # partition gate: frames dropped WITHOUT a response
        open_gate["open"] = False
        with pytest.raises(TransportError):
            t.call("echo", {}, timeout_s=0.2)
        open_gate["open"] = True
        assert t.call("echo", {})["method"] == "echo"
        # nothing is listening -> TransportError, not a hang
        dead = SocketTransport(("127.0.0.1", srv.addr[1]))
        srv.stop()
        with pytest.raises(TransportError):
            dead.call("echo", {}, timeout_s=0.2)
    finally:
        srv.stop()


def test_frame_rejects_oversized_announcement():
    import socket as _socket
    import struct

    from ray_tpu.serve.fleet.transport import MAX_FRAME, recv_frame
    a, b = _socket.socketpair()
    try:
        a.sendall(struct.pack(">I", MAX_FRAME + 1))
        with pytest.raises(TransportError):
            recv_frame(b)
    finally:
        a.close()
        b.close()


# ----------------------------------------------------- agent fencing


def _loopback_directory(clock=None):
    d = FleetDirectory(lease_ttl_s=1.0,
                       **({"time_fn": clock} if clock else {}))
    return d, DirectoryClient(LoopbackTransport(d.handle))


def test_agent_lease_lapse_self_fences_and_recovers():
    """The fencing-token state machine, driven deterministically on
    a fake clock: a partitioned agent's lease lapses -> it
    self-fences (refusing admission and failing its in-flight work
    typed) STRICTLY before the directory could confirm it dead; when
    the partition heals it re-joins as generation+1 with an empty
    request table."""
    clock = FakeClock()
    d, dc = _loopback_directory(clock)
    a = ReplicaAgent("r0", lambda g: ScriptedEngine(token_delay_s=0),
                     dc, renew_period_s=3600.0, time_fn=clock)
    # drive renew_once by hand; never start the renew thread
    a.engine = a._factory(0)
    a._register(min_fence=0)
    fence0 = a.fence
    assert a.state == "active"

    # an in-flight request that the fence must fail typed
    a.engine.token_delay_s = 30.0
    sub = a.rpc_submit(key="k0", prompt_ids=[1, 2],
                       max_new_tokens=4, deadline_s=None,
                       fence=fence0)
    assert sub["dedup"] is False

    a.rpc_inject_partition(duration_s=100.0)
    # renewal still inside the lease: no fence yet
    clock.advance(0.5)
    assert a.renew_once() is False
    assert a.state == "active"
    # SAFE ORDER: the agent judges its lease at call-SEND time, so
    # at t=1.5 it fences itself while the directory (which stamped
    # receive time) would reach the same verdict — the agent can
    # never believe itself alive after the directory declared death
    clock.advance(1.0)
    assert a.renew_once() is False
    assert a.state == "fenced"
    assert a.counters["self_fences"] == 1
    assert d.rpc_confirm_dead(replica_id="r0",
                              fence=fence0)["dead"] is True

    # fenced -> every admission refused, in-flight failed typed
    with pytest.raises(AgentFenced):
        a.rpc_submit(key="k1", prompt_ids=[3], max_new_tokens=1,
                     deadline_s=None, fence=fence0)
    assert a.counters["refused_fenced"] == 1
    poll = a.rpc_poll(rid=sub["rid"])
    assert poll["error"]["type"] == "AgentFenced"

    # still partitioned: stays fenced (no re-register through a wall)
    assert a.renew_once() is False
    assert a.state == "fenced"

    # heal -> re-joins as a FRESH incarnation with no request state
    clock.advance(200.0)
    a.renew_once()
    assert a.state == "active"
    assert a.generation == 1
    assert a.fence > fence0
    assert a.counters["reregisters"] == 1
    with pytest.raises(EngineShutdown):
        a.rpc_poll(rid=sub["rid"])   # old rid fenced away
    # the zombie token can no longer write
    with pytest.raises(StaleFencingToken):
        dc.renew("r0", fence0)


def test_agent_reregisters_after_directory_restart_same_generation():
    """A directory crash/restart must be INVISIBLE to clients: the
    agent sees UnknownMember on renewal and re-registers under the
    same generation, keeping its request table."""
    clock = FakeClock()
    d, dc = _loopback_directory(clock)
    a = ReplicaAgent("r0", lambda g: ScriptedEngine(token_delay_s=0),
                     dc, renew_period_s=3600.0, time_fn=clock)
    a.engine = a._factory(0)
    a._register(min_fence=0)
    fence0 = a.fence
    sub = a.rpc_submit(key="k0", prompt_ids=[1], max_new_tokens=2,
                       deadline_s=None, fence=fence0)

    # "restart": fresh table, same handler object on the same client
    d._members.clear()
    clock.advance(0.3)
    assert a.renew_once() is False      # UnknownMember -> re-register
    assert a.state == "active"
    assert a.generation == 0            # same incarnation
    assert a.fence > fence0             # min_fence kept monotonicity
    # request state survived; the restart never touched the data path
    deadline = time.monotonic() + 5
    while not a.rpc_poll(rid=sub["rid"])["done"] \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    assert a.rpc_poll(rid=sub["rid"])["done"] is True


# ------------------------------------------------- loopback fleet e2e


def _loopback_fleet(n=3, token_delay_s=0.0005, seed=7,
                    wrap_transport=None, **router_kw):
    d = FleetDirectory(lease_ttl_s=1.0)
    dc = DirectoryClient(LoopbackTransport(d.handle))
    agents = {}

    def tf(addr):
        t = LoopbackTransport(agents[addr[1]].handle)
        return wrap_transport(addr[1], t) if wrap_transport else t

    for i in range(n):
        rid = f"a{i}"
        agents[rid] = ReplicaAgent(
            rid,
            lambda g, _d=token_delay_s: ScriptedEngine(
                token_delay_s=_d),
            dc, renew_period_s=0.05).start()
    kw = dict(seed=seed, snapshot_ttl_s=0.01, poll_interval_s=0.002)
    kw.update(router_kw)
    return d, dc, agents, FleetRouter(dc, tf, **kw)


def test_fleet_loopback_end_to_end():
    d, dc, agents, r = _loopback_fleet()
    try:
        # token identity through the whole submit/poll wire path
        h = r.submit([3, 1, 4, 1, 5], max_new_tokens=12)
        assert h.result() == scripted_completion([3, 1, 4, 1, 5], 12)
        assert h.replica_idx in agents
        assert h.replica_tag == f"{h.replica_idx}:0"

        # session stickiness holds across concurrent submits
        hs = [r.submit([i, i + 1], max_new_tokens=8,
                       session_id="s1") for i in range(6)]
        assert len({x.replica_idx for x in hs}) == 1
        for i, x in enumerate(hs):
            assert x.result() == scripted_completion([i, i + 1], 8)

        # aggregate surfaces
        lr = r.load_report()
        assert lr["replicas"] == 3
        assert r.pool_stats()["counters"]["routed"] >= 7
        assert set(r.member_stats()) == set(agents)
        assert r.stats["routed"] >= 7
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


def test_fleet_zero_delivery_resubmit_exactly_once():
    """Fence the serving agent BEFORE its first token: the router
    must resubmit token-identically to a different replica exactly
    once — and a later fence AFTER delivery must fail typed instead
    (no token can ever be delivered twice)."""
    d, dc, agents, r = _loopback_fleet(token_delay_s=0.05)
    try:
        res = {}
        h = r.submit([9, 9, 9], max_new_tokens=6)

        def consume():
            try:
                res["out"] = h.result()
            except BaseException as e:   # noqa: BLE001
                res["err"] = e

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.01)                 # < token_delay: zero tokens
        victim = h.replica_idx
        agents[victim].rpc_fence(reason="test")
        t.join(timeout=30)
        assert res.get("out") == scripted_completion([9, 9, 9], 6), res
        assert h.resubmits == 1
        assert h.replica_idx != victim
        assert agents[victim].counters["cancelled_on_fence"] == 1

        # partial stream: fence after delivery -> typed failure
        h2 = r.submit([4, 4], max_new_tokens=8)
        res2 = {}

        def consume2():
            try:
                res2["out"] = h2.result()
            except BaseException as e:   # noqa: BLE001
                res2["err"] = e

        t2 = threading.Thread(target=consume2)
        t2.start()
        deadline = time.monotonic() + 10
        while not h2._generated and time.monotonic() < deadline:
            time.sleep(0.005)
        assert h2._generated, "no token delivered before fence"
        agents[h2.replica_idx].rpc_fence(reason="mid-stream")
        t2.join(timeout=30)
        assert isinstance(res2.get("err"), EngineShutdown), res2
        assert h2.resubmits == 0         # partials never resubmit
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


def test_fleet_faulty_transport_never_double_delivers():
    """Seeded drop/dup/delay on every router->agent call: request
    keys dedupe duplicate submits, poll cursors make duplicate polls
    harmless, so every completion is token-identical — while the
    fault stats prove duplicates and drops really happened."""
    faulty = {}

    def wrap(rid, t):
        f = FaultyTransport(t, seed=sum(map(ord, rid)), drop_p=0.08,
                            dup_p=0.25, delay_p=0.2, delay_s=0.001)
        faulty.setdefault(rid, []).append(f)
        return f

    d, dc, agents, r = _loopback_fleet(
        n=2, wrap_transport=wrap, transport_patience_s=30.0,
        submit_retries=6, retry_backoff_s=0.001)
    try:
        prompts = [[i, i + 1, i + 2] for i in range(24)]
        hs = [r.submit(p, max_new_tokens=6) for p in prompts]
        for p, h in zip(prompts, hs):
            got = h.result()
            assert got == scripted_completion(p, 6), (p, got)
        stats = [f.stats for fs in faulty.values() for f in fs]
        assert sum(s["duplicated"] for s in stats) > 0
        assert sum(s["dropped"] for s in stats) > 0
        # duplicated submits were deduped agent-side, not re-admitted
        dup_seen = sum(a.counters["dup_submits"]
                       for a in agents.values())
        admitted = sum(a.counters["submits"] for a in agents.values())
        assert admitted == len(prompts) + sum(h.resubmits for h in hs)
        assert dup_seen >= 0   # dedup path exercised opportunistically
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


class _GatedLoopback(Transport):
    """Loopback that honors the agent's partition gate, so in-process
    fleets can simulate an unreachable host."""

    def __init__(self, agent):
        self._agent = agent
        self._inner = LoopbackTransport(agent.handle)

    def call(self, method, args, *, timeout_s=None, trace_id=None):
        if not self._agent.reachable():
            raise TransportError(
                f"{self._agent.replica_id} unreachable")
        return self._inner.call(method, args, timeout_s=timeout_s,
                                trace_id=trace_id)


def _until(cond, timeout_s=30.0, poll_s=0.02):
    """Poll ``cond`` up to a deadline; its last reading either way (the
    asserts after it say what was missing)."""
    deadline = time.monotonic() + timeout_s
    while not cond() and time.monotonic() < deadline:
        time.sleep(poll_s)
    return cond()


def test_fleet_three_way_race():
    """Lease expiry (partition) vs graceful drain vs hard kill, all
    racing in one 3-agent fleet under client load: every admitted
    request completes token-identically or fails typed, the drained
    agent deregisters clean, the killed agent is confirmed dead, and
    the partitioned agent self-fences then re-joins as gen+1."""
    d = FleetDirectory(lease_ttl_s=0.3)
    dc = DirectoryClient(LoopbackTransport(d.handle))
    agents = {}

    def tf(addr):
        return _GatedLoopback(agents[addr[1]])

    for i in range(3):
        rid = f"a{i}"
        agents[rid] = ReplicaAgent(
            rid, lambda g: ScriptedEngine(token_delay_s=0.002), dc,
            renew_period_s=0.05).start()
    r = FleetRouter(dc, tf, seed=13, snapshot_ttl_s=0.02,
                    poll_interval_s=0.002, call_timeout_s=0.5,
                    transport_patience_s=0.4)

    results = {"ok": 0, "typed": 0, "lost": 0, "mismatched": 0}
    rlock = threading.Lock()
    stop = threading.Event()
    # set by a client that a0 served after it had re-joined
    rebuilt = threading.Event()

    def client(cseed):
        i = 0
        while not stop.is_set():
            i += 1
            p = [cseed, i % 50]
            try:
                h = r.submit(p, max_new_tokens=4)
                got = h.result()
                with rlock:
                    if got == scripted_completion(p, 4):
                        results["ok"] += 1
                    else:
                        results["mismatched"] += 1
                if h.replica_idx == "a0" and agents["a0"].generation:
                    rebuilt.set()
            except (EngineShutdown, EngineDraining,
                    EngineOverloaded):
                with rlock:
                    results["typed"] += 1
            except BaseException:        # noqa: BLE001
                with rlock:
                    results["lost"] += 1

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(3)]
    for t in threads:
        t.start()
    def members():
        return {m["replica_id"] for m in d.rpc_snapshot()["members"]}

    def settled():
        # what the asserts below read, and nothing else: on a loaded
        # host the collapse and the rebuild take as long as they take
        a0 = agents["a0"]
        return (a0.counters["self_fences"] >= 1 and a0.generation >= 1
                and a0.state == "active"
                and "a1" in d.rpc_stats()["tombstones"]
                and r.counters["deaths_confirmed"] >= 1
                and members() == {"a0"} and rebuilt.is_set())

    try:
        # load is flowing through a whole fleet before the race
        # starts, and the kill catches a request in flight on a2:
        # that request's poll loop is what carries the router to the
        # directory's verdict once a2's lease has run out (its
        # patience, 0.4 s, outlasts the 0.3 s lease). With nothing in
        # flight there, nobody asks: the router routes around an
        # expired member, and a2 stays a death candidate in the
        # snapshot for good.
        _until(lambda: results["ok"] >= 3
               and members() == {"a0", "a1", "a2"})
        _until(lambda: agents["a2"].engine._active >= 1, poll_s=0.0005)
        # the race: kill a2 (crash path: the host is gone before
        # anyone hears of its engine), partition a0 (lease expiry
        # path), drain a1 (scale-down path) — all inside one lease
        # period
        agents["a2"]._partition_until = float("inf")
        agents["a2"]._stop.set()          # renewals die with the host
        agents["a2"].engine.force_kill(
            EngineShutdown("simulated SIGKILL"))
        agents["a0"].rpc_inject_partition(duration_s=0.8)
        threading.Thread(
            target=lambda: agents["a1"].rpc_drain(timeout_s=2.0),
            daemon=True).start()

        # let the fleet collapse to zero and rebuild from a0
        _until(settled)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)

    try:
        assert results["lost"] == 0, results
        assert results["mismatched"] == 0, results
        assert results["ok"] > 0, results

        # a0 self-fenced during the partition, then re-joined fresh
        assert agents["a0"].counters["self_fences"] >= 1
        assert agents["a0"].generation >= 1
        assert agents["a0"].state == "active"
        # a1 drained clean and is tombstoned (gen retired)
        st = d.rpc_stats()
        assert st["counters"]["deregisters"] == 1
        assert "a1" in st["tombstones"]
        # a2's death was adjudicated by the directory, not guessed
        assert r.counters["deaths_confirmed"] >= 1
        snap = members()
        assert "a2" not in snap and "a1" not in snap
        assert "a0" in snap
        # and the recovered fleet still serves token-identically
        h = r.submit([7, 7], max_new_tokens=4)
        assert h.result() == scripted_completion([7, 7], 4)
        assert h.replica_idx == "a0"
    finally:
        r.shutdown()
        for a in agents.values():
            a.shutdown()


# ----------------------------------------------- deployment integration


def test_llm_deployment_fleet_knob():
    """LlamaDeployment(fleet=N) serves through a loopback fleet —
    token-identical to the single-engine deployment — and stamps the
    fleet aggregate into serve_stats."""
    from ray_tpu.serve.llm import LlamaDeployment

    with pytest.raises(ValueError):
        LlamaDeployment(fleet=2, num_engine_replicas=2)
    # fleet+autoscale is now a supported combination (the deployment
    # builds its own LoopbackAgentProvider); what stays rejected is
    # handing in a foreign provider, whose tickets couldn't spawn
    # fleet agents
    with pytest.raises(ValueError):
        LlamaDeployment(fleet=2, autoscale=True,
                        autoscale_provider=object())
    with pytest.raises(ValueError):
        LlamaDeployment(fleet=3, autoscale=True,
                        autoscale_max_replicas=2)

    d = LlamaDeployment(fleet=2, max_new_tokens=4, max_slots=4)
    try:
        ref = LlamaDeployment(max_new_tokens=4, max_slots=4)
        want = ref([1, 2, 3])
        assert d([1, 2, 3]) == want
        out = d({"prompt_ids": [1, 2, 3], "echo_replica": True})
        assert out["ids"] == want
        rid, gen = out["replica"].split(":")
        assert rid in ("r0", "r1") and gen == "0"
        ss = d.serve_stats()["engine"]
        assert ss["replicas"] == 2
        assert "fleet" in ss and ss["consistent"] is False
        # single-engine deployments answer the echo too
        single = ref({"prompt_ids": [5], "echo_replica": True})
        assert single["replica"] == "0:0"
        ref._engine.shutdown()
    finally:
        d._engine.shutdown()
        for a in d._fleet_agents.values():
            a.shutdown()


# ------------------------------------------------------- cross-process


@pytest.mark.slow      # 15 s: spawns directory and agent OS processes and kills them
def test_fleet_mini_campaign_cross_process(tmp_path):
    """2 real OS-process agents + a directory process under the
    seeded fault schedule (fake engines): the run's own gates assert
    0 lost / 0 mismatched / every fault explained / quiesced."""
    from tools.chaos_serve import run_fleet_chaos

    art = run_fleet_chaos(seed=11, agents=2, duration_s=3.0,
                          clients=2, model="fake",
                          lease_ttl_s=0.6, token_delay_s=0.002,
                          flight_dir=str(tmp_path))
    assert art["requests"]["lost"] == 0
    assert art["requests"]["mismatched"] == 0
    assert art["requests"]["resubmitted_ok"] >= 1
    assert art["topology"]["agents"] == 2
    assert art["topology"]["transport"] == "tcp-json-v1"
    assert art["quiesced"] is True
    assert art["flight_recorder"]["faults_explained"] is True
    for kind in ("kill_agent", "partition", "directory_restart"):
        assert art["injected"][kind] >= 1, art["injected"]


@pytest.mark.slow
def test_fleet_full_campaign_tiny_model(tmp_path):
    """The checked-in SERVE_FLEET_CHAOS artifact's recipe: 3 real
    llama_tiny engine processes under the full campaign."""
    from tools import check_bench_schema as cbs
    from tools.chaos_serve import run_fleet_chaos

    art = run_fleet_chaos(seed=47, agents=3, duration_s=4.0,
                          model="tiny", lease_ttl_s=1.0,
                          flight_dir=str(tmp_path))
    problems = []
    cbs.check_fleet_chaos(art, "SERVE_FLEET_CHAOS_test", problems)
    assert not problems, problems


def test_directory_prefix_holders_ranked_and_lease_filtered():
    """The global prefix directory: digests piggyback on renewals,
    holders rank by matched CONTIGUOUS prefix length, and lapsed /
    wedged / superseded incarnations never appear — a requester can
    only be pointed at donors that are provably alive under fencing."""
    clock = FakeClock()
    d = FleetDirectory(lease_ttl_s=1.0, time_fn=clock)
    dc = DirectoryClient(LoopbackTransport(d.handle))
    f = {}
    for rid in ("r0", "r1", "r2"):
        f[rid] = dc.register(rid, ["loopback", rid],
                             generation=0)["fence"]
    chain = [11, 22, 33, 44]
    dc.renew("r0", f["r0"], digest=chain)           # whole chain
    dc.renew("r1", f["r1"], digest=chain[:2])       # 2-page prefix
    dc.renew("r2", f["r2"], digest=[11, 33, 44])    # hole after 1

    out = dc.prefix_holders(chain)["holders"]
    assert [h["replica_id"] for h in out] == ["r0", "r1", "r2"]
    # contiguity, not overlap: r2 holds 3 of the hashes but only a
    # 1-page contiguous prefix
    assert [h["n_matched"] for h in out] == [4, 2, 1]
    assert out[0]["fence"] == f["r0"]
    assert [h["replica_id"]
            for h in dc.prefix_holders(chain, limit=1)["holders"]] \
        == ["r0"]
    assert dc.prefix_holders([999])["holders"] == []

    # a wedge report hides the member however fresh its digest is
    dc.renew("r1", f["r1"], digest=chain[:2], wedged=True)
    assert "r1" not in [h["replica_id"]
                        for h in dc.prefix_holders(chain)["holders"]]

    # lease lapse: recent advertisement, dead lease -> never a donor
    clock.advance(1.5)
    assert dc.prefix_holders(chain)["holders"] == []

    # generation fencing: the NEXT incarnation starts with an EMPTY
    # advertisement (its cache died with the process); the ghost
    # digest of the dead generation must not survive re-registration
    dc.confirm_dead("r0", f["r0"])
    f2 = dc.register("r0", ["loopback", "r0"], generation=1,
                     min_fence=f["r0"])["fence"]
    assert dc.prefix_holders(chain)["holders"] == []
    dc.renew("r0", f2, digest=chain)
    out = dc.prefix_holders(chain)["holders"]
    assert out[0]["generation"] == 1 and out[0]["fence"] == f2
