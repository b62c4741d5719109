"""Serving watchdog tests (serve/watchdog.py) + chaos campaign smoke.

Two layers, mirroring test_engine_pool.py: the escalation ladder
(HEALTHY -> SUSPECT -> WEDGED), progress judgment, and capacity
exclusion against scripted heartbeat fakes under a fake clock — then
the end-to-end contract against real tiny-Llama engines: a wedge
injected with a `hang` fault plan is detected within the stall
deadline, escalated hang -> death without touching healthy replicas,
unstreamed requests complete token-identically on survivors, and the
released zombie is generation-fenced (no token commit, no
prefix-cache touch, leak-free quiescence). The chaos campaign itself
(tools/chaos_serve.py) runs once as a smoke and must pass its own
schema family.
"""
import json
import os
import sys
import threading
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.engine_pool import (DEAD, HEALTHY, SUSPECT,
                                       EnginePool)
from ray_tpu.serve.errors import EngineShutdown
from ray_tpu.serve.faults import (FaultInjector, check_pool_quiesced,
                                  check_quiesced)
from ray_tpu.serve.watchdog import PoolWatchdog, ReplicaWedged

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


@pytest.fixture(scope="module")
def tiny_model():
    # fp32 so greedy decode is bit-identical across replicas
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    import jax
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


@pytest.fixture(autouse=True)
def _no_page_leaks(monkeypatch):
    """Every real engine built in a test — including force-killed
    corpses — must end with allocator occupancy == prefix-cache
    residency."""
    created = []
    orig = LLMEngine.__init__

    def record(self, *args, **kwargs):
        orig(self, *args, **kwargs)
        created.append(self)

    monkeypatch.setattr(LLMEngine, "__init__", record)
    yield
    for eng in created:
        cached = (eng.prefix_cache.cached_pages
                  if eng.prefix_cache is not None else 0)
        occ = eng.alloc.occupancy()
        assert occ == cached, (
            f"engine leaked pages at teardown: occupancy {occ} != "
            f"prefix-cache residency {cached}")


def _reference_completion(model, params, prompt, n):
    import numpy as np
    from ray_tpu.models.llama import generate
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


# ------------------------------------------ heartbeat fakes + clock


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class HBFakeEngine:
    """A replica engine reduced to the surface the watchdog touches:
    a load report carrying heartbeat_age_s/has_work driven by a fake
    clock, plus the lifecycle the pool's death path needs."""

    def __init__(self, idx, clock):
        self.idx = idx
        self._clock = clock
        self._stopped = False
        self._draining = False
        self._hb = clock()
        self.has_work = False
        self.force_kills = 0
        self.force_kill_err = None
        self.stats = {"submitted": 0}
        self.submits = []
        self.started = False

    def start(self):
        self.started = True
        return self

    def touch(self):
        self._hb = self._clock()

    def submit(self, prompt, max_new_tokens=64, deadline_s=None):
        if self._stopped:
            raise EngineShutdown("engine stopped")
        self.submits.append(list(prompt))
        self.stats["submitted"] += 1

        class _H:
            def stream(_self):
                yield from [1, 2]

            def cancel(_self):
                return True
        return _H()

    def shutdown(self):
        self._stopped = True

    def force_kill(self, err=None):
        self.force_kills += 1
        self.force_kill_err = err
        self._stopped = True

    def drain(self):
        self._draining = True

    def wait_idle(self, timeout_s=30.0):
        return True

    def is_idle(self):
        return True

    def load_report(self):
        return {"free_slots": 4, "free_pages": 100, "queue_depth": 0,
                "outstanding_tokens": 0, "max_queued": None,
                "shed_retry_after_s": 1.0,
                "draining": self._draining, "stopped": self._stopped,
                "prefix_digest": frozenset(),
                "heartbeat_age_s": self._clock() - self._hb,
                "has_work": self.has_work}

    def prefix_stats(self):
        return None

    def spec_stats(self):
        return None


def _wd_pool(clock, n=2, **kw):
    fakes = [HBFakeEngine(i, clock) for i in range(n)]
    pool = EnginePool(lambda i: fakes[i], n)
    # keep fake-clock tests hermetic: no flight bundles under /tmp
    # unless a test opts in with an explicit dir
    kw.setdefault("flight_dir", False)
    wd = PoolWatchdog(pool, time_fn=clock, **kw)
    return fakes, pool, wd


# --------------------------------------------- ladder (fake clock)


def test_ladder_suspect_then_wedge_drives_death_path():
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=10.0)
    assert wd.suspect_after_s == 5.0       # default: half the deadline
    fakes[0].has_work = True
    clock.advance(3.0)
    wd.tick()                              # age 3 < 5: nothing
    assert pool.replica(0).state == HEALTHY
    clock.advance(3.0)
    wd.tick()                              # age 6 >= 5: quarantine
    assert pool.replica(0).state == SUSPECT
    assert pool.replica(1).state == HEALTHY
    assert wd.counts["suspected"] == 1
    clock.advance(5.0)
    wd.tick()                              # age 11 >= 10: wedged
    assert wd.counts["wedged"] == 1
    assert pool.replica(0).state == DEAD
    assert fakes[0].force_kills == 1
    assert isinstance(fakes[0].force_kill_err, ReplicaWedged)
    assert pool.route_stats["wedged"] == 1
    assert pool.route_stats["replica_deaths"] == 1
    # flight recording was disabled: the escalation still carries
    # the (absent) bundle path rather than failing
    assert fakes[0].force_kill_err.bundle_path is None
    # the healthy replica was never probed into a restart
    assert fakes[1].force_kills == 0
    assert pool.replica(1).state == HEALTHY
    assert pool.replica(1).generation == 0
    pool.shutdown()


def test_wedge_dumps_flight_bundle_before_kill(tmp_path):
    """Escalation with recording on: the watchdog dumps a postmortem
    bundle BEFORE force-killing, stamps its path on the ReplicaWedged
    error and the log entry, and the bundle tolerates a fake engine
    (best-effort probes)."""
    from ray_tpu.serve import obs
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=10.0,
                               flight_dir=str(tmp_path))
    fakes[0].has_work = True
    clock.advance(6.0)
    wd.tick()
    clock.advance(5.0)
    wd.tick()
    err = fakes[0].force_kill_err
    assert isinstance(err, ReplicaWedged)
    assert err.bundle_path is not None and \
        os.path.isdir(err.bundle_path)
    (wedge,) = [e for e in wd.log if e["event"] == "wedged"]
    assert wedge["bundle"] == err.bundle_path
    b = obs.load_flight_bundle(err.bundle_path)
    assert b["reason"] == "wedged-r0"
    assert b["extra"]["replica"] == 0
    assert b["extra"]["stall_deadline_s"] == 10.0
    # HBFakeEngine has no event log; load_report still lands and the
    # recorded heartbeat gap explains the escalation
    assert b["engine"]["heartbeat_gap_s"] >= 10.0 * 0.9
    # the dump precedes the kill: the pool snapshot still shows the
    # replica alive — the bundle is the last look at the wedged state
    assert b["pool"]["pool_stats"].get("replica_deaths", 0) == 0
    assert pool.route_stats["replica_deaths"] == 1
    pool.shutdown()


def test_suspect_recovers_on_heartbeat_progress():
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=10.0)
    fakes[0].has_work = True
    clock.advance(6.0)
    wd.tick()
    assert pool.replica(0).state == SUSPECT
    # the heartbeat moves (a long-but-moving prefill): age shrinks
    # below what the watchdog recorded at suspicion
    fakes[0].touch()
    clock.advance(1.0)
    wd.tick()
    assert pool.replica(0).state == HEALTHY
    assert wd.counts["recovered"] == 1
    assert fakes[0].force_kills == 0
    # ... and a FRESH stall re-enters the ladder from the top
    clock.advance(6.0)
    wd.tick()
    assert pool.replica(0).state == SUSPECT
    pool.shutdown()


def test_progressing_readback_drain_never_escalates():
    """The overlapped hot loop's blocking readback drain touches the
    heartbeat BEFORE each device_get as well as after
    (engine._drain_fetches_locked), so a slow-but-PROGRESSING
    multi-buffer readback presents as a stream of sub-threshold
    heartbeat ages — it must ride the ladder nowhere, for as long as
    it keeps moving. The moment the touches stop (a genuine hang
    inside one get) the normal ladder takes over."""
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=10.0)
    fakes[0].has_work = True
    # each buffer of the drain costs 4s of wall — slow, but every
    # iteration boundary refreshes the heartbeat the way the
    # pre-get touch does
    for _ in range(8):                     # 32s >> stall deadline
        clock.advance(4.0)                 # 4 < suspect_after (5)
        fakes[0].touch()
        wd.tick()
        assert pool.replica(0).state == HEALTHY
    assert wd.counts["suspected"] == 0
    assert fakes[0].force_kills == 0
    # the readback genuinely hangs: touches stop, ladder engages
    clock.advance(6.0)
    wd.tick()
    assert pool.replica(0).state == SUSPECT
    clock.advance(5.0)
    wd.tick()
    assert pool.replica(0).state == DEAD
    assert fakes[0].force_kills == 1
    pool.shutdown()


def test_suspect_recovers_when_work_drains():
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=10.0)
    fakes[0].has_work = True
    clock.advance(6.0)
    wd.tick()
    assert pool.replica(0).state == SUSPECT
    fakes[0].has_work = False              # drained; hb still stale
    clock.advance(1.0)
    wd.tick()
    assert pool.replica(0).state == HEALTHY
    assert wd.counts["recovered"] == 1
    pool.shutdown()


def test_idle_stale_heartbeat_is_never_suspected():
    # an idle engine parks on its condition variable with a stale
    # heartbeat and NO work: silence without work is not a wedge
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=10.0)
    for _ in range(5):
        clock.advance(100.0)
        wd.tick()
    assert pool.replica(0).state == HEALTHY
    assert pool.replica(1).state == HEALTHY
    assert wd.counts["suspected"] == 0
    pool.shutdown()


def test_suspect_excluded_from_routing_and_capacity():
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=10.0)
    fakes[0].has_work = True
    clock.advance(6.0)
    wd.tick()
    assert pool.replica(0).state == SUSPECT
    # a maybe-dead replica must not count as capacity anywhere
    assert pool.healthy_count() == 1
    assert pool.load_report()["healthy_replicas"] == 1
    assert pool.pool_stats()["suspect_replicas"] == 1
    for _ in range(4):
        h = pool.submit([1, 2, 3])
        assert h.replica_idx == 1
    assert fakes[0].submits == []
    pool.shutdown()


def test_engines_without_heartbeat_surface_are_skipped():
    # a report lacking heartbeat_age_s/has_work (older engine, plain
    # FakeEngine) must never be judged — compat, not a wedge
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=10.0)
    orig = fakes[0].load_report

    def bare_report():
        rpt = orig()
        rpt.pop("heartbeat_age_s")
        rpt.pop("has_work")
        return rpt

    fakes[0].load_report = bare_report
    fakes[0].has_work = True
    clock.advance(100.0)
    wd.tick()
    assert pool.replica(0).state == HEALTHY
    assert wd.counts["suspected"] == 0
    pool.shutdown()


def test_watchdog_stats_block_in_pool_stats():
    clock = FakeClock()
    fakes, pool, wd = _wd_pool(clock, stall_deadline_s=8.0,
                               suspect_after_s=2.0,
                               poll_interval_s=0.5)
    wd.tick()
    blk = pool.pool_stats()["watchdog"]
    assert blk["ticks"] == 1
    assert blk["stall_deadline_s"] == 8.0
    assert blk["suspect_after_s"] == 2.0
    assert blk["poll_interval_s"] == 0.5
    assert blk["active_suspects"] == 0
    pool.shutdown()


def test_watchdog_validates_knobs():
    clock = FakeClock()
    fakes = [HBFakeEngine(0, clock)]
    pool = EnginePool(lambda i: fakes[i], 1)
    with pytest.raises(ValueError):
        PoolWatchdog(pool, stall_deadline_s=0.0)
    with pytest.raises(ValueError):
        PoolWatchdog(pool, stall_deadline_s=1.0, suspect_after_s=2.0)
    pool.shutdown()


# ------------------------------------------------------ real engines


def _warm_engine_factory(model, params, inj_for):
    """Factory building warmed real engines: the first dispatch
    compiles for seconds while holding the scheduler lock (frozen
    heartbeat) — warming BEFORE the engine joins the pool keeps the
    watchdog's stall judgment about wedges, not XLA."""

    def factory(idx):
        eng = LLMEngine(model, params, max_slots=2, page_size=8,
                        n_pages=64, chunk=4, temperature=0.0,
                        seed=idx, prefix_cache=True,
                        admit_timeout_s=0.5,
                        fault_injector=inj_for(idx))
        eng.start()
        try:
            eng.submit([3, 1, 4, 1], max_new_tokens=4).result()
            eng.submit([3, 1, 4, 1, 5, 9], max_new_tokens=4).result()
        except EngineShutdown:
            pass
        eng.reset_latency_stats()
        return eng

    return factory


def test_injected_hang_escalates_to_death_within_deadline(
        tiny_model, tmp_path):
    """The tentpole end-to-end: a `hang` fault plan parks replica 0's
    scheduler thread mid-step (lock held, heartbeat frozen, work
    pending). The watchdog must declare it wedged within the stall
    deadline, force-kill it out-of-band, leave the healthy replica
    untouched, and the pool must land every in-flight request either
    token-identically on the survivor or typed. The escalation must
    leave a flight bundle — dumped lock-free while the wedged thread
    still HOLDS the engine lock — that explains the hang."""
    model, params = tiny_model
    stall = 1.0
    inj = FaultInjector()
    factory = _warm_engine_factory(
        model, params, lambda idx: inj if idx == 0 else None)
    pool = EnginePool(factory, 2)
    watchdog = PoolWatchdog(pool, stall_deadline_s=stall,
                            poll_interval_s=0.05,
                            flight_dir=str(tmp_path)).run()
    try:
        prompts = [[3, 1, 4, 1, 10 + i, 20 + i] for i in range(6)]
        want = [_reference_completion(model, params, p, 12)
                for p in prompts]
        # arm the wedge, then load the pool: whichever requests land
        # on replica 0 freeze with it
        inj.hang("step")
        t0 = time.monotonic()
        results = [None] * len(prompts)

        def consume(i, h):
            try:
                results[i] = ("ok", h.result())
            except EngineShutdown:
                results[i] = ("typed", None)

        handles = [pool.submit(p, max_new_tokens=12)
                   for p in prompts]
        threads = [threading.Thread(target=consume, args=(i, h))
                   for i, h in enumerate(handles)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + stall + 10.0
        while (watchdog.counts["wedged"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        detect_s = time.monotonic() - t0
        assert watchdog.counts["wedged"] == 1, \
            f"wedge undetected after {detect_s:.1f}s"
        # detection within the deadline (+ scheduling slack: one
        # poll interval and the probe ladder)
        assert detect_s < stall + 3.0
        wedge_events = [e for e in watchdog.log
                        if e["event"] == "wedged"]
        assert wedge_events and \
            wedge_events[0]["heartbeat_age_s"] >= stall * 0.9
        # the postmortem bundle was written BEFORE the force-kill,
        # with the wedged scheduler still holding the engine lock,
        # and its heartbeat gap explains the escalation
        from ray_tpu.serve import obs
        bundle_path = wedge_events[0]["bundle"]
        assert bundle_path is not None and os.path.isdir(bundle_path)
        bundle = obs.load_flight_bundle(bundle_path)
        assert bundle["reason"].startswith("wedged-r0")
        assert bundle["engine"]["heartbeat_gap_s"] >= stall * 0.9
        # the event tail survived the death: the typed log shows the
        # engine was mid-flight (admits/prefills), then went silent
        assert bundle["engine"]["events"], "bundle lost the event tail"
        for t in threads:
            t.join(timeout=60)
        assert all(not t.is_alive() for t in threads), "request hung"
        assert all(r is not None for r in results), "request lost"
        ok = [i for i, r in enumerate(results) if r[0] == "ok"]
        for i in ok:
            assert results[i][1] == want[i], i
        assert ok, "no request completed on the survivor"
        # hang -> death: the wedged replica took the existing death
        # path; the healthy one was never killed or restarted. It MAY
        # be transiently SUSPECT (a survivor recompiling under the
        # resubmit burst is a false alarm the ladder recovers from) —
        # with its work drained the next tick must clear it.
        assert pool.replica(0).state == DEAD
        deadline = time.monotonic() + 5.0
        while (pool.replica(1).state != HEALTHY
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert pool.replica(1).state == HEALTHY
        assert pool.replica(1).generation == 0
        assert watchdog.counts["wedged"] == 1
        assert pool.route_stats["wedged"] == 1
    finally:
        watchdog.stop()
        inj.release_all()
        pool.shutdown()
    check_pool_quiesced(pool)


def test_released_zombie_is_fenced(tiny_model):
    """Generation fencing: a force-killed engine whose wedged thread
    later wakes (hang plan released) must not commit tokens or touch
    the prefix cache — it drains and exits, and a second shutdown()
    completes the deferred cleanup leak-free."""
    model, params = tiny_model
    inj = FaultInjector()
    eng = _warm_engine_factory(
        model, params, lambda idx: inj)(0)
    try:
        cached_before = eng.prefix_cache.cached_pages
        inj.hang("step")
        h = eng.submit([7, 1, 8, 2], max_new_tokens=32)
        # wait for the scheduler thread to park inside step() with
        # the lock held: heartbeat freezes while work is pending
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            rpt = eng.load_report()
            if rpt["has_work"] and rpt["heartbeat_age_s"] > 0.3:
                break
            time.sleep(0.01)
        else:
            pytest.fail("hang plan never engaged")
        eng.force_kill(ReplicaWedged("test wedge"))
        # consumers unblock typed immediately — no waiting on the
        # parked thread
        with pytest.raises(EngineShutdown):
            h.result()
        assert eng.stats["force_killed"] == 1
        # release the zombie: it wakes inside step(), finds the
        # fence, and must not commit anything
        inj.release_all()
        t = eng._thread
        if t is not None:
            t.join(timeout=10.0)
            assert not t.is_alive(), "released zombie never exited"
        # the prefix cache was never touched by the zombie: the
        # fenced slot frees its pages instead of retiring them
        assert eng.prefix_cache.cached_pages == cached_before
    finally:
        inj.release_all()
        eng.shutdown()     # second shutdown: deferred cleanup runs
    check_quiesced(eng, expect_cached_pages=eng.prefix_cache
                   .cached_pages)


# ----------------------------------------------- chaos campaign smoke


def test_chaos_campaign_smoke_and_schema(tmp_path):
    """The seeded campaign (tools/chaos_serve.py) end-to-end: all six
    fault kinds fire against a live 3-replica pool under client load,
    the run's own hard asserts pass (zero lost, wedge within
    deadline, quiesced, attainment above floor), and the artifact
    validates under its schema family."""
    from tools import chaos_serve
    from tools import check_bench_schema as cbs
    art = chaos_serve.run_chaos(seed=47, replicas=3, duration_s=3.0,
                                clients=3, stall_deadline_s=1.0)
    assert art["requests"]["lost"] == 0
    assert art["requests"]["mismatched"] == 0
    assert art["wedge"]["detected"] is True
    assert art["wedge"]["within_deadline"] is True
    assert all(art["injected"][k] >= 1
               for k in ("kill", "hang", "stockout"))
    assert art["attainment"] >= art["attainment_floor"]
    p = tmp_path / "SERVE_CHAOS_test.json"
    p.write_text(json.dumps(art))
    problems = []
    cbs.check_file(str(p), problems)
    assert problems == [], problems
