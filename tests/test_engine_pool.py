"""Engine replica pool tests (serve/engine_pool.py).

Two layers, mirroring the reference's replica-set tests
(python/ray/serve/tests/test_replica_scheduler.py): routing policy
and lifecycle state machine against scripted fake engines
(deterministic load reports, no model in the loop), then the
end-to-end contract against real tiny-Llama engines — token parity
across replicas, replica-kill recovery with zero lost requests,
drain, and pool-wide quiescence (no replica, dead or alive, may
leak a page).
"""
import threading
import time

import jax.numpy as jnp
import pytest

from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.engine_pool import (DEAD, DRAINING, HEALTHY,
                                       EnginePool)
from ray_tpu.serve.errors import (DeadlineExceeded, EngineDraining,
                                  EngineOverloaded, EngineShutdown)
from ray_tpu.serve.faults import (FaultInjector, check_pool_quiesced,
                                  check_quiesced)
from ray_tpu.serve.prefix_cache import path_hashes


@pytest.fixture(scope="module")
def tiny_model():
    # fp32 so greedy decode is bit-identical across replicas (the
    # parity tests compare pool output against generate())
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    import jax
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


@pytest.fixture(autouse=True)
def _no_page_leaks(monkeypatch):
    """Same invariant net as test_llm_engine.py, pool-wide: every
    real engine built in a test — including ones the pool killed or
    rebuilt — must end with allocator occupancy == prefix-cache
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
            f"prefix-cache residency {cached}; leaked ids "
            f"{sorted(eng.alloc.leak_report())[:16]}")


def _reference_completion(model, params, prompt, n):
    import numpy as np
    from ray_tpu.models.llama import generate
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


# ------------------------------------------------------- fake engines


class FakeHandle:
    """Scripted request handle: streams ``tokens``, then optionally
    raises ``exc`` (set ``engine._stopped`` first to model a replica
    death rather than a request failure)."""

    def __init__(self, engine, tokens, exc=None):
        self._engine = engine
        self._tokens = list(tokens)
        self._exc = exc
        self.cancelled = False

    def stream(self):
        for t in self._tokens:
            yield t
        if self._exc is not None:
            if self._engine.die_on_failure:
                self._engine._stopped = True
            raise self._exc

    def cancel(self):
        self.cancelled = True
        return True


class FakeEngine:
    """A replica engine reduced to the surface the pool touches:
    load_report + submit + lifecycle flags, all scripted."""

    def __init__(self, idx, *, outstanding=0, digest=frozenset(),
                 max_queued=None, queue_depth=0, retry_after=1.0,
                 page_size=16):
        self.idx = idx
        self.Pg = page_size
        self._stopped = False
        self._draining = False
        self.die_on_failure = False
        self.outstanding = outstanding
        self.digest = digest
        self.max_queued = max_queued
        self.queue_depth = queue_depth
        self.retry_after = retry_after
        self.stats = {"submitted": 0}
        self.ttfts_s = []
        self.submits = []           # (prompt, max_new_tokens, deadline)
        self.script = []            # queued submit outcomes
        self.started = False
        self.shutdowns = 0

    def start(self):
        self.started = True
        return self

    def submit(self, prompt, max_new_tokens=64, deadline_s=None):
        if self._stopped:
            raise EngineShutdown("engine stopped")
        if self._draining:
            raise EngineDraining("draining")
        self.submits.append((list(prompt), max_new_tokens, deadline_s))
        self.stats["submitted"] += 1
        out = self.script.pop(0) if self.script else [1, 2]
        if isinstance(out, BaseException):
            raise out
        if isinstance(out, FakeHandle):
            return out
        return FakeHandle(self, out)

    def shutdown(self):
        self.shutdowns += 1
        self._stopped = True

    def drain(self):
        self._draining = True

    def wait_idle(self, timeout_s=30.0):
        return True

    def is_idle(self):
        return True

    def load_report(self):
        return {"free_slots": 4, "free_pages": 100,
                "queue_depth": self.queue_depth,
                "outstanding_tokens": self.outstanding,
                "max_queued": self.max_queued,
                "shed_retry_after_s": self.retry_after,
                "draining": self._draining,
                "stopped": self._stopped,
                "prefix_digest": self.digest}

    def prefix_stats(self):
        return None

    def spec_stats(self):
        return None

    def lifecycle_stats(self):
        return {"max_queued": self.max_queued, "max_retries": 2,
                "retry_backoff_s": 0.02, "shed": 0}


def _fake_pool(fakes, **kw):
    pool = EnginePool(lambda i: fakes[i], len(fakes), **kw)
    assert all(f.started for f in fakes)
    return pool


# --------------------------------------------------- routing (fakes)


def test_pool_rejects_zero_replicas():
    with pytest.raises(ValueError):
        EnginePool(lambda i: FakeEngine(i), 0)


def test_p2c_routes_least_outstanding():
    fakes = [FakeEngine(0, outstanding=500),
             FakeEngine(1, outstanding=5)]
    pool = _fake_pool(fakes)
    h = pool.submit([1, 2, 3])
    assert h.replica_idx == 1
    assert pool.route_stats["route_p2c"] == 1
    assert pool.route_stats["affinity_hits"] == 0
    pool.shutdown()


def test_affinity_routes_longest_cached_prefix():
    prompt = list(range(1, 65))           # 4 pages at Pg=16
    hashes = path_hashes(prompt, 16)
    fakes = [FakeEngine(0, outstanding=0,
                        digest=frozenset(hashes[:1])),
             FakeEngine(1, outstanding=900,     # busier, but hotter
                        digest=frozenset(hashes[:3]))]
    pool = _fake_pool(fakes)
    h = pool.submit(prompt)
    assert h.replica_idx == 1
    assert pool.route_stats["route_affinity"] == 1
    assert pool.route_stats["affinity_hits"] == 1
    assert pool.route_stats["affinity_hit_pages"] == 3
    pool.shutdown()


def test_sticky_session_rehomes_after_death():
    fakes = [FakeEngine(0), FakeEngine(1)]
    pool = _fake_pool(fakes)
    first = pool.submit([1, 2], session_id="s").replica_idx
    again = pool.submit([3, 4], session_id="s").replica_idx
    assert again == first
    assert pool.route_stats["sticky_hits"] >= 1
    # the sticky replica dies: the session must re-home, not 404
    fakes[first]._stopped = True
    pool._note_replica_death(pool.replica(first))
    assert pool.replica(first).state == DEAD
    rehomed = pool.submit([5, 6], session_id="s").replica_idx
    assert rehomed == 1 - first
    pool.shutdown()


def test_spill_when_affinity_target_saturated():
    prompt = list(range(1, 33))
    hashes = path_hashes(prompt, 16)
    fakes = [FakeEngine(0, digest=frozenset(hashes),
                        max_queued=2, queue_depth=2),   # full
             FakeEngine(1)]
    pool = _fake_pool(fakes)
    h = pool.submit(prompt)
    assert h.replica_idx == 1
    assert pool.route_stats["spills"] == 1
    assert pool.route_stats["route_p2c"] == 1
    assert pool.pool_stats()["spill_rate"] == 1.0
    pool.shutdown()


def test_all_shed_aggregates_max_retry_after():
    fakes = [FakeEngine(0, retry_after=2.0),
             FakeEngine(1, retry_after=5.0)]
    fakes[0].script.append(EngineOverloaded("full",
                                            retry_after_s=2.0))
    fakes[1].script.append(EngineOverloaded("full",
                                            retry_after_s=5.0))
    pool = _fake_pool(fakes)
    with pytest.raises(EngineOverloaded) as ei:
        pool.submit([1, 2, 3])
    # the pool's Retry-After hint must be honest for the WHOLE pool:
    # max over replicas, never the first shed's smaller hint
    assert ei.value.retry_after_s == 5.0
    assert pool.route_stats["all_shed"] == 1
    pool.shutdown()


def test_saturated_everywhere_sheds_with_report_hints():
    fakes = [FakeEngine(0, max_queued=1, queue_depth=1,
                        retry_after=0.5),
             FakeEngine(1, max_queued=1, queue_depth=3,
                        retry_after=4.0)]
    pool = _fake_pool(fakes)
    with pytest.raises(EngineOverloaded) as ei:
        pool.submit([1])
    assert ei.value.retry_after_s == 4.0
    assert fakes[0].submits == [] and fakes[1].submits == []
    pool.shutdown()


def test_no_healthy_replicas_is_typed_shutdown():
    fakes = [FakeEngine(0), FakeEngine(1)]
    pool = _fake_pool(fakes)
    for f in fakes:
        f._stopped = True
    with pytest.raises(EngineShutdown):
        pool.submit([1, 2])
    pool.shutdown()


def test_submit_routes_around_replica_that_died_racing():
    # replica 0 dies AFTER the routing snapshot: submit raises
    # EngineShutdown, the pool marks it dead and retries replica 1
    fakes = [FakeEngine(0, outstanding=0),
             FakeEngine(1, outstanding=10)]
    fakes[0].script.append(EngineShutdown("died mid-submit"))
    fakes[0]._make_stopped_on_script = True
    orig_submit = FakeEngine.submit

    def dying_submit(self, prompt, **kw):
        if self.script and isinstance(self.script[0], EngineShutdown):
            self._stopped = True
        return orig_submit(self, prompt, **kw)

    fakes[0].submit = dying_submit.__get__(fakes[0])
    pool = _fake_pool(fakes)
    h = pool.submit([1, 2])
    assert h.replica_idx == 1
    assert pool.replica(0).state == DEAD
    assert pool.route_stats["replica_deaths"] == 1
    pool.shutdown()


# ------------------------------------------- recovery + handle (fakes)


def test_unstreamed_death_resubmits_token_identically():
    fakes = [FakeEngine(0, outstanding=0),
             FakeEngine(1, outstanding=10)]
    # replica 0 accepts, then dies before emitting anything
    fakes[0].die_on_failure = True
    fakes[0].script.append(FakeHandle(fakes[0], [],
                                      RuntimeError("device lost")))
    fakes[1].script.append([7, 8, 9])
    pool = _fake_pool(fakes)
    h = pool.submit([1, 2])
    assert h.replica_idx == 0
    assert h.result() == [7, 8, 9]
    assert h.replica_idx == 1
    assert pool.route_stats["requeues"] == 1
    assert pool.route_stats["replica_deaths"] == 1
    assert h.ttft_s is not None
    pool.shutdown()


def test_partially_streamed_death_fails_typed():
    fakes = [FakeEngine(0, outstanding=0),
             FakeEngine(1, outstanding=10)]
    fakes[0].die_on_failure = True
    fakes[0].script.append(FakeHandle(fakes[0], [7, 8],
                                      RuntimeError("device lost")))
    pool = _fake_pool(fakes)
    h = pool.submit([1, 2])
    got = []
    with pytest.raises(EngineShutdown, match="cannot be replayed"):
        for t in h.stream():
            got.append(t)
    assert got == [7, 8]           # delivered tokens stay delivered
    assert h.error is not None and h.done
    assert pool.route_stats["requeues"] == 0
    assert fakes[1].submits == []  # at-most-once: no resubmission
    pool.shutdown()


def test_request_level_failure_is_not_a_replica_death():
    fakes = [FakeEngine(0), FakeEngine(1)]
    fakes[0].script.append(FakeHandle(fakes[0], [],
                                      DeadlineExceeded("too slow")))
    fakes[1].script.append(FakeHandle(fakes[1], [],
                                      DeadlineExceeded("too slow")))
    pool = _fake_pool(fakes)
    h = pool.submit([1, 2])
    with pytest.raises(DeadlineExceeded):
        h.result()
    assert pool.route_stats["replica_deaths"] == 0
    assert pool.route_stats["requeues"] == 0
    assert pool.replica(0).state == HEALTHY
    assert pool.replica(1).state == HEALTHY
    pool.shutdown()


def test_resubmit_cap_fails_typed():
    # every replica dies on first use; with max_resubmits=1 the
    # request gets exactly one more try, then a typed failure
    fakes = [FakeEngine(i) for i in range(3)]
    for f in fakes:
        f.die_on_failure = True
        f.script.append(FakeHandle(f, [], RuntimeError("boom")))
    pool = _fake_pool(fakes, max_resubmits=1)
    h = pool.submit([1, 2])
    with pytest.raises(EngineShutdown):
        h.result()
    assert pool.route_stats["requeues"] == 1
    pool.shutdown()


def test_deadline_shrinks_across_resubmit():
    fakes = [FakeEngine(0, outstanding=0),
             FakeEngine(1, outstanding=10)]
    fakes[0].die_on_failure = True
    fakes[0].script.append(FakeHandle(fakes[0], [],
                                      RuntimeError("boom")))
    fakes[1].script.append([5])
    pool = _fake_pool(fakes)
    h = pool.submit([1, 2], deadline_s=30.0)
    assert h.result() == [5]
    # replica 0 saw the full deadline; the resubmission to replica 1
    # must carry only what REMAINS of it
    assert fakes[0].submits[0][2] == 30.0
    remaining = fakes[1].submits[0][2]
    assert remaining is not None and 0 < remaining < 30.0
    pool.shutdown()


# --------------------------------------------------- lifecycle (fakes)


def test_drain_rebuilds_replica_with_new_generation():
    built = []

    def factory(i):
        f = FakeEngine(i)
        built.append(f)
        return f

    pool = EnginePool(factory, 2)
    old = pool.replica(0).engine
    assert pool.drain(0) is True
    rep = pool.replica(0)
    assert rep.state == HEALTHY
    assert rep.generation == 1
    assert rep.engine is not old
    assert old._draining and old.shutdowns >= 1
    assert pool.route_stats["drains"] == 1
    assert pool.route_stats["restarts"] == 1
    # only a healthy replica may drain
    pool.replica(1).state = DRAINING
    with pytest.raises(RuntimeError):
        pool.drain(1)
    pool.replica(1).state = HEALTHY
    pool.shutdown()


def test_restart_dead_rebuilds_only_dead_replicas():
    fakes = {0: FakeEngine(0), 1: FakeEngine(1)}

    def factory(i):
        f = FakeEngine(i)
        fakes[i] = f
        return f

    pool = EnginePool(lambda i: fakes[i], 2)
    fakes[0]._stopped = True
    pool._note_replica_death(pool.replica(0))
    pool._factory = factory
    assert pool.restart_dead() == 1
    assert pool.replica(0).state == HEALTHY
    assert pool.replica(0).generation == 1
    assert pool.replica(1).generation == 0
    assert pool.healthy_count() == 2
    pool.shutdown()


def test_pool_shutdown_is_typed_and_idempotent():
    fakes = [FakeEngine(0), FakeEngine(1)]
    pool = _fake_pool(fakes)
    pool.shutdown()
    pool.shutdown()
    assert all(r.state == DEAD for r in [pool.replica(0),
                                         pool.replica(1)])
    with pytest.raises(EngineShutdown):
        pool.submit([1])


def test_pool_load_report_aggregates_and_maxes_hint():
    fakes = [FakeEngine(0, outstanding=10, queue_depth=1,
                        retry_after=0.5),
             FakeEngine(1, outstanding=30, queue_depth=2,
                        retry_after=3.5)]
    pool = _fake_pool(fakes)
    rpt = pool.load_report()
    assert rpt["free_slots"] == 8
    assert rpt["queue_depth"] == 3
    assert rpt["outstanding_tokens"] == 40
    assert rpt["shed_retry_after_s"] == 3.5
    assert rpt["healthy_replicas"] == 2 and rpt["n_replicas"] == 2
    assert rpt["stopped"] is False
    pool.shutdown()
    assert pool.load_report()["stopped"] is True


def test_pool_stats_rates_and_replica_rows():
    fakes = [FakeEngine(0), FakeEngine(1)]
    pool = _fake_pool(fakes)
    for _ in range(4):
        pool.submit([1, 2]).result()
    ps = pool.pool_stats()
    assert ps["routed"] == 4
    assert ps["affinity_hit_rate"] == 0.0     # no digests anywhere
    assert ps["spill_rate"] == 0.0
    assert ps["n_replicas"] == 2
    assert [r["idx"] for r in ps["replicas"]] == [0, 1]
    assert pool.stats["submitted"] == 4       # summed engine counters
    pool.shutdown()


# ----------------------------------------------------- real engines


def test_pool_parity_and_affinity_compounding(tiny_model):
    """Two replicas, shared-prefix prompts, two waves: every
    completion token-identical to generate(); the second wave routes
    by affinity (each prompt re-hits the replica that cached it)."""
    model, params = tiny_model
    pool = EnginePool(
        lambda i: LLMEngine(model, params, max_slots=2, page_size=8,
                            n_pages=64, chunk=4, temperature=0.0,
                            seed=i, prefix_cache=True),
        2)
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    prompts = [shared + [10 + i, 20 + i, 30 + i] for i in range(4)]
    want = {i: _reference_completion(model, params, p, 10)
            for i, p in enumerate(prompts)}
    for wave in range(2):
        handles = [(i, pool.submit(p, max_new_tokens=10))
                   for i, p in enumerate(prompts)]
        for i, h in handles:
            assert h.result() == want[i], (wave, i)
    assert pool.route_stats["affinity_hits"] > 0
    assert pool.pool_stats()["affinity_hit_rate"] > 0
    pool.shutdown()
    check_pool_quiesced(pool)


def test_replica_kill_recovers_unstreamed_requests(tiny_model):
    """FaultInjector kills replica 0 mid-run: every request either
    completes token-identically (resubmitted to the survivor if it
    had not streamed) or fails typed EngineShutdown. Nothing hangs,
    nothing is lost, no replica leaks pages."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.kill_replica(round=6)

    def factory(idx):
        return LLMEngine(model, params, max_slots=2, page_size=16,
                         n_pages=64, chunk=2, prefill_chunk=16,
                         temperature=0.0, eos_id=-1, seed=idx,
                         fault_injector=inj if idx == 0 else None)

    pool = EnginePool(factory, 2)
    import numpy as np
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 50, size=10).tolist() for _ in range(6)]
    want = [_reference_completion(model, params, p, 16)
            for p in prompts]
    results = [None] * len(prompts)

    def consume(i, h):
        try:
            results[i] = ("ok", h.result())
        except EngineShutdown:
            results[i] = ("typed", None)

    handles = [pool.submit(p, max_new_tokens=16) for p in prompts]
    threads = [threading.Thread(target=consume, args=(i, h))
               for i, h in enumerate(handles)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(not t.is_alive() for t in threads), "request hung"
    assert all(r is not None for r in results), "request lost"
    ok = [i for i, r in enumerate(results) if r[0] == "ok"]
    for i in ok:
        assert results[i][1] == want[i], i
    assert pool.route_stats["replica_deaths"] == 1
    assert pool.replica(0).state == DEAD
    # the kill actually interrupted work: something was resubmitted
    # or failed typed (a no-op kill would prove nothing)
    assert pool.route_stats["requeues"] + (len(results) - len(ok)) > 0
    pool.shutdown()
    check_pool_quiesced(pool)


def test_mid_stream_kill_fails_typed_after_tokens(tiny_model):
    """A request that already streamed tokens when its replica died
    must surface EngineShutdown — not silently resubmit (duplicate
    tokens) and not hang."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.kill_replica(round=6)
    pool = EnginePool(
        lambda i: LLMEngine(model, params, max_slots=1, page_size=16,
                            n_pages=32, chunk=2, prefill_chunk=16,
                            temperature=0.0, eos_id=-1, seed=i,
                            fault_injector=inj),
        1)
    h = pool.submit([5, 9, 2, 7], max_new_tokens=32)
    got = []
    with pytest.raises(EngineShutdown):
        for t in h.stream():
            got.append(t)
    # rounds are deterministic on CPU: round 6 lands mid-decode, so
    # tokens streamed before the kill and the typed partial-stream
    # path (not the resubmit path) is what fired
    assert got, "kill landed before first token; expected mid-stream"
    assert got == _reference_completion(model, params,
                                        [5, 9, 2, 7], 32)[:len(got)]
    assert h.error is not None
    pool.shutdown()
    check_pool_quiesced(pool)


def test_drain_completes_inflight_and_rebuilds(tiny_model):
    model, params = tiny_model
    pool = EnginePool(
        lambda i: LLMEngine(model, params, max_slots=2, page_size=8,
                            n_pages=32, chunk=4, temperature=0.0,
                            seed=i),
        2)
    prompt = [5, 9, 2, 7, 11]
    want = _reference_completion(model, params, prompt, 8)
    h = pool.submit(prompt, max_new_tokens=8)
    idx = h.replica_idx
    assert pool.drain(idx) is True      # waits for the request
    assert h.result() == want           # finished, not axed
    rep = pool.replica(idx)
    assert rep.state == HEALTHY and rep.generation == 1
    # the rebuilt replica serves
    h2 = pool.submit(prompt, max_new_tokens=8)
    assert h2.result() == want
    pool.shutdown()
    check_pool_quiesced(pool)


def test_draining_engine_rejects_direct_submits(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, temperature=0.0).start()
    eng.drain()
    assert eng.load_report()["draining"] is True
    with pytest.raises(EngineDraining):
        eng.submit([1, 2, 3], max_new_tokens=4)
    assert eng.wait_idle(5.0) is True
    eng.shutdown()
    check_quiesced(eng)


def test_engine_load_report_shape(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, temperature=0.0,
                    prefix_cache=True).start()
    prompt = [(i % 50) + 1 for i in range(16)]   # two full pages
    h = eng.submit(prompt, max_new_tokens=6)
    h.result()
    rpt = eng.load_report()
    for key in ("free_slots", "free_pages", "queue_depth",
                "outstanding_tokens", "max_queued",
                "shed_retry_after_s", "draining", "stopped",
                "prefix_digest"):
        assert key in rpt, key
    assert rpt["stopped"] is False and rpt["draining"] is False
    assert rpt["free_slots"] == 2
    # retirement (prompt pages -> radix cache) trails the stream by
    # one readback; poll briefly, then the digest must advertise the
    # prompt's page path for affinity routing
    deadline = time.monotonic() + 5.0
    while not rpt["prefix_digest"] and time.monotonic() < deadline:
        time.sleep(0.01)
        rpt = eng.load_report()
    assert rpt["prefix_digest"]
    hashes = path_hashes(prompt, eng.Pg)
    assert hashes[0] in rpt["prefix_digest"]
    eng.shutdown()
    check_quiesced(eng, expect_cached_pages=eng.prefix_cache
                   .cached_pages)


def test_cancel_through_pool_handle(tiny_model):
    model, params = tiny_model
    pool = EnginePool(
        lambda i: LLMEngine(model, params, max_slots=1, page_size=8,
                            n_pages=32, chunk=2, temperature=0.0,
                            eos_id=-1, seed=i),
        1)
    h = pool.submit([5, 9, 2, 7], max_new_tokens=64)
    assert h.cancel() is True
    from ray_tpu.serve.errors import RequestCancelled
    with pytest.raises(RequestCancelled):
        h.result()
    pool.shutdown()
    check_pool_quiesced(pool)


# -------------------------------- auto-restart backoff + crash loops


def test_auto_restart_backoff_doubles_and_caps(monkeypatch):
    """Each successive death of the same replica doubles the rebuild
    backoff until the cap — a crash-looping factory must not spin
    hot. The sleep itself is spied out so the test is timing-free."""
    backoffs = []
    orig = EnginePool._backoff_rebuild

    def spy(self, rep, backoff_s):
        backoffs.append(backoff_s)
        orig(self, rep, 0.0)          # skip the real sleep

    monkeypatch.setattr(EnginePool, "_backoff_rebuild", spy)
    fakes = {}

    def factory(i):
        f = FakeEngine(i)
        fakes[i] = f
        return f

    pool = EnginePool(factory, 2, auto_restart=True,
                      restart_backoff_s=0.1,
                      restart_backoff_max_s=0.4,
                      max_restarts=None)
    for _ in range(4):
        rep = pool.replica(0)
        rep.engine._stopped = True
        pool._note_replica_death(rep)
        deadline = time.monotonic() + 5.0
        while pool.replica(0).state != HEALTHY \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert pool.replica(0).state == HEALTHY
    assert backoffs == pytest.approx([0.1, 0.2, 0.4, 0.4])
    assert pool.replica(0).deaths == 4
    pool.shutdown()


def test_crash_loop_cap_parks_replica_degraded():
    """Past max_restarts the pool stops feeding the factory: the
    replica parks DEGRADED (skipped by routing), a full-pool outage
    surfaces as typed PoolDegraded (HTTP 503), and restart_dead() is
    the manual override that clears the state."""
    from ray_tpu.serve.engine_pool import DEGRADED
    from ray_tpu.serve.errors import (PoolDegraded,
                                      classify_http_status)
    fakes = {}

    def factory(i):
        f = FakeEngine(i)
        fakes[i] = f
        return f

    pool = EnginePool(factory, 1, auto_restart=True,
                      restart_backoff_s=0.0, max_restarts=1)
    # death 1: within budget, auto-rebuilds
    rep = pool.replica(0)
    rep.engine._stopped = True
    pool._note_replica_death(rep)
    deadline = time.monotonic() + 5.0
    while pool.replica(0).state != HEALTHY \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    assert pool.replica(0).state == HEALTHY
    # death 2: budget burned -> DEGRADED, no rebuild
    rep = pool.replica(0)
    rep.engine._stopped = True
    pool._note_replica_death(rep)
    assert pool.replica(0).state == DEGRADED
    assert pool.degraded is True
    assert pool.route_stats["crash_loops"] == 1
    assert pool.pool_stats()["degraded"] is True
    with pytest.raises(PoolDegraded) as ei:
        pool.submit([1, 2])
    assert classify_http_status(ei.value) == 503
    # PoolDegraded IS an EngineShutdown: existing handlers still match
    assert isinstance(ei.value, EngineShutdown)
    # manual intervention: restart_dead rebuilds DEGRADED replicas too
    assert pool.restart_dead() == 1
    assert pool.replica(0).state == HEALTHY
    assert pool.submit([1, 2]).result() == [1, 2]
    pool.shutdown()


def test_backoff_rebuild_aborts_when_world_moved():
    """A rebuild sleeping out its backoff must re-check the world:
    if the pool stopped meanwhile, no zombie replica may be built."""
    fakes = {}

    def factory(i):
        f = FakeEngine(i)
        fakes[i] = f
        return f

    pool = EnginePool(factory, 1, auto_restart=True,
                      restart_backoff_s=0.2, max_restarts=None)
    rep = pool.replica(0)
    rep.engine._stopped = True
    pool._note_replica_death(rep)     # restart thread now sleeping
    pool.shutdown()                   # ... and the pool stops
    time.sleep(0.4)
    assert pool.replica(0).state == DEAD
    assert pool.route_stats["restarts"] == 0


# ------------------------------------------ drain racing with death


def test_resubmit_after_death_skips_draining_replica():
    """The satellite race, deterministic at the fakes layer: replica
    2 is mid-drain when replica 0 dies; the orphaned request must
    resubmit to the remaining HEALTHY replica — a draining replica
    is finishing its last requests, never accepting new ones."""
    fakes = [FakeEngine(0, outstanding=0),
             FakeEngine(1, outstanding=50),
             FakeEngine(2, outstanding=5)]
    fakes[0].die_on_failure = True
    fakes[0].script.append(FakeHandle(fakes[0], [],
                                      RuntimeError("device lost")))
    fakes[1].script.append([7, 8])
    pool = _fake_pool(fakes)
    pool.replica(2).state = DRAINING
    fakes[2]._draining = True
    h = pool.submit([1, 2])           # least loaded: replica 0
    assert h.replica_idx == 0
    assert h.result() == [7, 8]
    assert h.replica_idx == 1         # NOT the draining replica
    assert fakes[2].submits == []
    assert pool.route_stats["requeues"] == 1
    pool.replica(2).state = HEALTHY
    pool.shutdown()


def test_drain_racing_replica_death_quiesces_leak_free(tiny_model):
    """End-to-end race: replica 1 drains WHILE replica 0 dies
    mid-decode. Every in-flight request either completes
    token-identically to the single-engine reference or fails typed
    EngineShutdown (post-stream deaths) — none lost, none landed on
    the draining replica's corpse, and every engine ever built
    quiesces with zero leaked pages (autouse fixture + explicit
    check)."""
    import numpy as np
    model, params = tiny_model
    inj = FaultInjector()
    inj.kill_replica(round=6)

    def factory(idx):
        return LLMEngine(model, params, max_slots=2, page_size=16,
                         n_pages=64, chunk=2, prefill_chunk=16,
                         temperature=0.0, eos_id=-1, seed=idx,
                         fault_injector=inj if idx == 0 else None)

    pool = EnginePool(factory, 3)
    rng = np.random.RandomState(23)
    prompts = [rng.randint(1, 1000, size=10).tolist()
               for _ in range(8)]
    want = [_reference_completion(model, params, p, 20)
            for p in prompts]
    handles = [pool.submit(p, max_new_tokens=20) for p in prompts]
    drainer = threading.Thread(target=lambda: pool.drain(1))
    drainer.start()
    completed = typed = 0
    for h, w in zip(handles, want):
        try:
            assert h.result() == w    # token-identical or typed
            completed += 1
        except EngineShutdown:
            typed += 1
    drainer.join(timeout=60)
    assert not drainer.is_alive()
    assert completed + typed == len(handles)   # lost == 0
    assert completed >= 1
    assert pool.route_stats["replica_deaths"] >= 1
    assert pool.route_stats["drains"] == 1
    pool.shutdown()
    check_pool_quiesced(pool)


def test_idle_replica_death_detected_at_route_time():
    """A replica that dies with NO in-flight requests has no handle
    around to trip the death path — routing is where the corpse
    becomes visible. The next submit must note the death (DEAD state,
    auto-restart scheduled) instead of leaving a 'healthy' zombie the
    router silently skips forever."""
    built = []

    def factory(i):
        eng = FakeEngine(i)
        built.append(eng)
        return eng

    pool = EnginePool(factory, 2, auto_restart=True,
                      restart_backoff_s=0.0)
    # replica 0's engine dies while idle: nothing in flight, nobody
    # observes it
    built[0]._stopped = True
    h = pool.submit([1, 2, 3])          # routes around the corpse
    assert h.replica_idx == 1
    assert pool.route_stats["replica_deaths"] == 1
    deadline = time.monotonic() + 5.0
    while (pool.replica(0).generation == 0
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert pool.replica(0).state == HEALTHY
    assert pool.replica(0).generation == 1
    assert len(built) == 3              # rebuild used the factory
    pool.shutdown()


def test_scale_down_drain_vs_kill_vs_resubmit_three_way():
    """The full three-way race, deterministic at the fakes layer:
    the autoscaler's scale_down is mid-drain on replica 2 (wait_idle
    gated open) when replica 0 dies with an unstreamed request in
    flight — the resubmit must land on replica 1, the only remaining
    HEALTHY replica. Replica 2 is then killed WHILE draining: a
    drained-and-killed replica must never receive a resubmission,
    and the retire converges instead of wedging the scale-down."""
    import threading as _t
    gate = _t.Event()
    fakes = [FakeEngine(0, outstanding=5),
             FakeEngine(1, outstanding=50),
             FakeEngine(2, outstanding=0)]
    fakes[2].wait_idle = lambda timeout_s=30.0: (
        gate.wait(timeout_s), True)[1]
    fakes[0].die_on_failure = True
    fakes[0].script.append(FakeHandle(fakes[0], [],
                                      RuntimeError("device lost")))
    fakes[1].script.append([7, 8])
    pool = _fake_pool(fakes)
    # arm the scale-down: least-loaded healthy replica is 2
    retired = []
    scaler = _t.Thread(target=lambda: retired.extend(
        pool.scale_down(1, timeout_s=10.0)))
    scaler.start()
    deadline = time.monotonic() + 5.0
    while (pool.replica(2).state != DRAINING
           and time.monotonic() < deadline):
        time.sleep(0.005)
    assert pool.replica(2).state == DRAINING
    # replica 0 (5 outstanding vs 50) takes the request and dies;
    # the resubmit races the in-progress drain
    h = pool.submit([1, 2])
    assert h.replica_idx == 0
    assert h.result() == [7, 8]
    assert h.replica_idx == 1          # NOT the draining replica
    # now the draining replica is killed mid-drain
    fakes[2]._stopped = True
    pool._note_replica_death(pool.replica(2))
    gate.set()
    scaler.join(timeout=10.0)
    assert not scaler.is_alive()
    assert retired == [2]
    # the drained-and-killed replica saw zero submissions, ever
    assert fakes[2].submits == []
    assert pool.route_stats["requeues"] == 1
    assert pool.route_stats["replica_deaths"] == 2
    assert pool.route_stats["replicas_retired"] == 1
    assert pool.replica(1).state == HEALTHY
    from ray_tpu.serve.engine_pool import RETIRED
    assert pool.replica(2).state == RETIRED
    pool.shutdown()
