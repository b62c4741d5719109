"""SLO-driven pool autoscaler tests (serve/pool_autoscaler.py).

Two layers, same split as test_engine_pool.py: the CONTROL surface
(decide/tick against scripted fake engines on a fake clock — policy
decisions, hysteresis, cooldowns, clamps, provisioning delay, denial)
and the end-to-end contract against real tiny-Llama engines —
scale-down goes through the health-gated drain so every in-flight
request completes token-identically, and the shrunk pool quiesces
leak-free."""
import threading
import time

import pytest

from ray_tpu.autoscaler.node_provider import (CapacityUnavailable,
                                              ImmediateCapacityProvider,
                                              ReplicaCapacityProvider,
                                              SimulatedTPUCloud,
                                              TPUSliceCapacityProvider)
from ray_tpu.serve.engine_pool import RETIRED, EnginePool
from ray_tpu.serve.errors import (EngineDraining, EngineOverloaded,
                                  EngineShutdown)
from ray_tpu.serve.pool_autoscaler import PoolAutoscaler, SLOPolicy
from ray_tpu.util import metrics


# ------------------------------------------------- fakes + fixtures


class FakeHandle:
    def __init__(self, tokens=(1, 2)):
        self._tokens = list(tokens)

    def stream(self):
        for t in self._tokens:
            yield t

    def cancel(self):
        return True


class FakeEngine:
    """A replica reduced to the signal surface the autoscaler senses:
    every load_report field is a mutable attribute the test scripts.
    """

    def __init__(self, idx):
        self.idx = idx
        self._stopped = False
        self._draining = False
        self.free_slots = 4
        self.total_slots = 4
        self.queue_depth = 0
        self.outstanding = 0
        self.shed_total = 0
        self.ttft_ewma = None
        self.shed_next = False      # submit raises EngineOverloaded
        self.stats = {"submitted": 0}
        self.ttfts_s = []
        self.shutdowns = 0

    def start(self):
        return self

    def submit(self, prompt, max_new_tokens=64, deadline_s=None):
        if self._stopped:
            raise EngineShutdown("stopped")
        if self._draining:
            raise EngineDraining("draining")
        if self.shed_next:
            raise EngineOverloaded("shed", retry_after_s=0.1)
        self.stats["submitted"] += 1
        return FakeHandle()

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
        return {"free_slots": self.free_slots,
                "total_slots": self.total_slots,
                "free_pages": 100,
                "queue_depth": self.queue_depth,
                "outstanding_tokens": self.outstanding,
                "max_queued": None,
                "shed_retry_after_s": 0.1,
                "shed_total": self.shed_total,
                "ttft_ewma_s": self.ttft_ewma,
                "draining": self._draining,
                "stopped": self._stopped,
                "prefix_digest": frozenset()}

    def prefix_stats(self):
        return None

    def spec_stats(self):
        return None

    def lifecycle_stats(self):
        return {"max_queued": None, "max_retries": 2,
                "retry_backoff_s": 0.02, "shed": 0}


class ManualProvider(ReplicaCapacityProvider):
    """Capacity that becomes ready only when the test says so."""

    def __init__(self, eta=1.0, capacity=None):
        self.eta = eta
        self.capacity = capacity
        self.requested = []
        self.ready_tickets = set()
        self.released = []
        self._n = 0

    def request(self):
        held = len(self.requested) - len(self.released)
        if self.capacity is not None and held >= self.capacity:
            raise CapacityUnavailable("at capacity")
        self._n += 1
        t = f"ticket-{self._n}"
        self.requested.append(t)
        return t

    def ready(self, ticket):
        return ticket in self.ready_tickets

    def eta_s(self, ticket):
        return 0.0 if ticket in self.ready_tickets else self.eta

    def release(self, ticket):
        self.released.append(ticket)


def _rig(n=1, policy=None, provider=None):
    """(pool, scaler, clock, engines): a fake-engine pool plus an
    autoscaler on a hand-cranked clock. ``clock[0] += x`` advances
    time; tick() is driven manually (no thread)."""
    engines = {}

    def factory(idx):
        engines[idx] = FakeEngine(idx)
        return engines[idx]

    pool = EnginePool(factory, n)
    clock = [0.0]
    scaler = PoolAutoscaler(
        pool,
        policy or SLOPolicy(min_replicas=n, max_replicas=4,
                            queue_high=2.0, queue_low=0.5,
                            idle_stable_s=5.0, cooldown_up_s=0.0,
                            cooldown_down_s=0.0),
        provider or ManualProvider(),
        time_fn=lambda: clock[0])
    return pool, scaler, clock, engines


# --------------------------------------------------- policy decisions


def test_scale_up_on_queue_pressure():
    pool, scaler, clock, engines = _rig()
    engines[0].queue_depth = 5        # 5 per replica > queue_high 2
    assert scaler.tick() == "up"
    assert len(scaler.provider.requested) == 1
    # capacity is ON ORDER, not live: the replica joins on a later
    # tick, once the provider reports the ticket ready
    assert pool.active_count() == 1
    assert scaler.target_replicas() == 2
    scaler.provider.ready_tickets.update(scaler.provider.requested)
    clock[0] += 1.0
    scaler.tick()
    assert pool.active_count() == 2
    assert scaler.stats()["replicas_added"] == 1
    pool.shutdown()


def test_scale_up_on_shed_pressure():
    pool, scaler, clock, engines = _rig()
    scaler.tick()                     # baseline shed_total sample
    engines[0].shed_total = 3
    clock[0] += 1.0
    assert scaler.tick() == "up"      # shed_rate 3/s > shed_rate_high 0
    pool.shutdown()


def test_scale_up_on_ttft_slo_breach():
    pool, scaler, clock, engines = _rig(
        policy=SLOPolicy(max_replicas=4, ttft_slo_s=0.5,
                         cooldown_up_s=0.0))
    engines[0].ttft_ewma = 0.9        # over the 0.5s SLO
    assert scaler.tick() == "up"
    pool.shutdown()


def test_hold_inside_hysteresis_band():
    pool, scaler, clock, engines = _rig()
    # queue_per_replica 1.0 sits between queue_low 0.5 and
    # queue_high 2.0: neither pressured nor idle — hold forever
    engines[0].queue_depth = 1
    for _ in range(5):
        assert scaler.tick() == "hold"
        clock[0] += 10.0
    assert scaler.provider.requested == []
    assert pool.active_count() == 1
    assert scaler.stats()["holds"] == 5
    pool.shutdown()


def test_scale_down_on_sustained_idle_via_drain():
    pool, scaler, clock, engines = _rig(
        n=2, policy=SLOPolicy(min_replicas=1, max_replicas=4,
                              idle_stable_s=5.0,
                              cooldown_down_s=0.0))
    assert scaler.tick() == "hold"    # idle starts counting here
    clock[0] += 2.0
    assert scaler.tick() == "hold"    # idle but not yet stable
    clock[0] += 4.0                   # 6s idle > idle_stable_s 5
    assert scaler.tick() == "down"
    assert pool.active_count() == 1
    # scale-down went THROUGH the drain path: the retired engine was
    # put into draining before shutdown, and its slot is a tombstone
    retired = [e for e in engines.values() if e.shutdowns][0]
    assert retired._draining
    states = [r["state"] for r in pool.pool_stats()["replicas"]]
    assert states.count(RETIRED) == 1
    pool.shutdown()


def test_idle_timer_resets_on_activity():
    pool, scaler, clock, engines = _rig(
        n=2, policy=SLOPolicy(min_replicas=1, max_replicas=4,
                              idle_stable_s=5.0,
                              cooldown_down_s=0.0))
    scaler.tick()
    clock[0] += 4.0
    engines[0].queue_depth = 1        # activity inside the window
    scaler.tick()
    engines[0].queue_depth = 0
    clock[0] += 4.0
    # 8s since first idle tick, but the timer RESTARTED at 4s: only
    # 4s of continuous idle — not enough
    assert scaler.tick() == "hold"
    assert pool.active_count() == 2
    pool.shutdown()


def test_cooldown_limits_consecutive_scale_ups():
    pool, scaler, clock, engines = _rig(
        policy=SLOPolicy(max_replicas=4, cooldown_up_s=10.0))
    engines[0].queue_depth = 50       # sustained heavy pressure
    assert scaler.tick() == "up"
    clock[0] += 1.0
    assert scaler.tick() == "hold"    # refractory
    clock[0] += 10.0
    assert scaler.tick() == "up"
    assert len(scaler.provider.requested) == 2
    pool.shutdown()


def test_scale_down_cooldown():
    pool, scaler, clock, engines = _rig(
        n=3, policy=SLOPolicy(min_replicas=1, max_replicas=4,
                              idle_stable_s=1.0,
                              cooldown_down_s=30.0))
    scaler.tick()
    clock[0] += 2.0
    assert scaler.tick() == "down"
    assert pool.active_count() == 2
    clock[0] += 2.0                   # idle again, but in cooldown
    assert scaler.tick() == "hold"
    clock[0] += 30.0
    assert scaler.tick() == "down"
    assert pool.active_count() == 1
    pool.shutdown()


def test_max_replicas_clamp():
    provider = ManualProvider()
    pool, scaler, clock, engines = _rig(
        policy=SLOPolicy(max_replicas=2, cooldown_up_s=0.0),
        provider=provider)
    engines[0].queue_depth = 50
    assert scaler.tick() == "up"      # target 2 == max
    clock[0] += 1.0
    assert scaler.tick() == "hold"    # clamped: never over-orders
    assert len(provider.requested) == 1
    pool.shutdown()


def test_min_replicas_clamp():
    pool, scaler, clock, engines = _rig(
        policy=SLOPolicy(min_replicas=1, max_replicas=4,
                         idle_stable_s=1.0, cooldown_down_s=0.0))
    scaler.tick()
    clock[0] += 100.0
    assert scaler.tick() == "hold"    # idle forever, but at the floor
    assert pool.active_count() == 1
    assert [e.shutdowns for e in engines.values()] == [0]
    pool.shutdown()


# ---------------------------------------- provisioning delay + denial


def test_pending_capacity_counts_toward_target_and_eta():
    provider = ManualProvider(eta=3.0)
    pool, scaler, clock, engines = _rig(provider=provider)
    engines[0].queue_depth = 50
    scaler.tick()
    assert scaler.target_replicas() == 2
    assert scaler.capacity_eta_s() == 3.0
    # still pressured: a second order is placed (target 3), but the
    # unready tickets never become replicas on their own
    clock[0] += 1.0
    scaler.tick()
    assert pool.active_count() == 1
    assert scaler.target_replicas() == 3
    pool.shutdown()


def test_all_shed_hint_covers_provisioning_eta():
    """The Retry-After honesty contract: with capacity still
    provisioning, a full-pool shed must hint AT LEAST the remaining
    ETA — never invite the client back before a replica exists."""
    provider = ManualProvider(eta=3.0)
    pool, scaler, clock, engines = _rig(provider=provider)
    engines[0].queue_depth = 50
    scaler.tick()                     # order placed, eta 3.0
    engines[0].shed_next = True
    with pytest.raises(EngineOverloaded) as ei:
        pool.submit([1, 2, 3])
    assert ei.value.retry_after_s >= 3.0
    pool.shutdown()


def test_no_scale_down_while_capacity_pending():
    """Order in flight + idle pool: retiring NOW would race the
    incoming replica (pay provisioning, then immediately drain) —
    the controller waits for the order to land first."""
    provider = ManualProvider(eta=3.0)
    pool, scaler, clock, engines = _rig(
        n=2, policy=SLOPolicy(min_replicas=1, max_replicas=4,
                              idle_stable_s=0.5, cooldown_up_s=0.0,
                              cooldown_down_s=0.0),
        provider=provider)
    engines[0].queue_depth = 50
    scaler.tick()                     # pending order
    engines[0].queue_depth = 0
    clock[0] += 10.0
    scaler.tick()
    clock[0] += 10.0
    assert scaler.tick() == "hold"
    assert pool.active_count() == 2
    pool.shutdown()


def test_capacity_denial_is_counted_not_fatal():
    provider = ManualProvider(capacity=0)
    pool, scaler, clock, engines = _rig(provider=provider)
    engines[0].queue_depth = 50
    assert scaler.tick() == "hold"    # wanted up, provider said no
    assert scaler.stats()["denied"] == 1
    assert scaler.target_replicas() == 1
    pool.shutdown()


def test_retired_replica_releases_its_ticket():
    provider = ManualProvider(eta=0.0)
    provider.ready_tickets = set()
    pool, scaler, clock, engines = _rig(
        policy=SLOPolicy(min_replicas=1, max_replicas=4,
                         idle_stable_s=1.0, cooldown_up_s=0.0,
                         cooldown_down_s=0.0),
        provider=provider)
    engines[0].queue_depth = 50
    scaler.tick()
    engines[0].queue_depth = 0        # pressure relieved before the
    provider.ready_tickets.update(    # order lands (else the still-
        provider.requested)           # hot queue orders MORE)
    clock[0] += 1.0
    scaler.tick()                     # harvest: replica 1 joins
    assert pool.active_count() == 2
    # load sits on the pool-born replica, so scale-down retires the
    # TICKETED one (least loaded) — its capacity must go back
    engines[0].outstanding = 10
    clock[0] += 2.0
    assert scaler.tick() == "down"
    assert provider.released == provider.requested
    # the pool-born survivor carries no ticket: nothing left pending
    assert scaler.stats()["pending"] == 0
    pool.shutdown()


def test_tpu_slice_provider_lifecycle():
    # readiness is wall-clock in the sim, so model a short real delay
    cloud = SimulatedTPUCloud(provision_delay_s=0.2)
    provider = TPUSliceCapacityProvider(cloud, "v5e-1")
    t = provider.request()
    assert not provider.ready(t)
    assert provider.eta_s(t) > 0
    deadline = time.monotonic() + 5.0
    while not provider.ready(t) and time.monotonic() < deadline:
        time.sleep(0.02)
    assert provider.ready(t)
    assert provider.eta_s(t) == 0.0
    provider.release(t)
    provider.release(t)               # idempotent
    assert provider.eta_s(t) == 0.0   # gone = nothing to wait for


# ------------------------------------------------- surfacing + loop


def test_metrics_and_pool_stats_surface_autoscale():
    metrics.clear_registry()
    pool, scaler, clock, engines = _rig()
    engines[0].queue_depth = 50
    scaler.tick()
    def _val(name):
        samples = metrics.registry()[name]._samples()
        return samples[0][1] if samples else 0

    assert _val("serve_pool_scale_up_total") == 1
    assert _val("serve_pool_target_replicas") == 2
    engines[0].queue_depth = 1
    clock[0] += 1.0
    scaler.tick()
    assert _val("serve_pool_scale_hold_total") == 1
    block = pool.pool_stats()["autoscale"]
    assert block["scale_ups"] == 1
    assert block["ticks"] == 2
    assert block["target_replicas"] == 2
    assert block["max_replicas"] == 4
    pool.shutdown()
    metrics.clear_registry()


def test_background_loop_scales_up_and_stops():
    engines = {}

    def factory(idx):
        engines[idx] = FakeEngine(idx)
        return engines[idx]

    pool = EnginePool(factory, 1)
    provider = ManualProvider(eta=0.0)
    scaler = PoolAutoscaler(
        pool, SLOPolicy(max_replicas=2, cooldown_up_s=0.0),
        provider).run(interval_s=0.01)
    engines[0].queue_depth = 50
    deadline = time.monotonic() + 5.0
    while not provider.requested and time.monotonic() < deadline:
        time.sleep(0.01)
    provider.ready_tickets.update(provider.requested)
    while pool.active_count() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    scaler.stop()
    assert pool.active_count() == 2
    assert scaler.stats()["ticks"] > 0
    pool.shutdown()


def test_policy_validation():
    with pytest.raises(ValueError):
        SLOPolicy(min_replicas=0)
    with pytest.raises(ValueError):
        SLOPolicy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        SLOPolicy(queue_low=5.0, queue_high=1.0)


# ------------------------------------- end-to-end with real engines


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama, llama_tiny
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def test_scale_down_drains_without_losing_inflight(tiny_model):
    """The acceptance contract: scale-down is indistinguishable from
    a rolling drain — every request in flight on the retiring replica
    completes TOKEN-IDENTICALLY to the single-engine reference, and
    the shrunk pool quiesces leak-free."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.llama import generate
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.faults import check_pool_quiesced
    model, params = tiny_model

    def factory(idx):
        return LLMEngine(model, params, max_slots=2, page_size=16,
                         n_pages=64, chunk=2, prefill_chunk=16,
                         temperature=0.0, eos_id=-1, seed=idx)

    pool = EnginePool(factory, 2)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 1000, size=10).tolist()
               for _ in range(6)]
    want = [np.asarray(generate(
        model, params, jnp.asarray([p], jnp.int32),
        max_new_tokens=16, temperature=0.0))[0, len(p):].tolist()
        for p in prompts]
    handles = [pool.submit(p, max_new_tokens=16) for p in prompts]
    # retire one replica while all six requests are in flight
    retired = pool.scale_down(1, timeout_s=30.0)
    assert len(retired) == 1
    got = [h.result() for h in handles]
    assert got == want
    assert pool.active_count() == 1
    assert pool.healthy_count() == 1
    # new load routes onto the survivor
    h = pool.submit(prompts[0], max_new_tokens=16)
    assert h.result() == want[0]
    pool.shutdown()
    check_pool_quiesced(pool)


def test_scale_to_grows_and_shrinks_real_pool(tiny_model):
    import numpy as np
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.faults import check_pool_quiesced
    model, params = tiny_model

    def factory(idx):
        return LLMEngine(model, params, max_slots=2, page_size=16,
                         n_pages=64, chunk=2, prefill_chunk=16,
                         temperature=0.0, eos_id=-1, seed=idx)

    pool = EnginePool(factory, 1)
    assert pool.scale_to(3) == 3
    rng = np.random.RandomState(5)
    handles = [pool.submit(rng.randint(1, 1000, size=8).tolist(),
                           max_new_tokens=8) for _ in range(6)]
    for h in handles:
        assert len(h.result()) == 8
    assert pool.scale_to(1) == 1
    # the freed slots are tombstones, reusable by the next scale-up
    assert pool.scale_to(2) == 2
    pool.shutdown()
    check_pool_quiesced(pool)
