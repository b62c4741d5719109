"""Prefill/decode disaggregation: role-aware pools over the KV
handoff path.

What is covered here (PR 18):

- ``role_plan_caps``: the pure planner-knob mapping — prefill
  replicas refuse decode-phase growth, decode replicas collapse the
  prefill lane to a handoff-tail budget, unified passes through,
  typos raise.
- ``EnginePool(roles=)`` validation: every replica must be named, the
  names must be real roles, and a disaggregated pool without
  ``share_prefixes=True`` (the KV handoff wiring) is a construction
  error, not a silent re-prefill.
- Routing policy on scripted fakes: the two-leg online split (leg 1
  one bridging token on the prefill side, leg 2 the rest on the
  decode side carrying the finished-prefill push hint), the typed
  decode-in-place fallback when the decode side is gone, and the two
  guardrails the satellites demand — the batch lane and session
  stickiness never target a prefill-only replica.
- Token parity on real engines: a role-split pool must produce the
  exact ``generate()`` stream through the handoff, and again through
  the decode-dead fallback ladder (disaggregation can cost time,
  never correctness).
- Per-role autoscaling: two ``PoolAutoscaler``s over ``RolePoolView``s
  of ONE pool reach different sizes on the same signals.
- ``validate_pull_knobs`` / ``LlamaDeployment`` knob validation: junk
  pull knobs and contradictory role splits fail at construction.
"""
import pytest

jnp = pytest.importorskip("jax.numpy")

from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.serve import kv_migration
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.engine_pool import EnginePool, RolePoolView
from ray_tpu.serve.scheduler import (LANE_BATCH, ROLE_DECODE,
                                     ROLE_PREFILL, ROLE_UNIFIED,
                                     role_plan_caps)
from ray_tpu.serve.errors import EngineShutdown


# ----------------------------------------------------- planner knobs


def test_role_plan_caps_prefill_clamps_run_ahead():
    caps = role_plan_caps(ROLE_PREFILL, page_size=16, decode_chunk=4,
                          prefill_chunk=512, prefill_batch=4,
                          max_run_ahead=256)
    assert caps == {"prefill_chunk": 512, "prefill_batch": 4,
                    "max_run_ahead": 4}


def test_role_plan_caps_decode_collapses_prefill_budget():
    # page_size + 1: one residual page plus the bridging token — the
    # largest tail a handoff can leave unpulled
    # ... and ONE row of it: the lane is no larger than the 17 tokens
    # a round it was when the rows shared one budget
    caps = role_plan_caps(ROLE_DECODE, page_size=16, decode_chunk=4,
                          prefill_chunk=512, prefill_batch=4,
                          max_run_ahead=256)
    assert caps == {"prefill_chunk": 17, "prefill_batch": 1,
                    "max_run_ahead": 256}


def test_role_plan_caps_unified_passthrough():
    caps = role_plan_caps(ROLE_UNIFIED, page_size=16, decode_chunk=4,
                          prefill_chunk=512, prefill_batch=4,
                          max_run_ahead=256)
    assert caps == {"prefill_chunk": 512, "prefill_batch": 4,
                    "max_run_ahead": 256}


def test_role_plan_caps_floors_never_zero():
    # degenerate knobs still leave one unit of budget on each side
    caps = role_plan_caps(ROLE_PREFILL, page_size=1, decode_chunk=0,
                          prefill_chunk=1, prefill_batch=4,
                          max_run_ahead=8)
    assert caps["max_run_ahead"] == 1
    caps = role_plan_caps(ROLE_DECODE, page_size=0, decode_chunk=4,
                          prefill_chunk=0, prefill_batch=4,
                          max_run_ahead=8)
    assert caps["prefill_chunk"] == 1 and caps["prefill_batch"] == 1


def test_role_plan_caps_unknown_role_raises():
    with pytest.raises(ValueError, match="unknown replica role"):
        role_plan_caps("prefil", page_size=16, decode_chunk=4,
                       prefill_chunk=512, prefill_batch=4,
                       max_run_ahead=256)


# ------------------------------------------------- pull-knob typing


def test_validate_pull_knobs_defaults_and_overrides():
    assert kv_migration.validate_pull_knobs() == {}
    assert kv_migration.validate_pull_knobs(None, None) == {}
    assert kv_migration.validate_pull_knobs(2.5, 0.01) == {
        "deadline_s": 2.5, "backoff_s": 0.01}
    # one-sided override returns only the overridden knob
    assert kv_migration.validate_pull_knobs(backoff_s=1) == {
        "backoff_s": 1.0}


@pytest.mark.parametrize("bad", ["soon", 0, -1.0, float("inf"),
                                 float("nan"), [1.0]])
def test_validate_pull_knobs_rejects_junk(bad):
    with pytest.raises(ValueError, match="kv pull deadline_s"):
        kv_migration.validate_pull_knobs(deadline_s=bad)
    with pytest.raises(ValueError, match="kv pull backoff_s"):
        kv_migration.validate_pull_knobs(backoff_s=bad)


# ------------------------------------------------------ fake engines


class FakeHandle:
    def __init__(self, engine, tokens, exc=None):
        self._engine = engine
        self._tokens = list(tokens)
        self._exc = exc
        self.cancelled = False

    def stream(self):
        for t in self._tokens:
            yield t
        if self._exc is not None:
            raise self._exc

    def cancel(self):
        self.cancelled = True
        return True


class FakeEngine:
    """The pool-facing engine surface, scripted — accepts the full
    disaggregated submit signature (``pull=``, ``priority=``) and
    records every kwarg so tests can assert on what routing sent."""

    def __init__(self, idx, *, outstanding=0, page_size=16,
                 report_extra=None):
        self.idx = idx
        self.Pg = page_size
        self._stopped = False
        self._draining = False
        self.outstanding = outstanding
        self.report_extra = dict(report_extra or {})
        self.submits = []           # (prompt, max_new_tokens, kwargs)
        self.script = []            # queued submit outcomes
        self.started = False

    def start(self):
        self.started = True
        return self

    def submit(self, prompt, max_new_tokens=64, deadline_s=None, **kw):
        if self._stopped:
            raise EngineShutdown("engine stopped")
        self.submits.append((list(prompt), max_new_tokens, kw))
        out = self.script.pop(0) if self.script else [1, 2]
        if isinstance(out, BaseException):
            raise out
        return FakeHandle(self, out)

    def shutdown(self):
        self._stopped = True

    def drain(self):
        self._draining = True

    def wait_idle(self, timeout_s=30.0):
        return True

    def is_idle(self):
        return True

    def load_report(self):
        rpt = {"free_slots": 4, "free_pages": 100, "queue_depth": 0,
               "outstanding_tokens": self.outstanding,
               "max_queued": None, "shed_retry_after_s": 1.0,
               "draining": self._draining, "stopped": self._stopped,
               "prefix_digest": frozenset()}
        rpt.update(self.report_extra)
        return rpt

    def prefix_stats(self):
        return None

    def spec_stats(self):
        return None

    def lifecycle_stats(self):
        return {"max_queued": None, "max_retries": 2,
                "retry_backoff_s": 0.02, "shed": 0}


def _fake_disagg_pool(fakes, n=None, **kw):
    kw.setdefault("share_prefixes", True)
    kw.setdefault("roles", [ROLE_PREFILL, ROLE_DECODE])
    pool = EnginePool(lambda i: fakes[i], n or len(fakes), **kw)
    return pool


# -------------------------------------------- construction contracts


def test_roles_must_name_every_replica():
    fakes = [FakeEngine(0), FakeEngine(1)]
    with pytest.raises(ValueError, match="every replica"):
        EnginePool(lambda i: fakes[i], 2, share_prefixes=True,
                   roles=[ROLE_PREFILL])


def test_unknown_role_rejected_at_construction():
    fakes = [FakeEngine(0), FakeEngine(1)]
    with pytest.raises(ValueError, match="unknown replica role"):
        EnginePool(lambda i: fakes[i], 2, share_prefixes=True,
                   roles=[ROLE_PREFILL, "decoder"])


def test_disaggregated_pool_requires_share_prefixes():
    fakes = [FakeEngine(0), FakeEngine(1)]
    with pytest.raises(ValueError, match="share_prefixes"):
        EnginePool(lambda i: fakes[i], 2,
                   roles=[ROLE_PREFILL, ROLE_DECODE])
    # an all-unified roles list is NOT disaggregated: no wiring needed
    pool = EnginePool(lambda i: fakes[i], 2,
                      roles=[ROLE_UNIFIED, ROLE_UNIFIED])
    assert not pool.disaggregated()
    pool.shutdown()


def test_pool_kv_pull_knobs_validated_at_construction():
    fakes = [FakeEngine(0), FakeEngine(1)]
    with pytest.raises(ValueError, match="kv pull deadline_s"):
        _fake_disagg_pool(fakes, kv_pull_deadline_s=-1.0)


# ------------------------------------------------ routing on fakes


def test_two_leg_split_routes_prefill_then_decode_with_hint():
    prompt = list(range(1, 33))            # 2 full pages at Pg=16
    fakes = [FakeEngine(0), FakeEngine(1)]
    fakes[0].script = [[5]]                # leg 1: bridging token
    fakes[1].script = [[6, 7, 8]]          # leg 2: rest of stream
    pool = _fake_disagg_pool(fakes)
    try:
        assert pool.disaggregated()
        toks = pool.submit(prompt, max_new_tokens=4).result()
        assert toks == [5, 6, 7, 8]
        # leg 1 landed on the prefill replica for exactly one token
        (p1, mnt1, _), = fakes[0].submits
        assert (p1, mnt1) == (prompt, 1)
        # leg 2 resumed at full prompt length + bridging token on the
        # decode replica, carrying the donor's push hint
        (p2, mnt2, kw2), = fakes[1].submits
        assert (p2, mnt2) == (prompt + [5], 3)
        hint = kw2["pull"]
        assert hint["replica_idx"] == 0
        assert len(hint["hashes"]) == 2
        ps = pool.pool_stats()
        assert ps["disagg_handoffs"] == 1
        assert ps.get("disagg_handoff_fallbacks", 0) == 0
        names = [e[2] for e in pool.events.tail(64)]
        assert "handoff" in names
        assert "handoff_first_token" in names
    finally:
        pool.shutdown()


def test_dead_decode_side_falls_back_to_decode_in_place():
    prompt = list(range(1, 33))
    fakes = [FakeEngine(0), FakeEngine(1)]
    fakes[0].script = [[5], [6, 7, 8]]     # leg 1, then the fallback
    pool = _fake_disagg_pool(fakes)
    try:
        fakes[1]._stopped = True           # decode side dies
        toks = pool.submit(prompt, max_new_tokens=4).result()
        assert toks == [5, 6, 7, 8]
        # both legs served by the donor: leg 1, then decode-in-place
        assert [s[:2] for s in fakes[0].submits] == [
            (prompt, 1), (prompt + [5], 3)]
        # the fallback leg is a direct-target submit, no pull hint
        assert "pull" not in fakes[0].submits[1][2]
        ps = pool.pool_stats()
        assert ps["disagg_handoff_fallbacks"] == 1
        names = [e[2] for e in pool.events.tail(64)]
        assert "handoff_fallback" in names
    finally:
        pool.shutdown()


def test_single_token_requests_skip_the_handoff():
    fakes = [FakeEngine(0), FakeEngine(1)]
    fakes[0].script = [[9]]
    fakes[1].script = [[9]]
    pool = _fake_disagg_pool(fakes)
    try:
        pool.submit(list(range(1, 33)), max_new_tokens=1).result()
        assert pool.pool_stats().get("disagg_handoffs", 0) == 0
    finally:
        pool.shutdown()


def test_batch_lane_never_lands_on_prefill_replica():
    # the prefill replica is EMPTIER — batch must still skip it
    fakes = [FakeEngine(0, outstanding=0),
             FakeEngine(1, outstanding=900)]
    pool = _fake_disagg_pool(fakes)
    try:
        pool.submit(list(range(8)), max_new_tokens=4,
                    priority=LANE_BATCH).result()
        assert fakes[0].submits == []
        assert len(fakes[1].submits) == 1
    finally:
        pool.shutdown()


def test_batch_lane_with_only_prefill_capacity_fails_typed():
    fakes = [FakeEngine(0), FakeEngine(1)]
    pool = _fake_disagg_pool(fakes)
    try:
        fakes[1].shutdown()
        with pytest.raises(EngineShutdown):
            pool.submit(list(range(8)), max_new_tokens=4,
                        priority=LANE_BATCH).result()
    finally:
        pool.shutdown()


def test_sticky_session_pinned_to_prefill_is_dropped_not_followed():
    fakes = [FakeEngine(0, outstanding=900),
             FakeEngine(1, outstanding=0)]
    pool = _fake_disagg_pool(fakes)
    try:
        # a stale placement entry (e.g. written before the replica
        # was re-roled) pins the session to the prefill replica
        with pool._lock:
            pool._sticky["s"] = 0
        pool.submit(list(range(8)), max_new_tokens=1,
                    session_id="s").result()
        assert fakes[0].submits == []      # never followed to prefill
        assert pool._sticky["s"] == 1      # re-pinned where it landed
        assert pool.route_stats["sticky_hits"] == 0
    finally:
        pool.shutdown()


# --------------------------------------------- per-role autoscaling


def test_role_pool_views_scale_apart_on_the_same_pool():
    from ray_tpu.serve.pool_autoscaler import (
        ImmediateCapacityProvider, PoolAutoscaler, SLOPolicy)
    fakes = [FakeEngine(i) for i in range(4)]
    # the prefill side is breaching its TTFT SLO; the decode side is
    # comfortably idle on ITL + free slots
    fakes[0].report_extra = {"ttft_ewma_s": 0.5, "total_slots": 4}
    fakes[1].report_extra = {"itl_ewma_s": 0.001, "total_slots": 4}
    pool = _fake_disagg_pool(fakes, n=2)
    try:
        provider = ImmediateCapacityProvider()
        sc_pre = PoolAutoscaler(
            RolePoolView(pool, ROLE_PREFILL),
            SLOPolicy(min_replicas=1, max_replicas=2,
                      ttft_slo_s=0.001, cooldown_up_s=0.0),
            provider)
        sc_dec = PoolAutoscaler(
            RolePoolView(pool, ROLE_DECODE),
            SLOPolicy(min_replicas=1, max_replicas=2,
                      itl_slo_s=60.0, idle_stable_s=3600.0),
            provider)
        for _ in range(4):
            sc_pre.tick()
            sc_dec.tick()
            if pool.role_counts().get(ROLE_PREFILL, 0) > 1:
                break
        counts = pool.role_counts()
        assert counts[ROLE_PREFILL] == 2    # scaled up into fakes[2]
        assert counts[ROLE_DECODE] == 1     # held
        assert sc_pre.counts["scale_ups"] >= 1
        assert sc_dec.counts["scale_ups"] == 0
        # the new replica joined with the view's role
        ps = pool.pool_stats()
        roles = [r["role"] for r in ps["replicas"]]
        assert roles.count(ROLE_PREFILL) == 2
        assert "autoscale_by_role" in ps
        assert set(ps["autoscale_by_role"]) == {ROLE_PREFILL,
                                                ROLE_DECODE}
    finally:
        pool.shutdown()


def test_role_pool_view_rejects_unknown_role():
    fakes = [FakeEngine(0), FakeEngine(1)]
    pool = _fake_disagg_pool(fakes)
    try:
        with pytest.raises(ValueError, match="unknown replica role"):
            RolePoolView(pool, "prefil")
    finally:
        pool.shutdown()


# --------------------------------------------- real-engine parity


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
    """Every real engine built here — including replicas the pool
    added or killed — must end with allocator occupancy equal to
    prefix-cache residency."""
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


def _real_disagg_pool(model, params):
    def factory(idx):
        return LLMEngine(model, params, max_slots=2, page_size=8,
                         n_pages=48, chunk=2, prefill_chunk=8,
                         temperature=0.0, eos_id=-1, seed=0,
                         prefix_cache=True)
    return EnginePool(factory, 2, share_prefixes=True,
                      roles=[ROLE_PREFILL, ROLE_DECODE], seed=0)


def test_disagg_handoff_is_token_identical(tiny_model):
    import numpy as np
    model, params = tiny_model
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, llama_tiny().vocab_size - 1,
                         size=24).tolist()
    want = _reference_completion(model, params, prompt, 8)
    pool = _real_disagg_pool(model, params)
    try:
        toks = pool.submit(list(prompt), max_new_tokens=8).result()
        assert toks == want
        ps = pool.pool_stats()
        assert ps["disagg_handoffs"] >= 1
        assert ps.get("disagg_handoff_fallbacks", 0) == 0
        # the decode leg actually pulled the donor's pages instead of
        # re-prefilling: the prompt is 3 full pages at Pg=8
        decode_eng = next(
            e for e, r in zip(pool.engines(), ps["replicas"])
            if r["role"] == ROLE_DECODE)
        assert decode_eng.kv_migration_stats["pulled_pages"] >= 3
    finally:
        pool.shutdown()


def test_disagg_decode_dead_recovers_token_identical(tiny_model):
    import numpy as np
    model, params = tiny_model
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, llama_tiny().vocab_size - 1,
                         size=24).tolist()
    want = _reference_completion(model, params, prompt, 8)
    pool = _real_disagg_pool(model, params)
    try:
        ps = pool.pool_stats()
        decode_idx = next(i for i, r in enumerate(ps["replicas"])
                          if r["role"] == ROLE_DECODE)
        pool.engines()[decode_idx].shutdown()
        toks = pool.submit(list(prompt), max_new_tokens=8).result()
        assert toks == want
        assert pool.pool_stats()["disagg_handoff_fallbacks"] >= 1
    finally:
        pool.shutdown()


# ------------------------------------------- deployment-level knobs


def test_deployment_role_knobs_require_disaggregate():
    from ray_tpu.serve.llm import LlamaDeployment
    with pytest.raises(ValueError, match="require"):
        LlamaDeployment(params=object(), prefill_replicas=2)


def test_deployment_disaggregate_excludes_fleet():
    from ray_tpu.serve.llm import LlamaDeployment
    with pytest.raises(ValueError, match="exclusive"):
        LlamaDeployment(params=object(), disaggregate=True,
                        prefix_cache=True, fleet=2)


def test_deployment_disaggregate_requires_prefix_cache():
    from ray_tpu.serve.llm import LlamaDeployment
    with pytest.raises(ValueError, match="prefix_cache"):
        LlamaDeployment(params=object(), disaggregate=True)


def test_deployment_replica_count_must_match_role_split():
    from ray_tpu.serve.llm import LlamaDeployment
    with pytest.raises(ValueError, match="conflicts"):
        LlamaDeployment(params=object(), disaggregate=True,
                        prefix_cache=True, prefill_replicas=2,
                        decode_replicas=2, num_engine_replicas=3)
    d = LlamaDeployment(params=object(), disaggregate=True,
                        prefix_cache=True, prefill_replicas=1,
                        decode_replicas=2)
    assert d.num_engine_replicas == 3


def test_deployment_rejects_junk_pull_knobs():
    from ray_tpu.serve.llm import LlamaDeployment
    with pytest.raises(ValueError, match="kv pull"):
        LlamaDeployment(params=object(), kv_pull_deadline_s=0)
