"""Overlapped hot-loop tests (the engine's double-buffered
plan/dispatch pipeline, serve/engine.py ``overlap=``).

The load-bearing property is EXACT greedy parity: the overlapped loop
plans round N+1 from the STALE token frontier (dispatched-but-
undrained steps) while round N executes, so every correctness path
that reads tokens — eos detection, speculation, prefix-cache resume,
cancellation, fault containment — is re-proven token-identical
against the lockstep loop (``overlap=False``: full readback drain
before every plan, the pre-overlap behavior). Plus the pipeline
mechanics themselves: the stale-cap discard bound in the planner, the
depth-2 in-flight fence, the heartbeat contract of the blocking
drain, and the per-round host-gap accounting the ``--overlap-ab``
bench artifact is built from.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import Llama, generate, llama_tiny
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.scheduler import SlotView, plan_step


@pytest.fixture(scope="module")
def tiny_model():
    # fp32 so both arms agree bit-for-bit (bf16 rounding could flip
    # greedy argmax on ties and fake a pipeline bug).
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


@pytest.fixture(autouse=True)
def _no_page_leaks(monkeypatch):
    """Same invariant net as test_llm_engine.py: every engine built
    in this file must end with its allocator back at baseline —
    an overlapped round that loses track of an undrained rider's
    pages shows up here, with the leaked ids named."""
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
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _run(eng, prompts, n):
    hs = [eng.submit(p, max_new_tokens=n) for p in prompts]
    while eng.step():
        pass
    return [h.result() for h in hs]


def _both_arms(tiny_model, prompts, n, **kw):
    """The file's workhorse: the identical engine + load under
    overlap=False and overlap=True; returns (lockstep, overlapped)
    outputs for the caller's parity assert."""
    model, params = tiny_model
    outs = []
    for overlap in (False, True):
        eng = LLMEngine(model, params, overlap=overlap, **kw)
        outs.append(_run(eng, [list(p) for p in prompts], n))
    return outs


REP_PROMPT = ([7, 8, 9, 10] * 6)[:20]


# ------------------------------------------------------- knob resolution


def test_overlap_default_on_and_kwarg(tiny_model):
    model, params = tiny_model
    assert LLMEngine(model, params, max_slots=1, page_size=8,
                     n_pages=16).overlap is True
    assert LLMEngine(model, params, max_slots=1, page_size=8,
                     n_pages=16, overlap=False).overlap is False


# ----------------------------------------------------- planner stale cap


_PLAN = dict(total_slots=2, prefill_chunk=16, decode_chunk=4,
             max_run_ahead=128, prefill_batch=4, eos_bounded=True)


def test_stale_rider_caps_eos_dispatch_at_one_chunk():
    """The discard bound: an eos-bounded rider with undrained steps
    may already be past its eos — the next dispatch shrinks from the
    usual 2*decode_chunk run-ahead to ONE decode_chunk."""
    fresh = [SlotView(sid=i, admit_seq=i, prompt_remaining=0,
                      owed=50, seeded=True) for i in range(2)]
    assert plan_step(fresh, **_PLAN).decode_steps == 8
    stale = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=50, seeded=True, stale=4),
             SlotView(sid=1, admit_seq=1, prompt_remaining=0,
                      owed=50, seeded=True)]
    assert plan_step(stale, **_PLAN).decode_steps == 4


def test_stale_cap_only_binds_eos_bounded_plans():
    """Without an eos there is nothing to discard — staleness must
    not cost deferred-mode run-ahead."""
    views = [SlotView(sid=i, admit_seq=i, prompt_remaining=0,
                      owed=24, seeded=True, stale=4)
             for i in range(2)]
    plan = plan_step(views, **dict(_PLAN, eos_bounded=False))
    assert plan.decode_steps == 24


# --------------------------------------------------------- token parity


def test_plain_eos_parity(tiny_model):
    """Late-revealed eos: the overlapped loop learns about the eos
    one round late, discards the overshoot, and must still emit the
    exact lockstep truncation."""
    model, params = tiny_model
    prompt = [5, 9, 2]
    ref = _reference_completion(model, params, prompt, 16)
    eos = ref[3]                   # a token that actually samples
    lock, over = _both_arms(tiny_model, [prompt], 16, max_slots=2,
                            page_size=8, n_pages=32, chunk=4,
                            eos_id=eos)
    assert over == lock == [ref[:ref.index(eos) + 1]]


def test_multi_slot_eos_bounded_parity(tiny_model):
    """eos configured but never sampled (eos_id=-1): every slot runs
    to budget through the stale-frontier scheduler; full-length
    streams must match the lockstep arm exactly."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 255, size=9 + i).tolist()
               for i in range(4)]
    lock, over = _both_arms(tiny_model, prompts, 20, max_slots=2,
                            page_size=8, n_pages=64, chunk=4,
                            eos_id=-1)
    assert over == lock


def test_deferred_mode_parity(tiny_model):
    """No eos at all (deferred emission): overlap unifies with the
    old opportunistic path and must change nothing."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 255, size=12).tolist()
               for _ in range(3)]
    lock, over = _both_arms(tiny_model, prompts, 16, max_slots=2,
                            page_size=8, n_pages=64, chunk=4)
    assert over == lock


def test_spec_oracle_parity(tiny_model):
    """Speculation from a stale frontier, accept path: drafts from
    the n-gram proposer over a repetitive prompt fire and verify —
    outputs token-identical across modes, spec lane engaged in both.
    """
    model, params = tiny_model
    prompt = list(REP_PROMPT)
    outs, engines = [], []
    for overlap in (False, True):
        eng = LLMEngine(model, params, max_slots=2, page_size=8,
                        n_pages=64, chunk=4, spec_len=4,
                        spec_ngram=2, eos_id=-1, overlap=overlap)
        outs.append(_run(eng, [prompt, list(REP_PROMPT[2:])], 24))
        engines.append(eng)
    assert outs[0] == outs[1]
    for eng in engines:
        st = eng.spec_stats()
        assert st["rounds"] > 0 and st["accepted_tokens"] > 0


def test_spec_anti_oracle_full_rejection_parity(tiny_model):
    """Stale-frontier drafts are only hints: a proposer that is
    ALWAYS wrong forces every verify to reject everything and roll
    back the KV frontier — under the overlapped loop the rollback
    machinery and the stale planner compose, and the output is still
    the exact greedy stream."""
    model, params = tiny_model
    prompt = [5, 9, 2, 7, 11]
    ref = _reference_completion(model, params, prompt, 16)
    wrong = [(t + 1) % 256 for t in ref]

    class _Anti:
        def __init__(self):
            self._done = 0

        def sync(self, context):
            self._done = len(context) - len(prompt)

        def propose(self, k):
            return wrong[self._done:self._done + k]

    outs = []
    for overlap in (False, True):
        eng = LLMEngine(model, params, max_slots=2, page_size=8,
                        n_pages=32, chunk=4, spec_len=4,
                        spec_proposer=_Anti, eos_id=-1,
                        overlap=overlap)
        outs.append(_run(eng, [prompt], 16))
        st = eng.spec_stats()
        assert st["proposed_tokens"] > 0 and st["accept_rate"] == 0.0
    assert outs[0] == outs[1] == [ref]


def test_prefix_cache_hit_resume_parity(tiny_model):
    """A cache-hit admission enters mid-prompt; under overlap its
    first decode rides behind undrained neighbors. Sequential runs so
    the second request HITS the pages the first inserted."""
    model, params = tiny_model
    prefix = list(REP_PROMPT)
    prompts = [prefix + [3, 1], prefix + [4, 2]]
    outs = []
    for overlap in (False, True):
        eng = LLMEngine(model, params, max_slots=2, page_size=8,
                        n_pages=32, chunk=4, prefix_cache=True,
                        eos_id=-1, overlap=overlap)
        got = _run(eng, [prompts[0]], 16) + _run(eng, [prompts[1]], 16)
        assert eng.prefix_cache.stats()["hit_tokens"] > 0
        eng.prefix_cache.check_invariants()
        outs.append(got)
    assert outs[0] == outs[1]


def test_cancel_mid_round_overlap(tiny_model):
    """Cancel while the pipeline holds undrained dispatches: the
    victim's slot frees NOW, late readbacks carrying the dead rider
    are discarded (req.closed guard), the survivor stays exact, and
    the engine quiesces leak-free."""
    from ray_tpu.serve import engine as engine_mod
    from ray_tpu.serve.errors import RequestCancelled
    from ray_tpu.serve.faults import check_quiesced
    model, params = tiny_model
    p1, p2 = [3, 1, 4, 1, 5], [2, 7, 1, 8]
    want1 = _reference_completion(model, params, p1, 24)
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, eos_id=-1, overlap=True)
    h1 = eng.submit(p1, max_new_tokens=24)
    h2 = eng.submit(p2, max_new_tokens=24)
    # a CPU "device" finishes each dispatch before the next step, so
    # the opportunistic drains would empty the pipeline every round;
    # report every buffer still-computing to hold the cancel window
    # open the way a real accelerator does
    real_ready = engine_mod._dev_ready
    engine_mod._dev_ready = lambda buf: False
    try:
        # step until the victim is live and the pipeline actually
        # holds an undrained dispatch (the overlapped-loop-specific
        # window)
        for _ in range(64):
            eng.step()
            if (eng.slots[1] is not None
                    and eng.slots[1].req is h2._req and eng._fetchq):
                break
        else:
            raise AssertionError("pipeline never held in-flight work")
        assert h2.cancel() is True
    finally:
        engine_mod._dev_ready = real_ready
    assert eng.slots[1] is None          # slot + pages freed NOW
    while eng.step():
        pass
    assert h1.result() == want1
    with pytest.raises(RequestCancelled):
        h2.result()
    assert eng.stats["cancelled"] == 1
    check_quiesced(eng)


def test_contained_fault_requeue_parity(tiny_model):
    """Fault containment under overlap: a decode dispatch fault fails
    ONLY the culprit; the innocent co-rider requeues (its stale
    pipeline state discarded with the fault) and re-decodes to the
    exact greedy stream."""
    from ray_tpu.serve.faults import FaultInjector, check_quiesced
    model, params = tiny_model
    inj = FaultInjector()
    inj.inject("dispatch_decode", sid=1, round=3)
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, eos_id=-1, overlap=True,
                    fault_injector=inj, retry_backoff_s=0.005)
    p1, p2 = [3, 1, 4, 1, 5], [2, 7, 1, 8]
    want1 = _reference_completion(model, params, p1, 16)
    h1 = eng.submit(p1, max_new_tokens=16)   # slot 0: innocent
    h2 = eng.submit(p2, max_new_tokens=16)   # slot 1: culprit
    while eng.step():
        pass
    with pytest.raises(RuntimeError, match="injected fault"):
        h2.result()
    assert h1.result() == want1
    assert eng.stats["contained_faults"] == 1
    assert eng.stats["fault_failed"] == 1
    assert eng.stats["failed_all"] == 0
    check_quiesced(eng)


# --------------------------------------------------- pipeline mechanics


def test_fetchq_depth_never_exceeds_two(tiny_model):
    """The trailing drain (limit=1, keep=1) is the discard bound's
    other half: after every step the pipeline holds at most two
    undrained dispatches."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, eos_id=-1, overlap=True)
    hs = [eng.submit([5, 9, 2, 7], max_new_tokens=32),
          eng.submit([1, 8, 3], max_new_tokens=32)]
    while eng.step():
        assert len(eng._fetchq) <= 2
    assert all(len(h.result()) == 32 for h in hs)


def test_heartbeat_touched_before_blocking_readback(tiny_model):
    """The watchdog contract: the blocking drain must refresh the
    heartbeat BEFORE each device_get, so a slow-but-progressing
    multi-buffer readback never reads as one long stall."""
    from ray_tpu.serve import engine as engine_mod
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, eos_id=-1, overlap=True)
    h = eng.submit([5, 9, 2, 7], max_new_tokens=16)
    # hold undrained work in the pipeline (a warm CPU jit finishes
    # each dispatch before the next step, emptying the queue)
    real_ready = engine_mod._dev_ready
    engine_mod._dev_ready = lambda buf: False
    seen = []
    real_get = jax.device_get

    def spy(x):
        seen.append(eng._hb)
        return real_get(x)

    try:
        for _ in range(8):
            eng.step()
            if eng._fetchq:
                break
        else:
            raise AssertionError("pipeline never held in-flight work")
        eng._hb = time.monotonic() - 1000.0  # pretend: ancient
        jax.device_get = spy
        with eng._lock:
            eng._drain_fetches_locked()      # full blocking drain
    finally:
        jax.device_get = real_get
        engine_mod._dev_ready = real_ready
    assert seen, "drain performed no readback"
    now = time.monotonic()
    assert all(now - hb < 10.0 for hb in seen), (
        "device_get saw a stale heartbeat — a slow readback would "
        "ride the watchdog ladder to SUSPECT/WEDGED")
    assert now - eng._hb < 10.0              # touched after, too
    while eng.step():
        pass
    assert len(h.result()) == 16


def test_round_events_and_histogram_crosscheck(tiny_model):
    """The obs satellite: every round appends a typed "round" event
    whose host_gap_s sums to what the serve_phase_host_gap_s
    histogram accumulated — the bench artifact and trace report
    derive from the events, the dashboard from the histogram, and
    they must tell the same story."""
    from ray_tpu.serve import obs
    from ray_tpu.util import metrics
    model, params = tiny_model
    metrics.clear_registry()
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, eos_id=-1, overlap=True)
    _run(eng, [[5, 9, 2, 7], [1, 8, 3]], 16)
    rounds = [e for e in eng.events.snapshot() if e[2] == "round"]
    assert rounds, "no round events recorded"
    for e in rounds:
        d = e[5]
        assert d["overlap"] is True
        assert 0.0 <= d["host_gap_s"] <= d["wall_s"]
    gap_total = sum(e[5]["host_gap_s"] for e in rounds)
    hist = metrics.registry()[obs.HOST_GAP]
    samples = hist._samples()
    assert len(samples) == 1
    _tags, s = samples[0]
    assert s["count"] == len(rounds)
    # events round to 6dp; the histogram holds raw observations
    assert abs(s["sum"] - gap_total) < 1e-4


def test_load_report_exposes_pipeline_state(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, eos_id=-1, overlap=True)
    rep = eng.load_report()
    assert rep["overlap"] is True
    assert rep["fetchq_depth"] == 0
    assert rep["pending_prefills"] == 0
    h = eng.submit([5, 9, 2], max_new_tokens=8)
    for _ in range(4):
        eng.step()
    rep = eng.load_report()
    assert isinstance(rep["fetchq_depth"], int)
    assert 0 <= rep["fetchq_depth"] <= 2
    while eng.step():
        pass
    assert len(h.result()) == 8
    # drained and idle: nothing in flight may linger
    rep = eng.load_report()
    assert rep["fetchq_depth"] == 0 and rep["pending_prefills"] == 0


def test_drain_then_is_idle_accounts_inflight_work(tiny_model):
    """is_idle must stay False while undrained dispatches hold
    emittable tokens — a pool drain that trusts it would otherwise
    drop tail tokens on shutdown."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=4, eos_id=-1, overlap=True)
    h = eng.submit([5, 9, 2], max_new_tokens=12)
    for _ in range(3):
        eng.step()
    if eng._fetchq:
        assert not eng.is_idle()
    while eng.step():
        pass
    assert eng.is_idle()
    assert len(h.result()) == 12
