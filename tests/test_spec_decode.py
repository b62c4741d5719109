"""Speculative decoding tests (serve/spec_decode.py + the engine's
spec lane).

The load-bearing property is EXACT greedy parity: at temperature 0
the spec engine's output must be token-identical to non-speculative
decode — drafts only decide how many argmaxes one dispatch keeps,
never what they are. Proposer quality is exercised through the
``spec_proposer`` seam: an oracle (always right) pins the accept
path, an anti-oracle (always wrong) pins rollback-then-continue, and
the real n-gram proposer runs over repetitive and random prompts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import Llama, generate, llama_tiny
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.scheduler import SlotView, SpecGrant, plan_step
from ray_tpu.serve.spec_decode import NGramIndex


@pytest.fixture(scope="module")
def tiny_model():
    # fp32 so paged vs contiguous decode agree bit-for-bit (bf16
    # rounding could flip greedy argmax on ties).
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _reference_completion(model, params, prompt, n):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _run(eng, prompts, n):
    hs = [eng.submit(p, max_new_tokens=n) for p in prompts]
    while eng.step():
        pass
    return [h.result() for h in hs]


REP_PROMPT = ([7, 8, 9, 10] * 6)[:20]


# ------------------------------------------------------ n-gram proposer


def test_ngram_proposes_continuation_of_previous_occurrence():
    idx = NGramIndex(2)
    idx.sync([1, 2, 3, 1, 2])
    # tail gram (1, 2) last occurred at the start; what followed it
    # is the draft
    assert idx.propose(3) == [3, 1, 2]
    assert idx.propose(1) == [3]


def test_ngram_no_match_and_short_context():
    idx = NGramIndex(3)
    idx.sync([1, 2])
    assert idx.propose(4) == []        # shorter than the gram
    idx.sync([1, 2, 3, 4])
    assert idx.propose(4) == []        # tail gram never seen before
    assert idx.propose(0) == []


def test_ngram_incremental_sync_matches_one_shot():
    ctx = [5, 6, 5, 6, 5, 6, 7]
    a, b = NGramIndex(2), NGramIndex(2)
    a.sync(ctx)
    b.sync(ctx[:3])
    b.sync(ctx)                        # only the tail is consumed
    assert a.propose(4) == b.propose(4)
    with pytest.raises(ValueError):
        b.sync(ctx[:2])                # context can never shrink


def test_ngram_validates_order():
    with pytest.raises(ValueError):
        NGramIndex(0)


# ------------------------------------------------------ planner spec lane


_PLAN = dict(total_slots=4, prefill_chunk=16, decode_chunk=4,
             max_run_ahead=128, prefill_batch=4, eos_bounded=False,
             spec_enabled=True)


def test_spec_lane_replaces_decode_and_covers_all_seeded():
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=50, seeded=True, spec_drafts=3),
             SlotView(sid=1, admit_seq=1, prompt_remaining=0,
                      owed=50, seeded=True, spec_drafts=0)]
    plan = plan_step(views, **dict(_PLAN, total_slots=2))
    assert plan.decode_steps == 0      # lanes are exclusive per round
    # zero-draft slots still ride the batched verify (plain one-token
    # rows), so speculation never forks the device schedule
    assert plan.spec == (SpecGrant(0, 3), SpecGrant(1, 0))


def test_spec_lane_degrades_to_quick_decode_without_proposals():
    views = [SlotView(sid=i, admit_seq=i, prompt_remaining=0,
                      owed=50, seeded=True, spec_drafts=0)
             for i in range(2)]
    plan = plan_step(views, **dict(_PLAN, total_slots=2))
    assert plan.spec == ()
    # quick cadence, NOT run-ahead: running ahead would decode past
    # every future proposal window before the host proposes again
    assert plan.decode_steps == 4


def test_spec_lane_clamps_drafts_to_owed_and_run_ahead():
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=2, seeded=True, spec_drafts=8),
             SlotView(sid=1, admit_seq=1, prompt_remaining=0,
                      owed=50, seeded=True, spec_drafts=8)]
    plan = plan_step(views, **dict(_PLAN, total_slots=2,
                                   max_run_ahead=4))
    # a verify emits drafts+1 tokens: clamp to owed-1 and to
    # max_run_ahead-1 so one dispatch never overshoots either bound
    assert plan.spec == (SpecGrant(0, 1), SpecGrant(1, 3))


def test_spec_lane_never_starves_prefill():
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=40,
                      owed=0, seeded=False),
             SlotView(sid=1, admit_seq=1, prompt_remaining=0,
                      owed=50, seeded=True, spec_drafts=4)]
    plan = plan_step(views, **_PLAN)
    assert plan.prefill and plan.prefill[0].sid == 0
    assert plan.spec == (SpecGrant(1, 4),)


def test_spec_disabled_ignores_drafts():
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=50, seeded=True, spec_drafts=4)]
    plan = plan_step(views, **dict(_PLAN, spec_enabled=False))
    assert plan.spec == ()
    assert plan.decode_steps > 0


# ------------------------------------------------------ engine parity


def test_spec_parity_repetitive_and_random_prompts(tiny_model):
    """The acceptance-criteria test: temperature-0 output with
    speculation on is token-identical to speculation off, across
    repetitive (spec-friendly) and random (spec-hostile) prompts."""
    model, params = tiny_model
    rng = np.random.default_rng(0)
    prompts = ([list(REP_PROMPT) for _ in range(2)]
               + [rng.integers(1, 255, size=14).tolist()
                  for _ in range(2)])
    base = _run(LLMEngine(model, params, max_slots=4, page_size=8,
                          n_pages=64, chunk=4), prompts, 24)
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=4, spec_len=4, spec_ngram=2)
    spec = _run(eng, prompts, 24)
    assert spec == base
    st = eng.spec_stats()
    assert st["rounds"] > 0            # the spec lane actually ran
    assert (st["accepted_tokens"] + st["rejected_tokens"]
            == st["proposed_tokens"])
    # every emitted token is accounted: spec emissions + decode-lane
    # emissions + prefill firsts cover all requests
    markers = [t for t in eng.sched_trace if t[0] == "spec"]
    assert markers, "no ('spec', ...) trace markers"
    for _tag, sid, proposed, accepted in markers:
        assert 0 <= accepted <= proposed <= 4
        assert 0 <= sid < 4


class _Scripted:
    """Proposer seam: proposes a fixed continuation script keyed on
    how many tokens the slot has generated (context beyond the
    prompt). An oracle scripts the true reference completion; an
    anti-oracle scripts guaranteed-wrong tokens."""

    def __init__(self, prompt_len, script):
        self.prompt_len = prompt_len
        self.script = script
        self._done = 0

    def sync(self, context):
        self._done = len(context) - self.prompt_len

    def propose(self, k):
        return self.script[self._done:self._done + k]


def test_spec_oracle_proposer_accepts_everything(tiny_model):
    model, params = tiny_model
    prompt = [5, 9, 2, 7, 11]
    ref = _reference_completion(model, params, prompt, 16)
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, spec_len=4,
                    spec_proposer=lambda: _Scripted(len(prompt), ref))
    out = _run(eng, [prompt], 16)
    assert out == [ref]
    st = eng.spec_stats()
    assert st["accept_rate"] == 1.0
    assert st["tokens_per_dispatch"] > 1.0
    # trace shows multi-token verifies, all fully accepted
    for _tag, _sid, proposed, accepted in (
            t for t in eng.sched_trace if t[0] == "spec"):
        assert accepted == proposed


def test_spec_full_rejection_rolls_back_then_continues(tiny_model):
    """Anti-oracle: every draft is guaranteed wrong, so every verify
    rejects everything, clamps the KV frontier back, and emits only
    the correction token — output must still be exact."""
    model, params = tiny_model
    prompt = [5, 9, 2, 7, 11]
    ref = _reference_completion(model, params, prompt, 16)
    wrong = [(t + 1) % 256 for t in ref]
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, spec_len=4,
                    spec_proposer=lambda: _Scripted(len(prompt),
                                                    wrong))
    out = _run(eng, [prompt], 16)
    assert out == [ref]
    st = eng.spec_stats()
    assert st["proposed_tokens"] > 0
    assert st["accept_rate"] == 0.0
    # full rejection degrades to exactly one (correction) token per
    # rider per dispatch — never zero, never stuck
    assert st["tokens_per_dispatch"] == 1.0


def test_spec_with_prefix_cache_parity_and_cow(tiny_model):
    """Spec verifies write at the slot's frontier, which sits past
    any cache-shared pages — parity must hold through a cache-hit
    admission and the radix tree must stay sound (a COW violation
    raises inside the dispatch)."""
    model, params = tiny_model
    prefix = list(REP_PROMPT)
    prompts = [prefix + [3, 1], prefix + [4, 2]]
    base = _run(LLMEngine(model, params, max_slots=2, page_size=8,
                          n_pages=32, chunk=4), prompts, 16)
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, prefix_cache=True,
                    spec_len=4, spec_ngram=2)
    # sequential so the second admission hits the first's inserted
    # prefix pages
    out0 = _run(eng, [prompts[0]], 16)
    out1 = _run(eng, [prompts[1]], 16)
    assert out0 + out1 == base
    assert eng.prefix_cache.stats()["hit_tokens"] > 0
    eng.prefix_cache.check_invariants()
    assert eng.spec_stats()["rounds"] > 0


def test_spec_preemption_mid_speculation(tiny_model):
    """A page pool too small for both requests forces preemption
    while speculation is active; recompute must land on the exact
    greedy stream (the victim's proposer dies with its slot)."""
    model, params = tiny_model
    # each request needs ceil((4+28)/8)=4 pages; pool has 6 usable ->
    # both admit early but cannot both finish (the shape
    # test_preemption_under_memory_pressure pins, now with spec on)
    prompts = [[1, 2, 1, 2], [9, 8, 9, 8]]
    want = [_reference_completion(model, params, p, 28)
            for p in prompts]
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=7, chunk=4, spec_len=4, spec_ngram=2)
    out = _run(eng, prompts, 28)
    assert out == want
    assert eng.stats["preemptions"] > 0
    assert eng.spec_stats()["rounds"] > 0
    assert eng.alloc.n_free == eng.alloc.n_pages - 1


def test_spec_eos_truncation_parity(tiny_model):
    """With an eos id, a verify that emits past the eos must truncate
    exactly where plain decode does."""
    model, params = tiny_model
    prompt = list(REP_PROMPT)
    ref = _reference_completion(model, params, prompt, 24)
    eos = ref[len(ref) // 2]           # an id that actually occurs
    base = _run(LLMEngine(model, params, max_slots=2, page_size=8,
                          n_pages=32, chunk=4, eos_id=eos),
                [prompt], 24)
    spec = _run(LLMEngine(model, params, max_slots=2, page_size=8,
                          n_pages=32, chunk=4, eos_id=eos,
                          spec_len=4, spec_ngram=2), [prompt], 24)
    assert spec == base
    assert base[0][-1] == eos


def test_spec_disabled_under_sampling(tiny_model):
    """Verification accepts against the argmax, so with sampling it
    would skew the output distribution: spec silently disables."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, temperature=0.8, spec_len=4)
    assert eng.spec_len == 0
    assert eng.spec_stats() is None
    _run(eng, [[5, 9, 2]], 8)          # still serves, just no spec
    assert not [t for t in eng.sched_trace if t[0] == "spec"]


def test_spec_cancel_mid_speculation(tiny_model):
    """Cancelling a slot while the spec lane is active: its freed
    pages must never be touched by the in-flight verify's rollback
    (stream ordering — the same argument as retire-at-dispatch), the
    surviving slot stays token-identical to greedy decode, and the
    allocator returns to baseline."""
    from ray_tpu.serve.errors import RequestCancelled
    from ray_tpu.serve.faults import check_quiesced
    model, params = tiny_model
    p1 = list(REP_PROMPT)
    p2 = list(REP_PROMPT[2:])
    want1 = _reference_completion(model, params, p1, 24)
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, spec_len=4, spec_ngram=2)
    h1 = eng.submit(p1, max_new_tokens=24)      # slot 0: survivor
    h2 = eng.submit(p2, max_new_tokens=24)      # slot 1: cancelled
    # step until speculation has actually dispatched and the victim
    # is mid-flight (slot live, verify rounds running)
    for _ in range(64):
        eng.step()
        if ([t for t in eng.sched_trace if t[0] == "spec"]
                and eng.slots[1] is not None
                and eng.slots[1].req is h2._req):
            break
    else:
        raise AssertionError("spec lane never engaged")
    assert h2.cancel() is True
    assert eng.slots[1] is None                 # slot + pages freed NOW
    while eng.step():
        pass
    assert h1.result() == want1
    with pytest.raises(RequestCancelled):
        h2.result()
    assert len(h2._req.generated) < 24
    assert eng.stats["cancelled"] == 1
    assert eng.spec_stats()["rounds"] > 0
    check_quiesced(eng)


def test_spec_off_by_default_and_validates(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4)
    assert eng.spec_stats() is None
    with pytest.raises(ValueError):
        LLMEngine(model, params, spec_len=-1)
    with pytest.raises(ValueError):
        LLMEngine(model, params, spec_len=2, spec_ngram=0)
