"""Continuous-batching engine + paged KV cache tests.

Strategy mirrors the reference's serve batching tests
(python/ray/serve/tests/test_batching.py): correctness of batched
results vs unbatched, join/leave under staggered arrival, and
resource-pressure behavior — here preemption instead of queue
backpressure, since the engine schedules at token granularity.
"""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.kv_cache import BlockAllocator
from ray_tpu.models.llama import Llama, generate, llama_tiny
from ray_tpu.serve.engine import LLMEngine, RequestError
from ray_tpu.serve.scheduler import (BACKLOG_DECODE_STEPS, PrefillGrant,
                                     SlotView, plan_step, role_plan_caps)


@pytest.fixture(scope="module")
def tiny_model():
    # fp32 params/activations so paged vs contiguous decode agree
    # bit-for-bit (bf16 rounding could flip greedy argmax on ties).
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    import jax
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


@pytest.fixture(autouse=True)
def _no_page_leaks(monkeypatch):
    """Invariant net under EVERY scenario in this file: once a test
    ends, each engine it built must have its allocator back at
    baseline — occupied pages exactly the prefix-cache residents
    (zero without a cache). A cancelled/failed/preempted path that
    drops a page shows up here, with the leaked ids named."""
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


# ---------------------------------------------------------------- allocator


def test_allocator_basics():
    a = BlockAllocator(8)          # 7 usable, page 0 reserved
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert a.n_free == 4
    assert a.alloc(5) is None      # all-or-nothing
    assert a.n_free == 4
    a.free(got)
    assert a.n_free == 7
    with pytest.raises(ValueError):
        a.free(got)                # double free detected
    with pytest.raises(ValueError):
        a.free([0])                # null page is never freeable


def test_allocator_free_validation_is_atomic():
    a = BlockAllocator(8)
    got = a.alloc(4)
    with pytest.raises(ValueError):
        a.free([got[0], got[0]])   # same page twice in one call
    with pytest.raises(ValueError):
        a.free([got[1], 99])       # out-of-range id
    with pytest.raises(ValueError):
        a.free([got[2], 2.5])      # non-int id
    # nothing was accepted from the rejected calls: freeing the batch
    # cleanly still works (no partial state)
    assert a.n_free == 3
    a.free(got)
    assert a.n_free == 7
    with pytest.raises(ValueError):
        a.alloc(-1)


# ------------------------------------------------------------------ parity


def test_paged_decode_matches_generate(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4)
    prompt = [5, 9, 2, 7, 11]
    want = _reference_completion(model, params, prompt, 12)
    h = eng.submit(prompt, max_new_tokens=12)
    while eng.step():
        pass
    assert h.result() == want


def test_parity_across_prompt_lengths(tiny_model):
    """Prompt lengths off and on page boundaries, decoded together."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=4)
    prompts = [[3], [1, 2, 3, 4, 5, 6, 7, 8],      # exactly one page
               [4, 4, 4, 4, 4, 4, 4, 4, 4],        # one page + 1
               list(range(1, 14))]
    want = [_reference_completion(model, params, p, 9)
            for p in prompts]
    hs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    while eng.step():
        pass
    assert [h.result() for h in hs] == want


# ------------------------------------------------- continuous batching


def test_join_leave_mid_decode(tiny_model):
    """A request arriving mid-decode joins the running batch (admitted
    into a free slot at a chunk boundary) and both finish correctly —
    the capability decode-to-completion batching lacks."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=2)
    p1, p2 = [5, 6, 7], [9, 8, 7, 6]
    want1 = _reference_completion(model, params, p1, 16)
    want2 = _reference_completion(model, params, p2, 8)
    h1 = eng.submit(p1, max_new_tokens=16)
    for _ in range(3):             # decode a few chunks solo
        eng.step()
    h2 = eng.submit(p2, max_new_tokens=8)   # joins mid-flight
    while eng.step():
        pass
    assert h1.result() == want1
    assert h2.result() == want2
    assert eng.stats["admitted"] == 2
    # 2nd request admitted while 1st was still decoding
    assert eng.stats["completed"] == 2


def test_slot_reuse_after_completion(tiny_model):
    """More requests than slots: finished requests free their slot and
    pages for waiting ones."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4)
    prompts = [[i + 1, i + 2] for i in range(6)]
    want = [_reference_completion(model, params, p, 6)
            for p in prompts]
    hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    while eng.step():
        pass
    assert [h.result() for h in hs] == want
    assert eng.alloc.n_free == eng.alloc.n_pages - 1   # all pages back


def test_eos_frees_slot_early(tiny_model):
    model, params = tiny_model
    prompt = [5, 9, 2]
    ref = _reference_completion(model, params, prompt, 16)
    eos = ref[3]                   # force an early stop on a real token
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, eos_id=eos)
    h = eng.submit(prompt, max_new_tokens=16)
    while eng.step():
        pass
    got = h.result()
    assert got == ref[:ref.index(eos) + 1]   # truncated at first eos
    assert eng.alloc.n_free == eng.alloc.n_pages - 1


# ---------------------------------------------------------- preemption


def test_preemption_under_memory_pressure(tiny_model):
    """Pool too small for both requests at full length: the younger
    slot is evicted (pages freed, request requeued) and recomputed
    after the elder completes — both streams still correct."""
    model, params = tiny_model
    # each request needs ceil((4+28)/8)=4 pages; pool has 6 usable ->
    # both admit early (1-2 pages each) but cannot both finish.
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=7, chunk=4)
    p1, p2 = [1, 2, 3, 4], [9, 8, 7, 6]
    want1 = _reference_completion(model, params, p1, 28)
    want2 = _reference_completion(model, params, p2, 28)
    h1 = eng.submit(p1, max_new_tokens=28)
    h2 = eng.submit(p2, max_new_tokens=28)
    while eng.step():
        pass
    assert h1.result() == want1
    assert h2.result() == want2
    assert eng.stats["preemptions"] >= 1
    assert eng.alloc.n_free == eng.alloc.n_pages - 1


def test_oversized_request_rejected(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=4, chunk=2)
    with pytest.raises(RequestError):
        eng.submit([1] * 20, max_new_tokens=20)   # needs 5 > 3 pages
    with pytest.raises(RequestError):
        eng.submit([], max_new_tokens=4)
    with pytest.raises(RequestError):
        eng.submit([1], max_new_tokens=0)


# ----------------------------------------------------------- threaded


def test_background_thread_streaming(tiny_model):
    """start() mode: concurrent submitters stream tokens while the
    engine thread schedules continuously."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2).start()
    prompts = [[i + 2, i + 5] for i in range(8)]
    want = [_reference_completion(model, params, p, 8)
            for p in prompts]
    results = [None] * len(prompts)

    def run(i):
        results[i] = list(eng.submit(prompts[i],
                                     max_new_tokens=8).stream())

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    eng.shutdown()
    assert results == want


def test_mixtral_through_engine():
    """MoE family shares LlamaAttention, so paged decode must work
    unchanged."""
    import jax
    from ray_tpu.models.mixtral import Mixtral, mixtral_tiny
    cfg = mixtral_tiny(dtype=jnp.float32)
    model = Mixtral(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    prompt = [3, 1, 4, 1, 5]
    want = _reference_completion(model, params, prompt, 8)
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4)
    h = eng.submit(prompt, max_new_tokens=8)
    while eng.step():
        pass
    assert h.result() == want


def test_run_ahead_dispatch_coalescing(tiny_model):
    """Device-paced scheduling: with a full batch and no eos, the
    engine runs ahead to the next completion event instead of syncing
    every `chunk` steps — the whole generation should take a handful
    of dispatches, not max_new/chunk of them."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4)
    p1, p2 = [3, 1, 4, 1, 5], [2, 7, 1, 8]
    h1 = eng.submit(p1, max_new_tokens=24)
    h2 = eng.submit(p2, max_new_tokens=24)
    while eng.step():
        pass
    assert h1.result() == _reference_completion(model, params, p1, 24)
    assert h2.result() == _reference_completion(model, params, p2, 24)
    # 2 slots x 24 tokens with aligned budgets: one quick chunk while
    # admission fills, then run-ahead to the completion boundary.
    # Chunked pacing would need ~6 dispatches per request stream.
    assert eng.stats["chunks"] <= 4, dict(eng.stats)
    assert eng.stats["decode_steps"] >= 23


def test_shutdown_delivers_trailing_readbacks(tiny_model):
    """No-eos mode retires slots at dispatch time while their tokens
    are still in flight; shutdown must deliver every computed token
    before the scheduler exits, or clients hang on result()."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4).start()
    p = [11, 3, 5]
    want = _reference_completion(model, params, p, 12)
    h = eng.submit(p, max_new_tokens=12)
    got = h.result()
    eng.shutdown()
    assert got == want


def test_mixed_budgets_retire_independently(tiny_model):
    """A short and a long request share the batch; the short one's
    slot retires by arithmetic mid-run and is reusable while the long
    one keeps decoding — both streams exact."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4)
    p1, p2, p3 = [5, 1], [7, 2, 9], [4, 4, 8]
    want1 = _reference_completion(model, params, p1, 4)
    want2 = _reference_completion(model, params, p2, 30)
    want3 = _reference_completion(model, params, p3, 6)
    h1 = eng.submit(p1, max_new_tokens=4)
    h2 = eng.submit(p2, max_new_tokens=30)
    h3 = eng.submit(p3, max_new_tokens=6)   # reuses p1's retired slot
    while eng.step():
        pass
    assert h1.result() == want1
    assert h2.result() == want2
    assert h3.result() == want3


# ----------------------------------------------------- chunked prefill


def test_prompt_shorter_than_chunk(tiny_model):
    """A prompt under prefill_chunk finishes in ONE chunk: admitted,
    prefilled, and seeded in a single round, with TTFT stamped at the
    first emission."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, prefill_chunk=16)
    prompt = [5, 9, 2, 7, 11]
    want = _reference_completion(model, params, prompt, 10)
    h = eng.submit(prompt, max_new_tokens=10)
    eng.step()
    assert eng.stats["prefills"] == 1
    assert eng.stats["prefilled_seqs"] == 1
    while eng.step():
        pass
    assert h.result() == want
    assert h.ttft_s is not None and h.ttft_s > 0
    assert len(eng.ttfts_s) == 1


def test_prompt_spanning_many_chunks(tiny_model):
    """A prompt of 3+ chunks prefills over several rounds and still
    matches the dense reference exactly (append-at-offset + causal
    masking make chunk boundaries invisible)."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, prefill_chunk=8)
    prompt = list(range(1, 29))           # 28 tokens: chunks 8/8/8/4
    want = _reference_completion(model, params, prompt, 8)
    h = eng.submit(prompt, max_new_tokens=8)
    while eng.step():
        pass
    assert h.result() == want
    assert eng.stats["prefills"] >= 4     # one dispatch per chunk
    assert eng.stats["prefill_tokens"] == 28
    assert eng.alloc.n_free == eng.alloc.n_pages - 1


def test_slot_exhaustion_mid_prefill(tiny_model):
    """Every slot busy while a long prompt is mid-prefill: the extra
    request waits for a completion, then admits; all streams exact."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=2, prefill_chunk=8)
    pa, pb, pc = [1, 2], list(range(1, 25)), [7, 3]
    wa = _reference_completion(model, params, pa, 6)
    wb = _reference_completion(model, params, pb, 10)
    wc = _reference_completion(model, params, pc, 6)
    ha = eng.submit(pa, max_new_tokens=6)
    hb = eng.submit(pb, max_new_tokens=10)
    hc = eng.submit(pc, max_new_tokens=6)
    eng.step()
    # both slots taken (pa seeded-or-prefilling, pb mid-prefill);
    # pc has nowhere to go yet
    assert all(s is not None for s in eng.slots)
    assert len(eng._wait) == 1
    assert any(s is not None and s.prefill_remaining > 0
               for s in eng.slots)
    while eng.step():
        pass
    assert ha.result() == wa
    assert hb.result() == wb
    assert hc.result() == wc
    assert eng.alloc.n_free == eng.alloc.n_pages - 1


def test_preempt_partially_prefilled_recompute(tiny_model):
    """A request evicted MID-PREFILL requeues with its untouched
    prompt (nothing generated yet) and recomputes to the exact
    reference stream."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=2, prefill_chunk=8)
    prompt = list(range(1, 25))           # 24 tokens: 3 chunks
    want = _reference_completion(model, params, prompt, 6)
    h = eng.submit(prompt, max_new_tokens=6)
    eng.step()                            # admit + FIRST chunk only
    with eng._lock:
        (ix,) = [i for i, s in enumerate(eng.slots) if s is not None]
        slot = eng.slots[ix]
        assert 0 < slot.prefilled < len(prompt)
        eng._preempt_locked(ix)
        # recompute path: nothing was generated, so the requeued
        # prompt is the original, whole
        assert list(eng._wait)[0].recompute_prompt == prompt
    assert eng.stats["preemptions"] == 1
    while eng.step():
        pass
    assert h.result() == want
    assert h._req.preemptions == 1
    assert eng.alloc.n_free == eng.alloc.n_pages - 1


def test_decode_interleaved_between_prefill_chunks(tiny_model):
    """THE chunked-prefill property: while a long prompt prefills
    chunk by chunk, decode dispatches for the active stream land
    BETWEEN its chunks — the in-flight stream never stalls for the
    whole prompt. Asserted on the engine's dispatch-order trace."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=2, prefill_chunk=8)
    p1 = [1, 2]
    w1 = _reference_completion(model, params, p1, 40)
    h1 = eng.submit(p1, max_new_tokens=40)
    for _ in range(3):                    # h1 decoding solo
        eng.step()
    p2 = list(range(1, 33))               # 32 tokens: 4 chunks of 8
    w2 = _reference_completion(model, params, p2, 4)
    h2 = eng.submit(p2, max_new_tokens=4)
    while eng.step():
        pass
    assert h1.result() == w1
    assert h2.result() == w2
    trace = list(eng.sched_trace)
    pf = [i for i, (kind, _) in enumerate(trace) if kind == "prefill"]
    assert len(pf) >= 5                   # p1's one + p2's four
    # between every pair of consecutive prefill chunks there is at
    # least one decode dispatch
    for a, b in zip(pf, pf[1:]):
        assert any(trace[i][0] == "decode" for i in range(a + 1, b)), \
            trace[a:b + 1]


def _round_events(eng):
    return [e[5] for e in eng.events.snapshot() if e[2] == "round"]


def test_four_waiting_prompts_share_one_prefill_call(tiny_model):
    """Four one-chunk prompts waiting in slots: ONE prefill dispatch
    advances all four rows (the call computes four rows whatever they
    hold), the round reports rows, tokens and the real budget, and
    every request's tokens are ``generate``'s."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=4, prefill_chunk=16)
    prompts = [list(range(1 + i, 17 + i)) for i in range(4)]  # 16 each
    wants = [_reference_completion(model, params, p, 6)
             for p in prompts]
    hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.step()
    assert eng.stats["prefills"] == 1
    assert eng.stats["prefill_rows"] == 4
    assert eng.stats["prefill_tokens"] == 4 * 16
    assert eng.stats["prefilled_seqs"] == 4
    (first,) = _round_events(eng)
    assert first["prefill_rows"] == 4
    assert first["prefill_tokens"] == first["prefill_budget"] == 4 * 16
    while eng.step():
        pass
    assert [h.result() for h in hs] == wants
    assert eng.stats["prefills"] == 1      # nothing left to prefill
    later = _round_events(eng)[1:]
    assert later and all(r["prefill_rows"] == 0 for r in later)


def test_long_prompt_prefills_beside_the_short_ones(tiny_model):
    """A 3-chunk prompt admitted FIRST holds one row for three rounds;
    the two short prompts behind it are seeded by the first call, not
    after the long one finishes. Tokens stay exact for all three."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, prefill_chunk=8)
    prompts = [list(range(1, 25)), [7, 3, 9], [4, 4, 2, 11, 6]]
    wants = [_reference_completion(model, params, p, 8)
             for p in prompts]
    hs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    assert eng.stats["prefills"] == 1
    assert eng.stats["prefill_rows"] == 3
    assert eng.stats["prefill_tokens"] == 8 + 3 + 5
    assert eng.stats["prefilled_seqs"] == 2     # both short ones
    while eng.step():
        pass
    assert [h.result() for h in hs] == wants
    assert eng.stats["prefills"] == 3           # the long one's chunks
    assert eng.stats["prefill_rows"] == 3 + 1 + 1


def test_decode_role_prefill_lane_is_one_small_row(tiny_model):
    """A ``decode``-role replica keeps the lane it had: one row of
    page_size + 1 tokens a round, whatever waits in its slots (a plain
    prefill that lands here crawls, and stays exact)."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, prefill_chunk=16,
                    role="decode")
    prompts = [list(range(1, 21)), [5, 9, 2]]
    wants = [_reference_completion(model, params, p, 4)
             for p in prompts]
    hs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    while eng.step():
        pass
    assert [h.result() for h in hs] == wants
    rounds = _round_events(eng)
    assert all(r["prefill_budget"] == 9 for r in rounds)
    assert all(r["prefill_rows"] <= 1 for r in rounds)
    assert all(r["prefill_tokens"] <= 9 for r in rounds)
    # 20 tokens at 9 a round = 3 calls, then the short prompt's one
    assert eng.stats["prefills"] == 4 == eng.stats["prefill_rows"]


@pytest.mark.parametrize("role,overlap", [("unified", False),
                                          ("unified", True),
                                          ("decode", False)])
def test_prompt_burst_cuts_the_decode_cadence(tiny_model, role, overlap):
    """Six five-chunk prompts land beside a stream that has a few
    tokens left: four take the call's rows, two queue behind them, and
    the queue outlasts the rider, so while they queue a round decodes
    ``BACKLOG_DECODE_STEPS`` steps (``backlog`` on the ``round`` event,
    ``stats["backlog_rounds"]``). Once every prompt has a row the
    cadence is ``chunk`` again. Tokens are ``generate``'s whatever the
    cadence. A decode-role replica's one-row lane counts no backlog:
    its cadence never drops."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=8, page_size=8,
                    n_pages=128, chunk=4, prefill_chunk=8, role=role,
                    overlap=overlap)
    p0 = [3, 1, 4]
    prompts = [list(range(1 + i, 41 + i)) for i in range(6)]  # 5 chunks
    wants = [_reference_completion(model, params, p, n)
             for p, n in [(p0, 24)] + [(p, 6) for p in prompts]]
    hs = [eng.submit(p0, max_new_tokens=24)]
    for _ in range(2):                       # p0 decoding solo
        eng.step()
    hs += [eng.submit(p, max_new_tokens=6) for p in prompts]
    while eng.step():
        pass
    assert [h.result() for h in hs] == wants
    rounds = [r for r in _round_events(eng) if r["decode_steps"]]
    cut = [r for r in rounds if r["backlog"]]
    if role == "decode":
        assert eng.stats["backlog_rounds"] == 0 and not cut
        return
    assert eng.stats["backlog_rounds"] == len(cut) > 0
    assert all(r["decode_steps"] == BACKLOG_DECODE_STEPS
               and r["prefill_rows"] == 4 and r["backlog"] <= 2
               for r in cut)
    # the first four prompts hold their rows five rounds: the two
    # behind them queue no longer than that
    assert len(cut) <= 5
    assert any(r["decode_steps"] == 4 and r["prefill_rows"]
               for r in rounds if not r["backlog"])


# ------------------------------------------------------- pure planner


_PLAN = dict(total_slots=4, prefill_chunk=16, decode_chunk=4,
             max_run_ahead=128, prefill_batch=4, eos_bounded=False)


def _waiting(remaining, first_sid=0, **kw):
    """Unseeded mid-prefill views, admitted in list order."""
    return [SlotView(sid=i, admit_seq=i, prompt_remaining=n, owed=0,
                     seeded=False, **kw)
            for i, n in enumerate(remaining, first_sid)]


def test_planner_long_prompt_takes_one_row():
    """A long prompt at the head of the lane holds ONE row of one
    chunk; the short prompt behind it rides the same call."""
    plan = plan_step(_waiting([100, 3]), **_PLAN)
    assert plan.prefill == (PrefillGrant(0, 16), PrefillGrant(1, 3))
    assert plan.decode_steps == 0                   # nothing seeded


def test_planner_short_prompts_each_take_a_row():
    """No token cap is shared between rows: the third prompt gets all
    9 of its tokens (5 + 6 + 9 = 20 > one chunk of 16)."""
    plan = plan_step(_waiting([5, 6, 9]), **_PLAN)
    assert plan.prefill == (PrefillGrant(0, 5), PrefillGrant(1, 6),
                            PrefillGrant(2, 9))


def test_planner_four_one_chunk_prompts_in_one_round():
    """The saturated cell's round: four prompts of exactly one chunk
    are all granted whole, at once — the budget is rows x chunk."""
    plan = plan_step(_waiting([16, 16, 16, 16, 16]),
                     **dict(_PLAN, total_slots=8))
    assert plan.prefill == tuple(PrefillGrant(i, 16) for i in range(4))


def test_planner_long_head_does_not_delay_the_rows_behind_it():
    """Round by round: three short prompts behind a 5-chunk prompt all
    finish in the FIRST round, and the long one advances a chunk a
    round, as it would alone."""
    remaining = [80, 7, 16, 2]
    rounds = []
    while any(remaining):
        plan = plan_step(_waiting(remaining), **_PLAN)
        rounds.append({g.sid: g.tokens for g in plan.prefill})
        for g in plan.prefill:
            remaining[g.sid] -= g.tokens
    assert rounds[0] == {0: 16, 1: 7, 2: 16, 3: 2}
    assert rounds[1:] == [{0: 16}] * 4


@pytest.mark.parametrize("remaining", [1, 15, 16, 17, 100])
def test_planner_one_mid_prefill_slot_plans_as_one_shared_chunk(
        remaining):
    """With ONE slot mid-prefill the plan is what a single shared
    chunk granted: min(remaining, chunk), beside the same decode."""
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=50, seeded=True),
             SlotView(sid=1, admit_seq=1, prompt_remaining=remaining,
                      owed=0, seeded=False)]
    plan = plan_step(views, **_PLAN)
    assert plan.prefill == (PrefillGrant(1, min(remaining, 16)),)
    assert plan.decode_steps == 4


def test_planner_online_rows_before_batch_rows():
    """More than four slots wait: every online slot takes a row before
    any batch slot, FIFO within each lane, whatever the admission
    order between the lanes."""
    views = [SlotView(sid=i, admit_seq=i, prompt_remaining=40, owed=0,
                      seeded=False, batch=b)
             for i, b in enumerate([True, True, False, True, False,
                                    False])]
    plan = plan_step(views, **dict(_PLAN, total_slots=8))
    assert [g.sid for g in plan.prefill] == [2, 4, 5, 0]
    assert all(g.tokens == 16 for g in plan.prefill)


def test_planner_pulling_slot_takes_no_row():
    views = _waiting([16, 16]) + [
        SlotView(sid=2, admit_seq=2, prompt_remaining=16, owed=0,
                 seeded=False, pulling=True)]
    plan = plan_step(views, **_PLAN)
    assert [g.sid for g in plan.prefill] == [0, 1]


def test_planner_decode_rides_behind_prefill():
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=50, seeded=True),
             SlotView(sid=1, admit_seq=1, prompt_remaining=40,
                      owed=0, seeded=False)]
    plan = plan_step(views, **_PLAN)
    assert plan.prefill == (PrefillGrant(1, 16),)
    assert plan.decode_steps == 4         # quick cadence, no run-ahead


def test_planner_run_ahead_when_full_and_seeded():
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=50, seeded=True),
             SlotView(sid=1, admit_seq=1, prompt_remaining=0,
                      owed=20, seeded=True)]
    plan = plan_step(views, **dict(_PLAN, total_slots=2))
    assert plan.prefill == ()
    assert plan.decode_steps == 20        # to the next completion
    bounded = plan_step(views, **dict(_PLAN, total_slots=2,
                                      eos_bounded=True))
    assert bounded.decode_steps == 8      # 2 x decode_chunk cap


def test_planner_prefill_batch_width_cap():
    views = [SlotView(sid=i, admit_seq=i, prompt_remaining=1,
                      owed=0, seeded=False) for i in range(6)]
    plan = plan_step(views, **dict(_PLAN, total_slots=8))
    assert len(plan.prefill) == 4         # prefill_batch
    assert [g.sid for g in plan.prefill] == [0, 1, 2, 3]


def test_planner_validates_budgets():
    with pytest.raises(ValueError):
        plan_step([], **dict(_PLAN, prefill_chunk=0))
    with pytest.raises(ValueError):
        plan_step([], **dict(_PLAN, decode_chunk=0))
    assert plan_step([], **_PLAN).idle


def test_planner_unbounded_run_ahead_clamps_to_ceiling():
    """eos_bounded=False tail: with a full seeded batch the plan runs
    ahead to the next completion, but never past max_run_ahead — the
    device token buffer is [KMAX, S]-sized."""
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=500, seeded=True),
             SlotView(sid=1, admit_seq=1, prompt_remaining=0,
                      owed=400, seeded=True)]
    plan = plan_step(views, **dict(_PLAN, total_slots=2))
    assert plan.decode_steps == 128     # max_run_ahead, not min(owed)


def test_planner_unbounded_tail_never_below_one():
    """owed can reach 0 mid-flight in no-eos mode (deferred
    retirement waits on a trailing readback); the lane must still
    dispatch >= 1 step, never 0 or negative."""
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=0, seeded=True)]
    plan = plan_step(views, **dict(_PLAN, total_slots=1))
    assert plan.decode_steps >= 1
    views = [SlotView(sid=0, admit_seq=0, prompt_remaining=0,
                      owed=0, seeded=True),
             SlotView(sid=1, admit_seq=1, prompt_remaining=0,
                      owed=9, seeded=True)]
    plan = plan_step(views, **dict(_PLAN, total_slots=2))
    assert plan.decode_steps >= 1


def _riders(n, *, owed=50, batch=False, first_sid=0, **kw):
    """Seeded views riding decode, admitted before anything else."""
    return [SlotView(sid=first_sid + i, admit_seq=first_sid + i,
                     prompt_remaining=0, owed=owed, seeded=True,
                     batch=batch, **kw) for i in range(n)]


def _queued(n, *, first_sid, tokens=400, **kw):
    """Unseeded mid-prompt views: 400 tokens is 25 chunks of 16, far
    more rounds of prefill than a rider owed 50 has rounds of decode
    (12.5 at 4 steps a round)."""
    return _waiting([tokens] * n, first_sid, **kw)


_BACKLOG_PLAN = dict(_PLAN, total_slots=8)
_CUT = BACKLOG_DECODE_STEPS
_DECODE_ROLE = role_plan_caps("decode", page_size=8, decode_chunk=4,
                              prefill_chunk=16, prefill_batch=4,
                              max_run_ahead=128)

# (views, plan_step overrides, rows granted, decode steps, spec rows,
#  StepPlan.backlog)
_BACKLOG_CASES = {
    "five prompts for four rows, riders: cut":
        (_riders(2) + _queued(5, first_sid=2), {}, 4, _CUT, 0, 1),
    "four prompts for four rows: decode_chunk":
        (_riders(2) + _queued(4, first_sid=2), {}, 4, 4, 0, 0),
    "full and seeded: run-ahead as before":
        (_riders(8, owed=20), {}, 0, 20, 0, 0),
    # 5 prompts x 2 chunks over 4 rows = 2.5 rounds of prefill; the
    # riders have 50 / 4 = 12.5 rounds left: the queue joins them
    "a queue that clears before the riders leave: decode_chunk":
        (_riders(2) + _queued(5, first_sid=2, tokens=32),
         {}, 4, 4, 0, 0),
    # the same queue behind riders about to leave (2 / 4 of a round)
    "the same queue outlasts riders about to leave: cut":
        (_riders(2, owed=2) + _queued(5, first_sid=2, tokens=32),
         {}, 4, _CUT, 0, 1),
    # 10 chunks x 4 steps x 2 riders = 80 against 2 x 10 owed x 4 rows
    "prefill rounds equal to decode rounds: decode_chunk":
        (_riders(2, owed=10) + _queued(5, first_sid=2, tokens=32),
         {}, 4, 4, 0, 0),
    "batch backlog behind online riders: decode_chunk":
        (_riders(2) + _queued(4, first_sid=2)
         + _queued(2, first_sid=6, batch=True), {}, 4, 4, 0, 0),
    "batch backlog, batch riders only: cut":
        (_riders(2, batch=True) + _queued(4, first_sid=2)
         + _queued(2, first_sid=6, batch=True), {}, 4, _CUT, 0, 2),
    "online backlog behind batch riders: cut":
        (_riders(2, batch=True) + _queued(5, first_sid=2),
         {}, 4, _CUT, 0, 1),
    "a pulling slot never counts":
        (_riders(2) + _queued(4, first_sid=2)
         + _queued(2, first_sid=6, pulling=True), {}, 4, 4, 0, 0),
    "spec lane untouched: one verify, drafts kept":
        (_riders(2, spec_drafts=3) + _queued(5, first_sid=2),
         {"spec_enabled": True}, 4, 0, 2, 1),
    "spec on, no proposal: the plain lane is cut":
        (_riders(2) + _queued(5, first_sid=2),
         {"spec_enabled": True}, 4, _CUT, 0, 1),
    "eos and stale caps still apply under backlog":
        (_riders(2, stale=4) + _queued(5, first_sid=2),
         {"eos_bounded": True}, 4, _CUT, 0, 1),
    "eos cap without backlog is what it was":
        (_riders(8, owed=20), {"eos_bounded": True}, 0, 8, 0, 0),
    "max_run_ahead of one caps the cut too":
        (_riders(2) + _queued(5, first_sid=2), {"max_run_ahead": 1},
         4, 1, 0, 1),
    "decode_chunk of one is the cadence either way":
        (_riders(2, owed=5) + _queued(5, first_sid=2),
         {"decode_chunk": 1}, 4, 1, 0, 1),
    "the decode role keeps its cadence":
        (_riders(2) + _queued(5, first_sid=2), _DECODE_ROLE,
         1, 4, 0, 0),
    "no rider: nothing to cut, the count still reported":
        (_queued(6, first_sid=0), {}, 4, 0, 0, 2),
}


@pytest.mark.parametrize("case", list(_BACKLOG_CASES))
def test_planner_decode_cadence_follows_the_prefill_backlog(case):
    """While a mid-prefill slot is left WITHOUT a row (the rows are
    full, prompts queue behind them) and the prompts in slots need
    more rounds of prefill than the riders have rounds of decode left,
    the plain decode lane dispatches ``BACKLOG_DECODE_STEPS``; in
    every other state the plan is what it was."""
    views, over, rows, steps, spec_rows, backlog = _BACKLOG_CASES[case]
    plan = plan_step(views, **dict(_BACKLOG_PLAN, **over))
    assert len(plan.prefill) == rows
    assert plan.decode_steps == steps
    assert len(plan.spec) == spec_rows
    assert plan.backlog == backlog
    if spec_rows:
        assert [g.drafts for g in plan.spec] == [3, 3]


def test_planner_all_slots_mid_prefill_decode_lane_empty():
    """A round where every slot is still prefilling: the decode lane
    must be EMPTY (0 steps), not negative, and the round must not
    read as idle — prefill work was granted."""
    views = [SlotView(sid=i, admit_seq=i, prompt_remaining=r,
                      owed=0, seeded=False)
             for i, r in enumerate([10, 20, 30, 40])]
    plan = plan_step(views, **_PLAN)
    assert plan.decode_steps == 0
    assert plan.spec == ()
    assert plan.prefill
    assert not plan.idle
