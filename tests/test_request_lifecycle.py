"""Request-lifecycle hardening tests: cancellation, deadlines,
bounded admission with shedding, fault-isolated dispatch, and the
deterministic fault-injection harness (serve/faults.py).

The containment contract under test: after ANY mix of cancels,
expired deadlines, and injected faults (allocator exhaustion,
per-row dispatch errors, readback errors, slow steps), only the
TARGETED request fails — with the right typed error — while every
survivor's stream stays token-identical to greedy decode and every
resource (allocator pages, prefix-cache refcounts, slots, queues)
returns to baseline (``check_quiesced``).
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import Llama, generate, llama_tiny
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.errors import (DeadlineExceeded, EngineOverloaded,
                                  EngineShutdown, RequestCancelled,
                                  RequestError, classify_http_status,
                                  retry_after_s)
from ray_tpu.serve.faults import (EngineFault, FaultInjector,
                                  check_quiesced)


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


def _drive(eng, max_rounds=5000):
    """Run the engine to quiescence (bounded: a scheduling bug must
    fail the test, not hang it)."""
    for _ in range(max_rounds):
        if not eng.step():
            return
    raise AssertionError("engine did not quiesce "
                         f"within {max_rounds} rounds")


def _slot_of(eng, handle):
    """Index of the live slot serving ``handle`` (None if not
    slotted)."""
    for i, s in enumerate(eng.slots):
        if s is not None and s.req is handle._req:
            return i
    return None


# -------------------------------------------------------- cancellation


def test_cancel_queued_request(tiny_model):
    """A queued request cancels without ever taking a slot; the
    running request is untouched."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=2)
    p1 = [5, 9, 2]
    want1 = _reference_completion(model, params, p1, 12)
    h1 = eng.submit(p1, max_new_tokens=12)
    eng.step()                       # h1 takes the only slot
    h2 = eng.submit([7, 7, 7], max_new_tokens=12)
    assert h2.cancel() is True
    assert h2.done
    with pytest.raises(RequestCancelled):
        h2.result()
    _drive(eng)
    assert h1.result() == want1
    assert eng.stats["cancelled"] == 1
    check_quiesced(eng)


def test_cancel_mid_decode_survivor_parity(tiny_model):
    """Cancelling a decoding slot frees it mid-flight; the other
    slot's stream stays token-identical to the greedy reference."""
    model, params = tiny_model
    # max_slots > live requests keeps quick cadence (chunk steps per
    # round), so the slot is still live when we cancel
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2)
    p1, p2 = [3, 1, 4, 1, 5], [2, 7, 1, 8]
    want1 = _reference_completion(model, params, p1, 24)
    h1 = eng.submit(p1, max_new_tokens=24)
    h2 = eng.submit(p2, max_new_tokens=24)
    for _ in range(4):
        eng.step()
    assert _slot_of(eng, h2) is not None     # mid-decode
    assert h2.cancel() is True
    assert _slot_of(eng, h2) is None         # slot freed NOW
    _drive(eng)
    assert h1.result() == want1
    with pytest.raises(RequestCancelled):
        h2.result()
    assert len(h2._req.generated) < 24       # genuinely partial
    assert eng.stats["cancelled"] == 1
    check_quiesced(eng)


def test_cancel_mid_prefill(tiny_model):
    """Cancelling a slot that is mid-way through chunked prefill
    returns its pages; a later request admits into the freed slot."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=2, prefill_chunk=8)
    p1 = list(range(1, 25))                  # 24 tokens: 3 chunks
    p2 = [7, 3]
    want2 = _reference_completion(model, params, p2, 6)
    h1 = eng.submit(p1, max_new_tokens=6)
    eng.step()                               # first chunk only
    ix = _slot_of(eng, h1)
    assert ix is not None
    assert 0 < eng.slots[ix].prefilled < len(p1)
    assert h1.cancel() is True
    h2 = eng.submit(p2, max_new_tokens=6)
    _drive(eng)
    with pytest.raises(RequestCancelled):
        h1.result()
    assert h2.result() == want2
    check_quiesced(eng)


def test_cancel_after_completion_is_noop(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=4)
    h = eng.submit([5, 9, 2], max_new_tokens=4)
    _drive(eng)
    assert h.result()                        # completed
    assert h.cancel() is False
    assert eng.stats["cancelled"] == 0
    check_quiesced(eng)


def test_cancel_retired_request_with_tokens_in_flight(tiny_model):
    """No-eos mode retires slots at dispatch time while their tokens
    are still in flight; cancelling THEN must close the stream
    (partial tokens, typed error) without touching freed pages."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4)
    h = eng.submit([5, 9, 2], max_new_tokens=12)
    # run-ahead retires the slot at dispatch time within a few rounds
    for _ in range(3):
        eng.step()
        if _slot_of(eng, h) is None:
            break
    if not h.done:                  # tokens still trailing
        assert h.cancel() is True
        with pytest.raises(RequestCancelled):
            h.result()
        assert eng.stats["cancelled"] == 1
    _drive(eng)
    check_quiesced(eng)


# ------------------------------------------------------------ deadlines


def test_deadline_expires_while_queued(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=2)
    p1 = [5, 9, 2]
    want1 = _reference_completion(model, params, p1, 8)
    h1 = eng.submit(p1, max_new_tokens=8)
    eng.step()                       # h1 owns the only slot
    h2 = eng.submit([1, 2, 3], max_new_tokens=8, deadline_s=0.01)
    time.sleep(0.03)
    _drive(eng)
    assert h1.result() == want1
    with pytest.raises(DeadlineExceeded):
        h2.result()
    assert eng.stats["deadline_exceeded"] == 1
    check_quiesced(eng)


def test_deadline_expires_mid_decode_under_slow_step(tiny_model):
    """The slow-step fault class: an injected stall blows a decoding
    request past its deadline; the no-deadline survivor is exact."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.slow("step", 0.05, round=3, times=1)
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, fault_injector=inj)
    p1, p2 = [3, 1, 4, 1, 5], [2, 7, 1, 8]
    want1 = _reference_completion(model, params, p1, 24)
    h1 = eng.submit(p1, max_new_tokens=24)
    h2 = eng.submit(p2, max_new_tokens=24, deadline_s=0.04)
    _drive(eng)
    assert h1.result() == want1
    with pytest.raises(DeadlineExceeded):
        h2.result()
    assert eng.stats["deadline_exceeded"] == 1
    assert ("step", 3, None, "sleep") in inj.log
    check_quiesced(eng)


def test_deadline_validation(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=2)
    with pytest.raises(RequestError):
        eng.submit([1], max_new_tokens=1, deadline_s=0.0)
    with pytest.raises(RequestError):
        eng.submit([1], max_new_tokens=1, deadline_s=-1)


# ------------------------------------------- bounded admission + shed


def test_overload_sheds_fast_with_retry_after(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=2, max_queued=2,
                    shed_retry_after_s=2.5)
    hs = [eng.submit([i + 1, i + 2], max_new_tokens=4)
          for i in range(2)]        # fills the queue (nothing admitted
                                    # yet: no step has run)
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit([9, 9], max_new_tokens=4)
    assert ei.value.retry_after_s == 2.5
    assert eng.stats["shed"] == 1
    # shedding never blocks admitted work
    want = [_reference_completion(model, params, [i + 1, i + 2], 4)
            for i in range(2)]
    _drive(eng)
    assert [h.result() for h in hs] == want
    # capacity back: the next submit is accepted
    h = eng.submit([5, 5], max_new_tokens=4)
    _drive(eng)
    assert h.result()
    assert eng.stats["shed"] == 1   # no further sheds
    check_quiesced(eng)
    stats = eng.lifecycle_stats()
    assert stats["shed"] == 1 and stats["max_queued"] == 2


def test_shed_counter_exported_to_metrics(tiny_model):
    from ray_tpu.util import metrics
    from ray_tpu.serve.engine import SHED_TOTAL
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=2, max_queued=0)
    with pytest.raises(EngineOverloaded):
        eng.submit([1, 2], max_new_tokens=4)
    reg = metrics.registry()
    assert SHED_TOTAL in reg
    assert any(v >= 1 for _tags, v in reg[SHED_TOTAL]._samples())
    assert SHED_TOTAL in metrics.prometheus_text()
    check_quiesced(eng)


# --------------------------------------------- fault class: allocator


def test_alloc_exhaustion_recovers_without_failures(tiny_model):
    """A transiently dry pool at admission is a WAIT, not an error:
    both requests admit on a later round and decode exactly."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.exhaust_alloc(times=2)
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, fault_injector=inj)
    p1, p2 = [3, 1, 4], [2, 7, 1, 8]
    want = [_reference_completion(model, params, p, 8)
            for p in (p1, p2)]
    h1 = eng.submit(p1, max_new_tokens=8)
    h2 = eng.submit(p2, max_new_tokens=8)
    _drive(eng)
    assert [h1.result(), h2.result()] == want
    assert [e for e in inj.log if e[0] == "alloc"]  # it DID fire
    assert eng.stats["contained_faults"] == 0
    assert eng.stats["retries"] == 0
    check_quiesced(eng)


def test_alloc_exhaustion_lone_slot_contained(tiny_model):
    """A lone slot that cannot grow (no victim to preempt) is an
    attributable failure: THAT request fails typed, the engine keeps
    serving the next one."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.exhaust_alloc(round=2, times=1)
    eng = LLMEngine(model, params, max_slots=1, page_size=4,
                    n_pages=32, chunk=2, fault_injector=inj)
    h1 = eng.submit([1, 2, 3], max_new_tokens=16)
    _drive(eng)
    with pytest.raises(RequestError, match="page pool exhausted"):
        h1.result()
    assert eng.stats["contained_faults"] == 1
    assert eng.stats["fault_failed"] == 1
    assert eng.stats["failed_all"] == 0      # engine survived
    p2 = [4, 4, 8]
    want2 = _reference_completion(model, params, p2, 6)
    h2 = eng.submit(p2, max_new_tokens=6)
    _drive(eng)
    assert h2.result() == want2
    check_quiesced(eng)


# ---------------------------------------------- fault class: dispatch


def test_decode_dispatch_fault_contained(tiny_model):
    """An exception attributable to one decode rider fails ONLY that
    request; the innocent co-rider requeues under the retry policy
    and still matches the greedy reference exactly."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.inject("dispatch_decode", sid=1, round=3)
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, fault_injector=inj,
                    retry_backoff_s=0.005)
    p1, p2 = [3, 1, 4, 1, 5], [2, 7, 1, 8]
    want1 = _reference_completion(model, params, p1, 16)
    h1 = eng.submit(p1, max_new_tokens=16)   # slot 0: innocent
    h2 = eng.submit(p2, max_new_tokens=16)   # slot 1: culprit
    _drive(eng)
    with pytest.raises(RuntimeError, match="injected fault"):
        h2.result()
    assert h1.result() == want1
    assert eng.stats["contained_faults"] == 1
    assert eng.stats["fault_failed"] == 1
    assert eng.stats["retries"] == 1         # innocent requeued once
    assert eng.stats["retry_exhausted"] == 0
    assert eng.stats["failed_all"] == 0
    assert h1._req.attempts == 1
    check_quiesced(eng)


def test_prefill_dispatch_fault_contained(tiny_model):
    """Same containment at the prefill phase: the faulted prompt dies
    before its first token, its co-prefilling neighbor retries to an
    exact stream."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.inject("dispatch_prefill", sid=0, round=1,
               exc=ValueError("bad row"))
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, fault_injector=inj,
                    retry_backoff_s=0.005)
    p1, p2 = [3, 1, 4, 1, 5], [2, 7, 1, 8]
    want2 = _reference_completion(model, params, p2, 8)
    h1 = eng.submit(p1, max_new_tokens=8)    # slot 0: culprit
    h2 = eng.submit(p2, max_new_tokens=8)    # slot 1: innocent
    _drive(eng)
    with pytest.raises(ValueError, match="bad row"):
        h1.result()
    assert len(h1._req.generated) == 0       # died before any token
    assert h2.result() == want2
    assert eng.stats["retries"] == 1
    assert eng.stats["fault_failed"] == 1
    check_quiesced(eng)


def test_spec_dispatch_fault_contained(tiny_model):
    """Containment in the speculation lane: a fault on one verify row
    fails that request only; the co-speculating slot still decodes
    token-identical greedy output."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.inject("dispatch_spec", sid=1, round=4)
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, spec_len=3, spec_ngram=2,
                    fault_injector=inj, retry_backoff_s=0.005)
    rep = ([7, 8, 9, 10] * 5)[:16]           # repetitive: drafts fire
    want1 = _reference_completion(model, params, rep, 12)
    h1 = eng.submit(rep, max_new_tokens=12)          # slot 0
    h2 = eng.submit(list(rep[2:]), max_new_tokens=12)  # slot 1
    _drive(eng)
    with pytest.raises(RuntimeError, match="injected fault"):
        h2.result()
    assert h1.result() == want1
    assert eng.stats["contained_faults"] == 1
    assert eng.stats["failed_all"] == 0
    check_quiesced(eng)


def test_retry_policy_exhausts_bounded(tiny_model):
    """max_retries=0: the innocent participant of a faulted dispatch
    fails too (typed, naming the retry budget) instead of retrying
    forever — and the engine still serves the next request."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.inject("dispatch_decode", sid=1, round=3)
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, max_retries=0,
                    fault_injector=inj)
    h1 = eng.submit([3, 1, 4], max_new_tokens=16)    # innocent
    h2 = eng.submit([2, 7, 1], max_new_tokens=16)    # culprit
    _drive(eng)
    with pytest.raises(RuntimeError, match="injected fault"):
        h2.result()
    with pytest.raises(RequestError, match="failed after 0 retries"):
        h1.result()
    assert eng.stats["retry_exhausted"] == 1
    assert eng.stats["retries"] == 0
    p3 = [4, 4, 8]
    want3 = _reference_completion(model, params, p3, 6)
    h3 = eng.submit(p3, max_new_tokens=6)
    _drive(eng)
    assert h3.result() == want3
    check_quiesced(eng)


# ---------------------------------------------- fault class: readback


def test_readback_fault_isolated(tiny_model):
    """A fault while emitting ONE rider's tokens host-side fails only
    that request; co-riders' emissions proceed untouched."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.inject("readback", sid=1, round=1, exc=OSError("xfer error"))
    eng = LLMEngine(model, params, max_slots=4, page_size=8,
                    n_pages=64, chunk=2, fault_injector=inj)
    p1, p2 = [3, 1, 4, 1, 5], [2, 7, 1, 8]
    want1 = _reference_completion(model, params, p1, 12)
    h1 = eng.submit(p1, max_new_tokens=12)   # slot 0
    h2 = eng.submit(p2, max_new_tokens=12)   # slot 1
    _drive(eng)
    with pytest.raises(OSError, match="xfer error"):
        h2.result()
    assert h1.result() == want1
    assert eng.stats["contained_faults"] == 1
    assert eng.stats["fault_failed"] == 1
    assert eng.stats["failed_all"] == 0
    check_quiesced(eng)


def test_readback_fault_eos_mode_slot_teardown(tiny_model):
    """eos mode keeps the slot live at emission time, so a readback
    fault must tear the SLOT down (pages freed), not just close the
    stream."""
    model, params = tiny_model
    prompt = [5, 9, 2]
    ref = _reference_completion(model, params, prompt, 16)
    inj = FaultInjector()
    inj.inject("readback", sid=0, round=2)
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, eos_id=max(ref) + 1,
                    fault_injector=inj)
    h = eng.submit(prompt, max_new_tokens=16)
    _drive(eng)
    with pytest.raises(RuntimeError, match="injected fault"):
        h.result()
    assert eng.stats["fault_failed"] == 1
    check_quiesced(eng)


# ------------------------------------------------------- global faults


def test_global_fault_fails_all_and_stops(tiny_model):
    """A fault at the ``step`` site carries no attribution (device
    loss): EVERY request fails with the raw error, the engine stops,
    and later submits see EngineShutdown — the last-resort path, now
    also leak-free."""
    model, params = tiny_model
    inj = FaultInjector()
    inj.inject("step", round=2, exc=RuntimeError("device lost"))
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=2, fault_injector=inj).start()
    h1 = eng.submit([3, 1, 4], max_new_tokens=40)
    h2 = eng.submit([2, 7, 1], max_new_tokens=40)
    for h in (h1, h2):
        with pytest.raises(RuntimeError, match="device lost"):
            h.result()
    assert eng.stats["failed_all"] == 1
    assert eng.stats["contained_faults"] == 0
    with pytest.raises(EngineShutdown):
        eng.submit([1], max_new_tokens=1)
    check_quiesced(eng)


# ------------------------------------------------------------ shutdown


def test_shutdown_unblocks_all_stream_readers(tiny_model):
    """Regression: shutdown() with queued AND in-flight requests must
    leave no stream() reader blocked — every consumer resolves with
    either its full completion or a typed EngineShutdown."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=2).start()
    outcomes = [None] * 3

    def run(i):
        try:
            outcomes[i] = list(
                eng.submit([i + 1, i + 2],
                           max_new_tokens=100).stream())
        except BaseException as e:  # noqa: BLE001
            outcomes[i] = e

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.05)                 # let readers block mid-flight
    eng.shutdown()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads), \
        "a stream() reader hung across shutdown"
    for out in outcomes:
        assert isinstance(out, (list, EngineShutdown)), out
    # shutdown is idempotent and late submits fail typed
    eng.shutdown()
    with pytest.raises(EngineShutdown):
        eng.submit([1], max_new_tokens=1)
    check_quiesced(eng)


def test_shutdown_fails_queued_requests_typed(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=32, chunk=2)      # never stepped
    h = eng.submit([1, 2], max_new_tokens=4)
    eng.shutdown()
    with pytest.raises(EngineShutdown):
        h.result()
    check_quiesced(eng)


# ----------------------------------------------- client disconnection


def test_stream_disconnect_cancels_engine_request():
    """The replica-side disconnect contract (serve/llm.py): a client
    abandoning a stream closes the generator, which must CANCEL the
    engine request — the slot and its pages free instead of decoding
    to completion."""
    from ray_tpu.serve.llm import LlamaDeployment
    dep = LlamaDeployment(max_new_tokens=64, max_slots=4,
                          page_size=8)
    gen = dep.stream([3, 1, 4])
    next(gen)                        # stream established
    gen.close()                      # client disconnect
    eng = dep._engine
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        with eng._lock:
            settled = (not any(eng.slots) and not eng._fetchq
                       and not eng._pending_prefill)
        if settled and eng.stats["cancelled"] == 1:
            break
        time.sleep(0.01)
    assert eng.stats["cancelled"] == 1
    check_quiesced(eng)
    eng.shutdown()


# ------------------------------------------------- injector mechanics


def test_injector_bounded_times_allows_recovery(tiny_model):
    """A plan with times=N stops firing after N hits: the engine
    recovers and later requests run clean — recovery is observable,
    not just failure."""
    inj = FaultInjector()
    plan = inj.inject("dispatch_decode", sid=0, times=1)
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=2, fault_injector=inj)
    h1 = eng.submit([3, 1, 4], max_new_tokens=8)
    _drive(eng)
    with pytest.raises(RuntimeError):
        h1.result()
    assert plan.fired == 1
    p2 = [2, 7, 1]
    want2 = _reference_completion(model, params, p2, 8)
    h2 = eng.submit(p2, max_new_tokens=8)    # re-lands on slot 0
    _drive(eng)
    assert h2.result() == want2              # plan spent: no re-fire
    assert plan.fired == 1
    check_quiesced(eng)


def test_engine_fault_attribution_defaults():
    e = EngineFault(RuntimeError("x"), culprit_sid=3, culprit_rid=7)
    assert e.sids == [3]
    assert e.culprit_rid == 7
    e2 = EngineFault(RuntimeError("x"))
    assert e2.sids == [] and e2.culprit_sid is None


# ------------------------------------------------- HTTP status mapping


def test_classify_http_status_direct():
    assert classify_http_status(EngineOverloaded("full")) == 429
    assert classify_http_status(DeadlineExceeded("late")) == 504
    assert classify_http_status(EngineShutdown("bye")) == 503
    assert classify_http_status(RequestCancelled("gone")) == 499
    assert classify_http_status(ValueError("nope")) == 500


def test_classify_http_status_wrapped_and_stringly():
    from ray_tpu.exceptions import GetTimeoutError
    assert classify_http_status(GetTimeoutError("slow")) == 504
    # cause-chain wrapping (the remote-call layer re-raises)
    outer = RuntimeError("task failed")
    outer.__cause__ = DeadlineExceeded("late")
    assert classify_http_status(outer) == 504
    wrapper = RuntimeError("boom")
    wrapper.cause = EngineOverloaded("full", retry_after_s=3.0)
    assert classify_http_status(wrapper) == 429
    assert retry_after_s(wrapper) == 3.0
    # stringly: a remote traceback that only NAMES the type
    assert classify_http_status(
        RuntimeError("RayTaskError: EngineOverloaded: shed")) == 429


def test_proxy_error_response_contract():
    """The proxy's error mapping (serve/http_proxy.py): clean JSON
    bodies, 429 + Retry-After for sheds, 504 for deadline/get-timeout
    — never a 500 with a traceback for lifecycle failures."""
    pytest.importorskip("aiohttp")
    from ray_tpu.exceptions import GetTimeoutError
    from ray_tpu.serve.http_proxy import HTTPProxy

    r = HTTPProxy._error_response(
        EngineOverloaded("queue full", retry_after_s=2.4))
    assert r.status == 429
    # Ceiling, not round: the header must never invite a client
    # back before the hint says capacity could exist (2.4s -> "3").
    assert r.headers["Retry-After"] == "3"
    body = json.loads(r.text)
    assert body["type"] == "EngineOverloaded"
    assert body["error"] == "queue full"

    r = HTTPProxy._error_response(GetTimeoutError())
    assert r.status == 504
    body = json.loads(r.text)
    assert body["error"] == "upstream timed out before replying"

    assert HTTPProxy._error_response(
        DeadlineExceeded("late")).status == 504
    assert HTTPProxy._error_response(
        EngineShutdown("bye")).status == 503
    assert HTTPProxy._error_response(
        RequestCancelled("gone")).status == 499
    r = HTTPProxy._error_response(ValueError("app bug"))
    assert r.status == 500
    assert json.loads(r.text)["type"] == "ValueError"
