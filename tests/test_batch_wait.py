"""``batch_wait_timeout_s``: an idle engine holds its first admission
until the prefill call's rows can be filled or the oldest request has
waited long enough; a busy engine holds nothing, and 0 changes nothing.

The model configuration is this file's own (test_engine_trace.py says
why).
"""
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.llm import LlamaDeployment

VOCAB = 239
STAGGER_S = 0.03


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny(dtype=jnp.float32, vocab_size=VOCAB)
    m = Llama(cfg)
    params = jax.jit(m.init)(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))
    return m, params


def _engine(model, **kw):
    opts = dict(max_slots=8, page_size=8, n_pages=129, chunk=4,
                prefill_chunk=16)
    opts.update(kw)
    return LLMEngine(*model, **opts).start()


def _prompt(i, n=40):
    return [1 + (7 * i + j) % (VOCAB - 1) for j in range(n)]


def _staggered(eng, n, new=5):
    """n three-chunk prompts STAGGER_S apart; returns their tokens and
    the rounds' prefill rows."""
    hs = []
    for i in range(n):
        hs.append(eng.submit(_prompt(i), max_new_tokens=new))
        time.sleep(STAGGER_S)
    outs = [h.result() for h in hs]
    rows = [e[5]["prefill_rows"] for e in eng.events.snapshot()
            if e[2] == "round" and e[5]["prefill_rows"]]
    return outs, rows


@pytest.fixture(scope="module")
def at_once(model):
    """The default: the first request leaves alone (this also compiles
    the step programs, so that no later case waits on a compile)."""
    eng = _engine(model)
    try:
        return _staggered(eng, 4)
    finally:
        eng.shutdown()


def test_default_admits_at_once(at_once):
    _outs, rows = at_once
    assert rows[0] == 1


def test_rows_that_fill_start_together(model, at_once):
    """Four requests 30 ms apart, a call of four rows, a wait far
    longer than their spread: the count ends the wait, the first call
    carries all four, and every call after it until a prompt ends."""
    eng = _engine(model, batch_wait_timeout_s=5.0)
    try:
        t0 = time.monotonic()
        outs, rows = _staggered(eng, 4)
        took = time.monotonic() - t0
    finally:
        eng.shutdown()
    assert rows[:3] == [4, 4, 4]
    assert took < 4.0                 # the timeout never ran out
    assert outs == at_once[0]         # same tokens as without the wait


def test_the_oldest_request_bounds_the_wait(model, at_once):
    eng = _engine(model, batch_wait_timeout_s=0.4)
    try:
        t0 = time.monotonic()
        h = eng.submit(_prompt(0), max_new_tokens=5)
        time.sleep(0.1)
        h2 = eng.submit(_prompt(1), max_new_tokens=5)
        outs = [h.result(), h2.result()]
        took = time.monotonic() - t0
        admits = [e for e in eng.events.snapshot() if e[2] == "admit"]
        rows = [e[5]["prefill_rows"] for e in eng.events.snapshot()
                if e[2] == "round" and e[5]["prefill_rows"]]
    finally:
        eng.shutdown()
    # both left when the FIRST had waited 0.4 s, in one call
    assert 0.4 <= took < 3.0
    assert rows[0] == 2 and len(admits) == 2
    assert outs == at_once[0][:2]


def test_a_busy_engine_holds_nothing(model, at_once):
    """A request that arrives while a slot is live is admitted in the
    next round, however long the wait is set."""
    eng = _engine(model, batch_wait_timeout_s=1.0)
    try:
        first = eng.submit(_prompt(0), max_new_tokens=80)
        next(first.stream())          # the engine is busy from here
        t0 = time.monotonic()
        late = eng.submit(_prompt(1), max_new_tokens=5)
        out = late.result()
        took = time.monotonic() - t0
        first.cancel()
    finally:
        eng.shutdown()
    assert out == at_once[0][1]
    assert took < 0.8                 # no 1 s hold behind a live slot


def test_refused_below_zero_and_passed_by_the_deployment(model):
    with pytest.raises(ValueError, match="batch_wait_timeout_s"):
        LLMEngine(*model, batch_wait_timeout_s=-1.0)
    dep = LlamaDeployment(config=model[0].config, params=model[1],
                          max_slots=2, page_size=8, n_pages=33,
                          batch_wait_timeout_s=0.05)
    try:
        assert dep.engine().batch_wait_timeout_s == 0.05
    finally:
        dep.engine().shutdown()
