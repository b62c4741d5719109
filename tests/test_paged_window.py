"""The paged attention window follows the live contexts: the blocked
loop (a runtime trip count over blocks of pages, online softmax)
against the one-shot softmax over the whole page table, and one engine
through short and long contexts without a new program.

The block is a constant of the shapes (512 tokens on a deployment); the
tests patch it to 16 tokens so that a 128-token table has 8 blocks, and
to more than the table for the one-shot reference, where the loop is a
single straight-line block. The engine's step programs are cached per
process by (model, knobs), so each side clears those caches, and the
model configuration here is one no other test file uses.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama as llama_mod
from ray_tpu.models.kv_cache import init_kv_pool
from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.ops import paged_attention as paged_mod
from ray_tpu.serve import step_programs
from ray_tpu.serve.engine import LLMEngine

PAGE, BLOCK, N_PAGES = 8, 16, 65
CFG = llama_tiny(dtype=jnp.float32, vocab_size=233)   # max_seq_len 128
MAX_PAGES = CFG.max_seq_len // PAGE
KMAX = 8


@pytest.fixture(scope="module")
def model_params():
    model = Llama(CFG)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


@pytest.fixture
def block_tokens(monkeypatch):
    """set(n): the window loop's block is n tokens for programs built
    from here on; the shared program caches are cleared now and after."""
    def clear():
        step_programs._jit_decode.cache_clear()
        step_programs._jit_prefill.cache_clear()

    def set_(n):
        monkeypatch.setattr(paged_mod, "_WINDOW_BLOCK_TOKENS", n)
        clear()
    yield set_
    clear()


def _table(contexts, room):
    """Page-table rows holding ``contexts[i] + room`` tokens each; None
    is a row no request owns (the null row)."""
    pt = np.zeros((len(contexts), MAX_PAGES), np.int32)
    nxt = 1
    for i, c in enumerate(contexts):
        if c is None:
            continue
        n = -(-(c + room) // PAGE)
        pt[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    assert nxt <= N_PAGES
    return pt


def _filled_pool(model, params, pt, contexts, kv_dtype):
    """A pool whose rows hold real K/V for their contexts, written by
    one prefill through the one-shot path (tokens past a row's context
    are overwritten or masked before any query can see them)."""
    width = max(c for c in contexts if c is not None)
    ids = jax.random.randint(jax.random.PRNGKey(3),
                             (len(contexts), width), 0, CFG.vocab_size)
    pages = init_kv_pool(CFG, N_PAGES, PAGE, kv_dtype)
    pre = step_programs._jit_prefill(model, 0.0, len(contexts), False, None)
    _, pages, _ = pre(params, pages, ids,
                      jnp.zeros((len(contexts),), jnp.int32),
                      jnp.zeros((len(contexts),), jnp.int32),
                      jnp.asarray(pt), jax.random.PRNGKey(0))
    return pages


def _run(case, model, params, pages, pt):
    """What the engine's own programs give for the case: tokens, the
    chosen tokens' log-probabilities (float32, of the logits) and the
    pool they leave."""
    live = [i for i, c in enumerate(case["contexts"]) if c is not None]
    rows = len(case["contexts"])
    pages = jax.tree_util.tree_map(jnp.array, pages)    # donated below
    if "chunk" in case:
        T = case["chunk"]
        ids = jax.random.randint(jax.random.PRNGKey(5), (rows, T), 0,
                                 CFG.vocab_size)
        start = np.asarray([c or 0 for c in case["contexts"]], np.int32)
        pre = step_programs._jit_prefill(model, 0.0, rows, True, None)
        (toks, lps), pages, _ = pre(
            params, pages, ids, jnp.asarray(start),
            jnp.full((rows,), T - 1, jnp.int32), jnp.asarray(pt),
            jax.random.PRNGKey(0))
        toks, lps = np.asarray(toks)[live], np.asarray(lps)[live]
    else:
        steps = case["steps"]
        pos = np.asarray([case.get("stale", 0) if c is None else c
                          for c in case["contexts"]], np.int32)
        dec = step_programs._jit_decode(model, 0.0, KMAX, rows, True, None)
        (toks, lps), pages, _, _, _ = dec(
            params, pages, jnp.asarray(pt), jnp.asarray(pos),
            jnp.full((rows,), 7, jnp.int32), jax.random.PRNGKey(0),
            jnp.int32(steps))
        toks = np.asarray(toks)[:steps, live]
        lps = np.asarray(lps)[:steps, live]
    # page 0 is the null page: dead rows scatter junk there
    pool = [tuple(np.asarray(t)[1:] for t in layer)
            for layer in pages]
    return toks, lps, pool


# contexts: tokens already in each row's pages (None: a null row);
# steps: a decode dispatch of that many steps; chunk: a prefill chunk
# of that many tokens appended at the contexts
CASES = {
    "inside_one_block": dict(contexts=[5, 9, 12], steps=2),
    # 14 -> 20 crosses 16 and 30 -> 36 crosses 32 inside the dispatch
    "decode_crosses_block_edges": dict(contexts=[14, 3, 30], steps=6),
    # positions 10..25 and 44..59: each starts in one block, ends in
    # the next
    "prefill_chunk_spans_blocks": dict(contexts=[10, 44], chunk=16),
    "null_row_with_stale_pos": dict(contexts=[14, None, 7], stale=125,
                                    steps=4),
    "int8_pool": dict(contexts=[14, 40, 7], steps=4, kv_dtype="int8"),
    "very_different_lengths": dict(contexts=[2, 100, 33, 17], steps=5),
}


@pytest.mark.parametrize("name", list(CASES))
def test_blocked_window_equals_one_shot(name, model_params,
                                        block_tokens):
    model, params = model_params
    case = CASES[name]
    contexts = case["contexts"]
    pt = _table(contexts, room=case.get("chunk", KMAX))
    block_tokens(CFG.max_seq_len)                # one block: one shot
    pool = _filled_pool(model, params, pt, contexts,
                        case.get("kv_dtype", "fp"))
    want = _run(case, model, params, pool, pt)
    block_tokens(BLOCK)
    got = _run(case, model, params, pool, pt)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    for g_layer, w_layer in zip(got[2], want[2]):
        for g, w in zip(g_layer, w_layer):
            if g.dtype == np.int8:
                # a code may round the other way on a 1e-7 difference
                assert np.abs(g.astype(int) - w.astype(int)).max() <= 1
            else:
                np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    if name == "null_row_with_stale_pos":
        # the dead row's stale position must not change live rows: the
        # same dispatch with the dead row's position at 0 agrees
        calm = _run(dict(case, stale=0), model, params, pool, pt)
        np.testing.assert_array_equal(got[0], calm[0])
        np.testing.assert_allclose(got[1], calm[1], rtol=1e-6, atol=1e-6)


# 4 heads over the pool's 2 KV heads, and one head each (the group
# padded to two rows): the count is the table's and the positions'
@pytest.mark.parametrize("heads", [4, 2])
def test_trip_count_follows_live_rows_only(block_tokens, heads):
    """The loop's trip count, read off the traced program: the blocks
    up to the longest LIVE row's last query, whatever a null row's
    position says, and never more than the table holds."""
    block_tokens(BLOCK)
    pk = jnp.zeros((N_PAGES, PAGE, 2, 16), jnp.float32)
    q = jnp.zeros((3, 1, heads, 16), jnp.float32)
    seen = []
    real = jax.lax.fori_loop

    def spy(lo, hi, body, init):
        seen.append(hi)
        return real(lo, hi, body, init)

    def trips(pt, pos):
        def hi(pt, pos):
            paged_mod._paged_window_attention(q, pk, pk, None, None,
                                              pt, pos)
            return seen[-1]

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax.lax, "fori_loop", spy)
            return int(jax.jit(hi)(jnp.asarray(pt), jnp.asarray(pos)))

    pt = np.zeros((3, MAX_PAGES), np.int32)
    pt[0, :2], pt[2, :5] = [1, 2], [3, 4, 5, 6, 7]
    assert trips(pt, np.asarray([15, 127, 33])) == 3
    assert trips(pt, np.asarray([15, 127, 31])) == 2
    assert trips(pt, np.asarray([16, 0, 3])) == 2
    pt[1, :1] = [8]                        # the stale row comes alive
    assert trips(pt, np.asarray([15, 127, 31])) == 8
    assert trips(pt, np.asarray([15, 4000, 31])) == 8


def test_long_contexts_build_no_new_program(model_params, block_tokens):
    """One engine through short and then long contexts: the decode and
    prefill programs stay one executable each, and the round events say
    how wide a window each dispatch attended."""
    model, params = model_params
    block_tokens(BLOCK)
    eng = LLMEngine(model, params, max_slots=4, page_size=PAGE,
                    n_pages=N_PAGES, chunk=4, prefill_chunk=PAGE).start()
    try:
        def windows(key):
            return [e[5][key] for e in eng.events.snapshot()
                    if e[2] == "round" and e[5][key]]

        short = [eng.submit(list(range(1, 6 + i)), max_new_tokens=4)
                 for i in range(3)]
        for h in short:
            h.result()
        assert eng.wait_idle(10)
        built = eng.stats["programs_built"]
        sizes = (eng._decode_fn._cache_size(),
                 eng._prefill_fn._cache_size())
        assert sizes == (1, 1)
        assert set(windows("decode_window_tokens")) == {BLOCK}
        assert set(windows("prefill_window_tokens")) == {BLOCK}

        prompt = [1 + (7 * i) % 200 for i in range(90)]
        long_ = eng.submit(prompt, max_new_tokens=24)
        beside = eng.submit([3, 1, 4, 1, 5], max_new_tokens=24)
        out = long_.result()
        beside.result()
        assert eng.wait_idle(10)
        assert eng.stats["programs_built"] == built
        assert (eng._decode_fn._cache_size(),
                eng._prefill_fn._cache_size()) == sizes
        # 90 + 24 tokens end in the eighth 16-token block
        assert max(windows("decode_window_tokens")) == 8 * BLOCK
        assert max(windows("prefill_window_tokens")) == 6 * BLOCK
        assert min(windows("decode_window_tokens")) == BLOCK
        assert eng.stats["decode_window_tokens"] == sum(
            windows("decode_window_tokens"))
    finally:
        eng.shutdown()
    # the long request's tokens are the contiguous-cache path's
    want = np.asarray(llama_mod.generate(
        model, params, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=24, temperature=0.0))[0, 90:].tolist()
    assert out[-24:] == want
