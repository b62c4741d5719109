"""Radix-tree prefix KV cache tests (serve/prefix_cache.py) and its
engine integration.

Two layers, mirroring how the reference tests its object store:
pure-host tests drive PrefixCache + BlockAllocator directly (refcount,
LRU, dedupe, invariants — no device), and engine tests prove the
user-visible contract: cache-hit decode is TOKEN-IDENTICAL to a cold
prefill, the pool always balances (free + cached == usable), eviction
reclaims cache residency before admission fails, and preemption never
frees a shared page.
"""
import dataclasses
import types

import jax.numpy as jnp
import pytest

from ray_tpu.models.kv_cache import BlockAllocator
from ray_tpu.models.llama import Llama, generate, llama_tiny
from ray_tpu.serve.engine import LLMEngine, _Slot
from ray_tpu.serve.prefix_cache import PrefixCache

import numpy as np


@pytest.fixture(scope="module")
def tiny_model():
    # fp32 so paged vs contiguous decode agree bit-for-bit (see
    # test_llm_engine.py).
    import jax
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def _reference_completion(model, params, prompt, n):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _drain(eng):
    while eng.step():
        pass


def _balanced(eng):
    """Pool conservation: every usable page is either free or cached
    (no slot holds any after a drain)."""
    return (eng.alloc.n_free + eng.prefix_cache.cached_pages
            == eng.alloc.n_pages - 1)


# ------------------------------------------------------- pure cache


def test_match_insert_roundtrip():
    alloc = BlockAllocator(16)
    pc = PrefixCache(alloc, page_size=4)
    toks = list(range(1, 11))              # 10 tokens: 2 full pages
    pages = alloc.alloc(2)
    pc.insert(toks, pages, n_shared=0)
    assert pc.cached_pages == 2

    got, n = pc.match(toks)
    assert got == pages and n == 8         # page-granular, not 10
    assert [pc.ref_of(p) for p in pages] == [1, 1]
    # shorter query matches only the covered prefix
    got2, n2 = pc.match(toks[:6])
    assert got2 == pages[:1] and n2 == 4
    # divergent second chunk matches one page
    got3, n3 = pc.match(toks[:4] + [99, 99, 99, 99])
    assert got3 == pages[:1] and n3 == 4
    pc.release(got + got2 + got3)
    assert [pc.ref_of(p) for p in pages] == [0, 0]
    pc.check_invariants()


def test_refcount_blocks_eviction():
    alloc = BlockAllocator(16)
    pc = PrefixCache(alloc, page_size=4)
    pages = alloc.alloc(2)
    pc.insert(list(range(8)), pages, n_shared=0)
    held, _ = pc.match(list(range(8)))
    assert pc.evict(10) == 0               # everything referenced
    assert pc.cached_pages == 2
    pc.release(held)
    assert pc.evict(10) == 2               # now reclaimable
    assert pc.cached_pages == 0
    assert alloc.n_free == 15
    pc.check_invariants()


def test_lru_evicts_leaf_first_oldest_first():
    alloc = BlockAllocator(16)
    pc = PrefixCache(alloc, page_size=2)
    # two chains sharing a root page: root -> a -> a2, root -> b
    root_a_a2 = alloc.alloc(3)
    pc.insert([1, 2, 3, 4, 5, 6], root_a_a2, n_shared=0)
    # second sequence matched the root, computed one private page (b):
    # exactly what the engine hands insert at retirement
    held, n = pc.match([1, 2, 9, 9])
    assert held == root_a_a2[:1] and n == 2
    b = alloc.alloc(1)
    pc.insert([1, 2, 9, 9], held + b, n_shared=1)
    assert pc.ref_of(root_a_a2[0]) == 0    # insert released the ref
    # first eviction: the LRU LEAF (a2) — never the shared root, even
    # though the root is older than everything
    assert pc.evict(1) == 1
    assert root_a_a2[2] not in pc._nodes
    assert root_a_a2[0] in pc._nodes
    # next: leaf a (branch a older than b)
    assert pc.evict(1) == 1
    assert root_a_a2[1] not in pc._nodes
    assert b[0] in pc._nodes
    # root only evictable once childless
    assert pc.evict(2) == 2
    assert pc.cached_pages == 0
    assert alloc.n_free == 15
    pc.check_invariants()


def test_insert_dedupes_duplicate_compute():
    """Two sequences miss on the same prefix concurrently and both
    compute it; the second insert must keep the incumbent page (other
    readers may reference it) and recycle its own."""
    alloc = BlockAllocator(16)
    pc = PrefixCache(alloc, page_size=4)
    first = alloc.alloc(1)
    dup = alloc.alloc(1)
    pc.insert([1, 2, 3, 4], first, n_shared=0)
    free_before = alloc.n_free
    pc.insert([1, 2, 3, 4], dup, n_shared=0)
    assert pc.cached_pages == 1
    assert pc._nodes[first[0]].chunk == (1, 2, 3, 4)
    assert alloc.n_free == free_before + 1     # dup went back
    pc.check_invariants()


def test_release_errors():
    alloc = BlockAllocator(16)
    pc = PrefixCache(alloc, page_size=4)
    pages = alloc.alloc(1)
    pc.insert([1, 2, 3, 4], pages, n_shared=0)
    with pytest.raises(RuntimeError):
        pc.release([pages[0]])                 # never matched: underflow
    with pytest.raises(RuntimeError):
        pc.release([13])                       # not cache-held
    held, _ = pc.match([1, 2, 3, 4])
    pc.release(held)                           # balanced: fine
    pc.check_invariants()


def test_account_and_stats():
    alloc = BlockAllocator(16)
    pc = PrefixCache(alloc, page_size=4)
    pc.account(24, 8)
    s = pc.stats()
    assert s["hit_tokens"] == 24 and s["miss_tokens"] == 8
    assert s["hit_rate"] == 0.75


# --------------------------------------------------- engine: parity


def test_cache_hit_output_token_identical(tiny_model):
    """THE correctness contract: a request admitted off cached prefix
    KV must produce exactly the tokens a cold prefill produces."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, prefix_cache=True)
    prefix = list(range(1, 25))                # 3 full pages
    p1 = prefix + [30, 31]
    p2 = prefix + [40, 41, 42]
    w1 = _reference_completion(model, params, p1, 10)
    w2 = _reference_completion(model, params, p2, 10)
    h1 = eng.submit(p1, max_new_tokens=10)
    _drain(eng)
    assert eng.stats["cache_hit_tokens"] == 0  # cold
    h2 = eng.submit(p2, max_new_tokens=10)
    _drain(eng)
    assert h1.result() == w1
    assert h2.result() == w2                   # hit == cold, exactly
    assert eng.stats["cache_hit_tokens"] == 24
    assert eng.stats["cache_hit_admissions"] == 1
    assert ("cache_hit", (0, 24)) in list(eng.sched_trace)
    assert _balanced(eng)
    eng.prefix_cache.check_invariants()


def test_fully_cached_prompt_boundary_copy(tiny_model):
    """An exact page-aligned repeat: every prompt page is cached, yet
    the model still needs the last position's logits — the engine
    copies the boundary page and re-prefills one token. Output must
    still match the cold run."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, prefix_cache=True)
    p = list(range(1, 17))                     # exactly 2 pages
    w = _reference_completion(model, params, p, 8)
    h1 = eng.submit(p, max_new_tokens=8)
    _drain(eng)
    h2 = eng.submit(p, max_new_tokens=8)       # 100% cached
    _drain(eng)
    assert h1.result() == w
    assert h2.result() == w
    # matched both pages but paid one back for the boundary re-prefill
    assert eng.stats["cache_hit_tokens"] == 15
    assert _balanced(eng)
    eng.prefix_cache.check_invariants()


def test_hit_skips_prefill_compute(tiny_model):
    """The point of the cache: prefill dispatches only pay for the
    uncached suffix."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=4, prefill_chunk=8,
                    prefix_cache=True)
    prefix = list(range(1, 33))                # 4 pages: 4 chunks cold
    h1 = eng.submit(prefix + [50], max_new_tokens=4)
    _drain(eng)
    cold_tokens = eng.stats["prefill_tokens"]
    h2 = eng.submit(prefix + [60, 61], max_new_tokens=4)
    _drain(eng)
    assert eng.stats["prefill_tokens"] - cold_tokens == 2  # suffix only
    assert h1.result() == _reference_completion(
        model, params, prefix + [50], 4)
    assert h2.result() == _reference_completion(
        model, params, prefix + [60, 61], 4)


# ---------------------------------------------------- engine: churn


def test_churn_returns_pool_to_baseline(tiny_model):
    """Submit/retire loops: pages migrate between slots, the tree and
    the free list, but every usable page is always accounted for."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, prefix_cache=True)
    prefix = list(range(1, 17))
    for i in range(6):
        tail = [100 + i, 200 + i]
        h = eng.submit(prefix + tail, max_new_tokens=4)
        _drain(eng)
        assert h.result() == _reference_completion(
            model, params, prefix + tail, 4)
        assert _balanced(eng), (i, eng.alloc.n_free,
                                eng.prefix_cache.stats())
        assert eng.prefix_cache.evictable_pages() \
            == eng.prefix_cache.cached_pages   # no refs leak
        eng.prefix_cache.check_invariants()
    assert eng.stats["cache_hit_tokens"] == 5 * 16


def test_eviction_under_pressure_before_admission_fails(tiny_model):
    """Pool small enough that cached pages crowd out a new admission:
    the engine must reclaim LRU refcount-0 cache pages instead of
    rejecting/preempting."""
    model, params = tiny_model
    # 7 usable pages; each retired request caches its full prompt
    # pages, so a few distinct prompts fill the pool with cache.
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=8, chunk=4, prefix_cache=True)
    for i in range(3):
        p = [10 * (i + 1) + j for j in range(16)]   # 2 pages each
        h = eng.submit(p, max_new_tokens=4)
        _drain(eng)
        assert h.result() == _reference_completion(model, params, p, 4)
        assert _balanced(eng)
    assert eng.prefix_cache.cached_pages >= 4
    # next distinct request needs 3 pages; free list alone can't cover
    assert eng.alloc.n_free < 3
    p = [77 + j for j in range(17)]
    h = eng.submit(p, max_new_tokens=4)
    _drain(eng)
    assert h.result() == _reference_completion(model, params, p, 4)
    assert eng.prefix_cache.evictions > 0
    assert eng.prefix_cache.stats()["evictions"] > 0
    assert _balanced(eng)
    eng.prefix_cache.check_invariants()


def test_preemption_never_frees_shared_pages(tiny_model):
    """A cache-hit slot preempted MID-PREFILL: its shared pages must
    stay in the tree (refs back to 0, never on the free list), its
    private pages return to the allocator, and the recomputed request
    still matches the reference."""
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=64, chunk=2, prefill_chunk=8,
                    prefix_cache=True)
    prefix = list(range(1, 17))                # 2 pages
    h0 = eng.submit(prefix + [90], max_new_tokens=4)
    _drain(eng)
    assert h0.result() == _reference_completion(
        model, params, prefix + [90], 4)
    shared = [p for p in eng.prefix_cache._nodes][:2]

    long_tail = prefix + list(range(200, 224))     # 24-token suffix
    want = _reference_completion(model, params, long_tail, 4)
    h = eng.submit(long_tail, max_new_tokens=4)
    eng.step()                                 # admit + first chunk
    with eng._lock:
        ixs = [i for i, s in enumerate(eng.slots)
               if s is not None and s.shared > 0]
        assert ixs, "expected a mid-prefill cache-hit slot"
        slot = eng.slots[ixs[0]]
        assert 0 < slot.prefilled < len(long_tail)
        held = slot.pages[:slot.shared]
        assert all(eng.prefix_cache.ref_of(p) == 1 for p in held)
        eng._preempt_locked(ixs[0])
        # shared pages survived the preemption, unreferenced
        assert all(p in eng.prefix_cache._nodes for p in held)
        assert all(eng.prefix_cache.ref_of(p) == 0 for p in held)
        assert all(p not in eng.alloc._free_set for p in held)
    assert eng.stats["preemptions"] == 1
    _drain(eng)                                # re-admits, re-matches
    assert h.result() == want
    assert _balanced(eng)
    eng.prefix_cache.check_invariants()
    assert set(shared) <= set(eng.prefix_cache._nodes)


# ------------------------------------------------ engine: invariants


def test_cow_check_rejects_shared_page_writes(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=1, page_size=8,
                    n_pages=16, chunk=2, prefix_cache=True)
    slot = _Slot(req=types.SimpleNamespace(rid=7), pages=[1, 2, 3],
                 pos=16, cur=None, admit_seq=0,
                 prompt=list(range(20)), prefilled=16, shared=2)
    eng._check_cow_locked(slot, 16)            # frontier: legal
    with pytest.raises(RuntimeError, match="COW violation"):
        eng._check_cow_locked(slot, 15)        # inside shared page 1
    with pytest.raises(RuntimeError, match="COW violation"):
        eng._check_cow_locked(slot, 0)


def test_prefix_metrics_exported(tiny_model):
    model, params = tiny_model
    from ray_tpu.util import metrics
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, prefix_cache=True)
    prefix = list(range(1, 17))
    eng.submit(prefix + [9], max_new_tokens=4)
    _drain(eng)
    eng.submit(prefix + [8], max_new_tokens=4)
    _drain(eng)
    text = metrics.prometheus_text()
    assert "serve_prefix_cache_hit_tokens" in text
    assert "serve_prefix_cache_miss_tokens" in text
    assert "serve_prefix_cache_pages" in text
    st = eng.prefix_stats()
    assert st["hit_tokens"] == 16
    assert st["cached_pages"] >= 2


def test_cache_off_by_default(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4)
    assert eng.prefix_cache is None
    assert eng.prefix_stats() is None
    h = eng.submit([1, 2, 3], max_new_tokens=4)
    _drain(eng)
    assert h.result() == _reference_completion(model, params,
                                               [1, 2, 3], 4)
    # legacy accounting: everything back on the free list
    assert eng.alloc.n_free == eng.alloc.n_pages - 1


# ------------------------------------------- digest advertisement cap


def _digest_fixture():
    """Three disjoint chains: A is 4 pages deep, B is 2, C is 1."""
    from ray_tpu.serve.prefix_cache import path_hashes
    alloc = BlockAllocator(32)
    pc = PrefixCache(alloc, page_size=4)
    A = [1] * 4 + [2] * 4 + [3] * 4 + [4] * 4
    B = [5] * 4 + [6] * 4
    C = [7] * 4
    for toks, n in ((A, 4), (B, 2), (C, 1)):
        pc.insert(toks, alloc.alloc(n), n_shared=0)
    hA = frozenset(path_hashes(A, 4))
    hB = frozenset(path_hashes(B, 4))
    hC = frozenset(path_hashes(C, 4))
    return pc, hA, hB, hC


def test_digest_cap_is_prefix_closed_longest_first():
    """The bounded advertisement keeps whole root->node paths,
    longest prefix first, backfilling with shorter paths that still
    fit — never a deep node without its ancestors (which affinity
    matching, walking root-first, could not see at all)."""
    from ray_tpu.serve.prefix_cache import path_hashes
    pc, hA, hB, hC = _digest_fixture()
    assert pc.digest() == hA | hB | hC            # uncapped: all
    assert pc.digest(7) == hA | hB | hC           # cap >= nodes: all
    assert pc.digest(4) == hA                     # deepest path wins
    # budget 5: B's 2-hash path no longer fits after A; the 1-hash
    # C path backfills instead of wasting the slot
    assert pc.digest(5) == hA | hC
    assert pc.digest(6) == hA | hB                # next-deepest fits
    assert pc.digest(0) == frozenset()
    # every capped advertisement is PREFIX-CLOSED: each kept hash's
    # whole root path is kept too
    chains = {tuple(path_hashes(t, 4)) for t in
              ([1] * 4 + [2] * 4 + [3] * 4 + [4] * 4,
               [5] * 4 + [6] * 4, [7] * 4)}
    for limit in range(8):
        d = pc.digest(limit)
        assert len(d) <= limit
        for chain in chains:
            for i, h in enumerate(chain):
                if h in d:
                    assert set(chain[:i]) <= d, (
                        f"limit {limit}: hash at depth {i} kept "
                        f"without its ancestors")


def test_digest_cap_prefers_hotter_chain_on_depth_tie():
    from ray_tpu.serve.prefix_cache import path_hashes
    alloc = BlockAllocator(16)
    pc = PrefixCache(alloc, page_size=4)
    D = [11] * 4 + [12] * 4
    E = [13] * 4 + [14] * 4
    pc.insert(D, alloc.alloc(2), n_shared=0)
    pc.insert(E, alloc.alloc(2), n_shared=0)
    # equal depth; E inserted later so it starts hotter
    assert pc.digest(2) == frozenset(path_hashes(E, 4))
    # touching D (a cache hit) makes it the hotter chain
    got, _ = pc.match(D)
    pc.release(got)
    assert pc.digest(2) == frozenset(path_hashes(D, 4))


def test_engine_load_report_bounds_digest(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, max_slots=2, page_size=8,
                    n_pages=32, chunk=4, temperature=0.0,
                    prefix_cache=True, prefix_digest_max=2)
    try:
        h = eng.submit(list(range(1, 41)), max_new_tokens=2)
        _drain(eng)
        h.result()
        assert eng.prefix_cache.cached_pages > 2
        rpt = eng.load_report()
        digest = rpt["prefix_digest"]
        assert len(digest) == 2
        # the bounded digest is the prompt's LEADING pages — the
        # prefix-closed head, not an arbitrary sample
        from ray_tpu.serve.prefix_cache import path_hashes
        assert digest == frozenset(
            path_hashes(list(range(1, 41)), 8)[:2])
    finally:
        eng.shutdown()
