"""Mellum 2 on the normal path (ray_tpu.models.mellum through LLMEngine
and LlamaDeployment) against the plain float32 reference
(benchmarks/reference/mellum2.py: no cache, no ring, every expert on
every token, the sliding mask and YaRN written out from the equations),
on the CPU at ``mellum_tiny``: two periods of (sliding, sliding,
sliding, full), a window of 12, YaRN over 32 original positions, 8
experts of which 3 a token. The first model whose layers keep caches of
TWO SIZES: a ring a slot in six layers, K/V pages in two.

Tolerances. Both sides compute in float32 on the same weights and
differ in the order of their sums and in the FORM of both attentions
(the program reads a ring by its indices' ages and the pages a block at
a time; the reference masks one row of scores a query): logits of the
order of 1 agree to rtol 1e-4 / atol 2e-5, as the other families' do.
Each wrong rule below moves logits by a thousand times that or more.
The engine's tokens are held to the reference's full forward pass
teacher-forced: at every generated position where the reference's
top-2 margin exceeds ten times the rtol of the logits, the engine's
token is the reference's argmax.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.kv_cache import (KIND_KV, KIND_SLIDING, SlidingRing,
                                     export_page_bytes, init_kv_pool,
                                     kv_layer_store, kv_layer_view,
                                     kv_pool_page_bytes, layer_kinds,
                                     page_cols_from_bytes,
                                     sliding_bytes_per_slot,
                                     sliding_ring_len,
                                     state_bytes_per_slot)
from ray_tpu.models.mellum import (FULL, SLIDING, Mellum, mellum2_12b,
                                   mellum_param_count, mellum_tiny,
                                   rope_by_type)
from ray_tpu.ops.paged_attention import (PagedShapeError, ring_append,
                                         ring_attention)
from ray_tpu.serve.engine import LLMEngine

RTOL, ATOL = 1e-4, 2e-5
PAGE, CHUNK = 4, 16                 # a ring of 12 + 16 + 4 = 32 positions


def _family():
    from benchmarks import common
    return common.load_family("mellum2", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights, then every norm's scale
    away from one so that a scale left out shows."""
    from benchmarks import weights
    model = Mellum(cfg)
    params = _family().init_params(weights.param_shapes(model), seed)
    rng = np.random.default_rng(seed + 1)

    def move(path, leaf):
        if "scale" in jax.tree_util.keystr(path):
            return leaf * (1.0 + 0.3 * rng.standard_normal(
                leaf.shape)).astype(np.float32)
        return leaf
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = mellum_tiny(dtype=jnp.float32)
    model, params = _seeded(cfg)
    return cfg, model, params


@pytest.fixture(params=["form", "kernel"])
def ring_form(request, monkeypatch):
    """The sliding layers through ``ring_append`` + ``ring_attention``
    (what the CPU runs) and through the Pallas kernel of
    ops/ring_window_attention.py in interpret mode, steered there as
    the chip's rule would (float32 here, exactly): the step programs
    are built anew so that none traced under the other form is
    reused."""
    from ray_tpu.ops import ring_window_attention as rw
    from ray_tpu.serve import step_programs
    programs = (step_programs._jit_prefill, step_programs._jit_decode)
    if request.param == "kernel":
        calls = []
        monkeypatch.setattr(rw, "applies", lambda *a: True)
        kernel = rw.ring_window_kernel
        monkeypatch.setattr(
            rw, "ring_window_kernel", lambda *a, **kw: calls.append(
                a[0].shape) or kernel(*a, interpret=True, **kw))
        for program in programs:
            program.cache_clear()
    yield request.param
    if request.param == "kernel":
        assert calls, "the kernel was never traced"
        for program in programs:
            program.cache_clear()


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(1, 255, size=shape)


def _reference(params, ids, cfg, **control):
    fam = _family()
    return np.asarray(fam.reference_forward(
        fam.reference_weights(params, cfg), jnp.asarray(ids, jnp.int32),
        cfg, **control))


def _held_to_the_reference(params, cfg, prompt, out, least=None):
    """The teacher-forced rule of the module docstring."""
    P, G = len(prompt), len(out)
    logits = _reference(params, [list(prompt) + list(out)], cfg)[0]
    steps = logits[P - 1:P - 1 + G]
    top2 = np.sort(steps, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 10 * RTOL * np.abs(steps).max()
    assert decisive.sum() >= (G * 2 // 3 if least is None else least)
    assert (steps.argmax(-1)[decisive] == np.asarray(out)[decisive]).all()


def _drive(eng, max_rounds=5000):
    for _ in range(max_rounds):
        if not eng.step():
            return
    raise AssertionError("the engine did not quiesce")


def _engine(tiny, **kw):
    _cfg, model, params = tiny
    opts = dict(max_slots=4, page_size=PAGE, n_pages=200, chunk=4,
                prefill_chunk=CHUNK, temperature=0.0, seed=0)
    opts.update(kw)
    return LLMEngine(model, params, **opts)


def _rounds(eng):
    return [e[5] for e in eng.events.snapshot() if e[2] == "round"]


# ----------------------------------------------------- the model itself

def test_forward_matches_the_reference(tiny):
    """The cache-less forward pass, 150 positions: twelve windows and
    past YaRN's 32 original positions."""
    cfg, model, params = tiny
    ids = _ids((2, 150))
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    want = _reference(params, ids, cfg)
    assert got.shape == want.shape == (2, 150, 256)
    np.testing.assert_allclose(np.asarray(got), want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("wrong", [
    dict(sliding_window=11),
    dict(layer_types=(FULL,) * 8),
    dict(layer_types=(SLIDING,) * 8),
    dict(yarn_attention_factor=1.0),
    dict(yarn_factor=1.0),
    dict(norm_topk_prob=False),
    dict(num_experts_per_tok=2)],
    ids=["window_one_short", "every_layer_full", "every_layer_sliding",
         "no_attention_factor", "no_yarn", "gates_not_renormalised",
         "two_experts_a_token"])
def test_each_declared_rule_shows(tiny, wrong):
    """A program that read one declared rule differently is far outside
    the tolerance that holds the right one."""
    cfg, _model, params = tiny
    ids = _ids((1, 120), seed=3)
    want = _reference(params, ids, cfg)
    got, _ = jax.jit(Mellum(dataclasses.replace(cfg, **wrong)).apply)(
        params, jnp.asarray(ids, jnp.int32))
    assert np.abs(np.asarray(got) - want).max() > 1e3 * ATOL


@pytest.mark.parametrize("control", [
    dict(sliding_as_full=True), dict(window=11),
    dict(plain_full_rope=True), dict(lower_precision=True)],
    ids=["sliding_as_full", "window_one_short", "full_rope_without_yarn",
         "lower_precision"])
def test_the_reference_shows_its_controls(tiny, control):
    """The four controls the cell's ``correct`` must read FALSE under,
    each as a failing case at this size: a reference that attends a
    sliding layer as a full one, takes the window one key short,
    rotates the full layers without YaRN or rounds every matrix to
    float8 e4m3 is far from what the program computes."""
    cfg, model, params = tiny
    ids = _ids((1, 120), seed=4)
    got, _ = jax.jit(model.apply)(params, jnp.asarray(ids, jnp.int32))
    got = np.asarray(got)
    np.testing.assert_allclose(got, _reference(params, ids, cfg),
                               rtol=RTOL, atol=ATOL)
    off = _reference(params, ids, cfg, **control)
    assert np.abs(got - off).max() > 1e3 * ATOL
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, off, rtol=RTOL, atol=ATOL)


def test_yarn_at_the_published_numbers():
    """The full layers' frequencies and factor at the published numbers
    against a table computed from the equations (head 128, theta 5e5,
    factor 16 over 8,192 original positions, beta 32 and 1): dimensions
    0-18 keep their frequency, 35-63 a sixteenth of it, a linear ramp
    between; the sliding layers' are plain."""
    cfg = mellum2_12b()
    d = lambda r: 64 * math.log(8192 / (2 * math.pi * r)) / math.log(5e5)
    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (18, 35)
    i = np.arange(64)
    plain = 5e5 ** (-2.0 * i / 128)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    want = plain * ((1 - ramp) + ramp / 16)
    inv, factor = rope_by_type(cfg, FULL)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    assert factor == 1.2772588722239782 == pytest.approx(
        0.1 * math.log(16) + 1, rel=1e-15)
    np.testing.assert_allclose(np.asarray(inv[:19]), plain[:19], rtol=2e-6)
    np.testing.assert_allclose(np.asarray(inv[35:]), plain[35:] / 16,
                               rtol=2e-6)
    inv, factor = rope_by_type(cfg, SLIDING)
    np.testing.assert_allclose(np.asarray(inv), plain, rtol=2e-6)
    assert factor == 1.0
    # the plain reference's own table is the same one
    from benchmarks.reference import mellum2 as ref
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inv_freq(128, 5e5, 16, 8192, 32, 1)), want,
        rtol=2e-6)


def test_layer_kinds_and_the_published_count():
    cfg = mellum2_12b()
    assert cfg.layer_kinds == (KIND_SLIDING,) * 3 + (KIND_KV,) + \
        cfg.layer_kinds[4:] and len(cfg.layer_kinds) == 28
    assert cfg.layer_kinds.count(KIND_KV) == 7
    assert cfg.n_heads * cfg.head_dim == 4096 != cfg.dim
    # 12B-A2.5B, and the cut's 3.795 B
    assert round(mellum_param_count(cfg) / 1e9, 2) == 12.15
    assert round(mellum_param_count(cfg, 8) / 1e9, 2) == 2.44
    cut = mellum2_12b(n_layers=8)
    assert cut.layer_kinds == (KIND_SLIDING,) * 3 + (KIND_KV,) + \
        (KIND_SLIDING,) * 3 + (KIND_KV,)
    assert round(mellum_param_count(cut) / 1e9, 3) == 3.795
    with pytest.raises(ValueError, match="layer_types"):
        mellum2_12b(n_layers=32)


# ------------------------------------------------------------- the ring

def test_the_rings_length_rule():
    """The window and one prefill chunk in whole pages, and one page:
    1,344 at the published window, a chunk of 256 and pages of 64."""
    assert sliding_ring_len(mellum2_12b(), 64, 256) == 1344
    assert sliding_ring_len(mellum2_12b(), 64, 64) == 1152
    tiny = mellum_tiny()
    assert sliding_ring_len(tiny, PAGE, CHUNK) == 32
    assert sliding_ring_len(tiny, 8, 32) == 56
    from ray_tpu.models.llama import llama_tiny
    assert sliding_ring_len(llama_tiny(), 8, 32) == 0


def test_sliding_bytes_do_not_grow_with_pages_or_context(tiny):
    """A sliding layer's entry is the same whatever ``n_pages``; a
    page's bytes count the full layers alone; a slot's count the rings;
    a shipped page's frames skip the sliding layers by kind."""
    cfg, _model, _params = tiny
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    small = init_kv_pool(cfg, 16, PAGE, n_slots=4, ring_len=L)
    large = init_kv_pool(cfg, 400, PAGE, n_slots=4, ring_len=L)
    for kind, a, b in zip(layer_kinds(cfg), small, large):
        if kind == KIND_SLIDING:
            assert isinstance(a, SlidingRing) and a.k is not a.v
            assert a.k.shape == b.k.shape == (4, 2, L, 16) == a.v.shape
        else:
            assert a[0].shape == (16, PAGE, 2, 16)
            assert b[0].shape == (400, PAGE, 2, 16)
    assert kv_pool_page_bytes(cfg, PAGE) == 2 * 2 * PAGE * 2 * 16 * 4
    per_slot = 6 * 2 * L * 2 * 16 * 4
    assert sliding_bytes_per_slot(cfg, L) == per_slot
    assert state_bytes_per_slot(cfg, L) == per_slot
    assert state_bytes_per_slot(cfg) == 0
    paged = [e for e in small if not isinstance(e, SlidingRing)]
    blobs = export_page_bytes(paged, 3)
    assert [len(layer) for layer in blobs] == [2, 2]
    assert len(page_cols_from_bytes(cfg, PAGE, "fp", blobs)) == 2
    # at the published widths: 16.5 MB a slot, 4,096 B a token
    big = mellum2_12b(n_layers=8)
    assert sliding_bytes_per_slot(big, 1344) == 6 * 1344 * 2048
    assert kv_pool_page_bytes(big, 64) == 64 * 4096


def test_ring_append_writes_only_a_requests_real_tokens():
    """Padding behind a row's last real token, a row without a request
    (whose position is stale) and a row whose slot is out of range
    write nothing; a real token lands at position mod L in every KV
    head's ring."""
    L, KH, D = 8, 2, 4
    ring = jnp.zeros((3, KH, L, D))
    k = jnp.arange(2 * 3 * KH * D, dtype=jnp.float32).reshape(2, 3, KH, D) + 1
    valid = jnp.asarray([[True, True, False], [False, False, False]])
    rk, rv = ring_append(ring, ring, jnp.asarray([2, 1]),
                         jnp.asarray([7, 5]), k, 2 * k, valid)
    rk, rv = np.asarray(rk), np.asarray(rv)
    assert not rk[:2].any() and not rv[:2].any()        # slot 1 untouched
    np.testing.assert_array_equal(rk[2, :, 7], np.asarray(k[0, 0]))
    np.testing.assert_array_equal(rk[2, :, 0], np.asarray(k[0, 1]))
    np.testing.assert_array_equal(rv[2, :, 0], 2 * np.asarray(k[0, 1]))
    assert not rk[2, :, 1:7].any()                      # the padding
    rk2, _ = ring_append(ring, ring, jnp.asarray([3, 0]),
                         jnp.asarray([0, 0]), k, k,
                         jnp.ones((2, 3), bool))
    assert not np.asarray(rk2)[1:].any() and np.asarray(rk2)[0].any()
    # a decode call: row i is slot i
    rk3, _ = ring_append(ring, ring, None, jnp.asarray([9, 2, 0]),
                         k[:1, :1].repeat(3, 0), k[:1, :1].repeat(3, 0),
                         jnp.asarray([[True], [False], [True]]))
    rk3 = np.asarray(rk3)
    assert rk3[0, :, 1].any() and not rk3[1].any() and rk3[2, :, 0].any()


def test_the_ring_refuses_a_chunk_it_cannot_hold():
    ring = jnp.zeros((1, 2, 16, 4))
    q = jnp.zeros((1, 6, 4, 4))
    pos, valid = jnp.zeros((1,), jnp.int32), jnp.ones((1, 6), bool)
    ring_attention(q[:, :5], ring, ring, pos, valid[:, :5], 12)
    with pytest.raises(PagedShapeError, match="ring of at least 17"):
        ring_attention(q, ring, ring, pos, valid, 12)
    with pytest.raises(PagedShapeError, match="laps a ring"):
        ring_append(ring, ring, None, pos, jnp.zeros((1, 17, 2, 4)),
                    jnp.zeros((1, 17, 2, 4)), jnp.ones((1, 17), bool))
    with pytest.raises(PagedShapeError, match="does not fit"):
        ring_append(ring, ring, None, pos, jnp.zeros((1, 2, 3, 4)),
                    jnp.zeros((1, 2, 3, 4)), jnp.ones((1, 2), bool))


# ------------------------------------ the paged path against the reference

def _call(model, params, table, slots):
    @jax.jit
    def call(pool, chunk, pos, n_real):
        def valid():
            return jnp.arange(chunk.shape[1])[None] < n_real[:, None]
        views = [kv_layer_view(layer, table, slots, valid)
                 for layer in pool]
        logits, new = model.apply(params, chunk, kv_caches=views,
                                  cache_len=pos)
        return logits, [kv_layer_store(v) for v in new]
    return call


def test_paged_logits_match_the_reference(tiny, ring_form):
    """Chunked prefill of 600 tokens in chunks of 16 (eighteen turns of
    the 32-position ring, 150 pages of 4, across the 512-token edge of
    the page loop's first block and far past YaRN's 32 original
    positions), then six decode steps, through BOTH kinds of entry (the
    ring of slot 2 of 3, the K/V pages), against the plain reference's
    full forward pass, ON LOGITS."""
    cfg, model, params = tiny
    P, G = 600, 6
    ids = _ids((1, P + G), seed=6)
    want = _reference(params, ids, cfg)[0]
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    pool = init_kv_pool(cfg, 160, PAGE, n_slots=3, ring_len=L)
    table = jnp.asarray(1 + np.arange(155)[None], jnp.int32)
    call = _call(model, params, table, jnp.asarray([2], jnp.int32))
    got = []
    for start in list(range(0, P, CHUNK)) + list(range(P, P + G)):
        n = min(CHUNK, P - start) if start < P else 1
        chunk = jnp.asarray(ids[:, start:start + n], jnp.int32)
        if n < CHUNK and start < P:
            chunk = jnp.pad(chunk, ((0, 0), (0, CHUNK - n)))
        logits, pool = call(pool, chunk, jnp.asarray([start], jnp.int32),
                            jnp.asarray([n], jnp.int32))
        got.append(np.asarray(logits[0, :n]))
    np.testing.assert_allclose(np.concatenate(got), want, rtol=RTOL,
                               atol=ATOL)
    # the other slots' rings were never written
    for entry in pool:
        if isinstance(entry, SlidingRing):
            assert not np.asarray(entry.k[:2]).any()
            assert np.abs(np.asarray(entry.k[2])).max() > 0


def test_rows_of_different_lengths_in_one_call(tiny):
    """Three rows of one prefill call at different offsets and with
    different counts of real tokens (one mid-ring, one whose chunk
    wraps the ring's end, one a dummy that names no slot), each against
    the reference's logits of its own sequence."""
    cfg, model, params = tiny
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    lens = (41, 29)
    ids = [_ids((n,), seed=30 + n) for n in lens]
    want = [_reference(params, [row], cfg)[0] for row in ids]
    pool = init_kv_pool(cfg, 60, PAGE, n_slots=4, ring_len=L)
    table = np.zeros((3, 16), np.int32)
    table[0, :11] = 1 + np.arange(11)
    table[1, :8] = 20 + np.arange(8)
    call = _call(model, params, jnp.asarray(table),
                 jnp.asarray([3, 0, 4], jnp.int32))   # row 2: no slot
    done = [0, 0]
    got = [[], []]
    while min(d - n for d, n in zip(done, lens)) < 0:
        chunk = np.zeros((3, CHUNK), np.int32)
        n_real = np.zeros((3,), np.int32)
        for r in range(2):
            n = min(CHUNK - 3 * r, lens[r] - done[r])   # rows out of step
            chunk[r, :n] = ids[r][done[r]:done[r] + n]
            n_real[r] = n
        logits, pool = call(pool, jnp.asarray(chunk),
                            jnp.asarray(done + [977], jnp.int32),
                            jnp.asarray(n_real))
        for r in range(2):
            got[r].append(np.asarray(logits[r, :n_real[r]]))
            done[r] += int(n_real[r])
    for r in range(2):
        np.testing.assert_allclose(np.concatenate(got[r]), want[r],
                                   rtol=RTOL, atol=ATOL)


def test_a_stale_ring_is_never_visible(tiny, ring_form):
    """A slot whose rings a longer request filled (here: with values a
    thousand times a key's) serves a shorter one, whose logits are the
    reference's: an index this request has not written is masked by the
    row's own last position, whatever lies there."""
    cfg, model, params = tiny
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    pool = init_kv_pool(cfg, 40, PAGE, n_slots=2, ring_len=L)
    pool = [SlidingRing(jnp.full_like(e.k, 1e3), jnp.full_like(e.v, -1e3))
            if isinstance(e, SlidingRing) else e for e in pool]
    ids = _ids((1, 21), seed=8)
    table = jnp.asarray(1 + np.arange(8)[None], jnp.int32)
    call = _call(model, params, table, jnp.asarray([1], jnp.int32))
    got = []
    for start, n in ((0, 16), (16, 5)):
        chunk = jnp.pad(jnp.asarray(ids[:, start:start + n], jnp.int32),
                        ((0, 0), (0, CHUNK - n)))
        logits, pool = call(pool, chunk, jnp.asarray([start], jnp.int32),
                            jnp.asarray([n], jnp.int32))
        got.append(np.asarray(logits[0, :n]))
    np.testing.assert_allclose(np.concatenate(got),
                               _reference(params, ids, cfg)[0],
                               rtol=RTOL, atol=ATOL)


# ------------------------------------------------------ the paged engine

def test_mixed_rows_through_both_kinds_of_entry(tiny):
    """Prompts of 150, 7 and 70 tokens in a prefill call of four rows
    of chunks of 16: the longest crosses ten rounds and turns its ring
    four times, rows carry padding inside, and 12 tokens each are
    decoded."""
    cfg, _model, params = tiny
    eng = _engine(tiny)
    prompts = [_ids((n,), seed=10 + n).tolist() for n in (150, 7, 70)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drive(eng)
    rounds = _rounds(eng)
    assert max(r["prefill_rows"] for r in rounds) == 3
    for p, h in zip(prompts, handles):
        out = h.result()
        assert len(out) == 12
        _held_to_the_reference(params, cfg, p, out)
    assert sum(r.get("state_slots", 0) for r in rounds) == \
        eng.stats["state_slots"] > 0
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


def test_a_reused_slot_serves_a_shorter_request(tiny, ring_form):
    """One slot, two requests in turn: the second, shorter than the
    first, finds the first's keys all round its ring and must see none
    of them."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=1)
    first, second = _ids((90,), seed=20).tolist(), _ids((9,), 21).tolist()
    h1 = eng.submit(first, max_new_tokens=8)
    _drive(eng)
    h2 = eng.submit(second, max_new_tokens=10)
    _drive(eng)
    _held_to_the_reference(params, cfg, first, h1.result())
    _held_to_the_reference(params, cfg, second, h2.result())
    alone = _engine(tiny, max_slots=1)
    h = alone.submit(second, max_new_tokens=10)
    _drive(alone)
    assert h.result() == h2.result()


def test_preemption_recomputes_both_kinds_of_entry(tiny, ring_form):
    """A pool too small for two growing requests: the younger is
    evicted, its pages freed, and requeued with prompt + generated,
    prefilled again from position 0 (its rings written again from
    index 0) and gives the tokens it would have given alone."""
    cfg, _model, params = tiny
    # chunks of 8 make a ring of 24, which no write-back block of the
    # kernel's divides (the rule keeps the form there): the kernel's
    # case has chunks of 16 and the ring of 32
    small = dict(max_slots=2, page_size=4, n_pages=14, chunk=2,
                 prefill_chunk=8 if ring_form == "form" else 16)
    eng = _engine(tiny, **small)
    prompts = [_ids((12,), seed=40).tolist(), _ids((11,), 41).tolist()]
    handles = [eng.submit(p, max_new_tokens=22) for p in prompts]
    _drive(eng)
    assert eng.stats["preemptions"] > 0
    for p, h in zip(prompts, handles):
        alone = _engine(tiny, **dict(small, max_slots=1))
        ha = alone.submit(p, max_new_tokens=22)
        _drive(alone)
        assert h.result() == ha.result()
        _held_to_the_reference(params, cfg, p, h.result())
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


def test_more_clients_than_slots(tiny):
    """24 requests on 8 slots, every slot reused: each ends as the
    reference has it and nothing leaks."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=8, n_pages=8 * 20 + 1)
    prompts = [_ids((5 + (11 * i) % 50,), seed=100 + i).tolist()
               for i in range(24)]
    handles = [eng.submit(p, max_new_tokens=6 + i % 5)
               for i, p in enumerate(prompts)]
    _drive(eng)
    for i, (p, h) in enumerate(zip(prompts, handles)):
        out = h.result()
        assert len(out) == 6 + i % 5
        if i % 4 == 0:
            _held_to_the_reference(params, cfg, p, out, least=3)
    assert max(r["decode_riders"] for r in _rounds(eng)) > 4
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []
    assert eng.load_report()["state_bytes_in_use"] == 0


def test_a_slots_sliding_bytes_are_the_same_at_any_context(tiny):
    """``load_report()`` mid-flight with a context of 20 and with one
    of 180: the pages in use grow with the context, the state's bytes
    (the rings: ``sliding_bytes_per_slot`` a slot that holds a request)
    do not."""
    cfg, _model, _params = tiny
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    per_slot = sliding_bytes_per_slot(cfg, L)
    seen = {}
    for n in (20, 180):
        eng = _engine(tiny)
        assert eng.ring_len == L and eng.accounts.sliding_window == 12
        eng.submit(_ids((n,), seed=60).tolist(), max_new_tokens=12)
        reports = []
        while eng.step():
            reports.append(eng.load_report())
        busy = [r for r in reports if r["free_slots"] == 3]
        assert busy
        assert {r["state_bytes_in_use"] for r in busy} == {per_slot}
        assert {r["sliding_bytes_per_slot"] for r in busy} == {per_slot}
        assert busy[0]["state_bytes_total"] == 4 * per_slot
        assert busy[0]["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
        seen[n] = max(r["kv_bytes_in_use"] for r in busy)
    assert seen[180] > 6 * seen[20]
    # a model with pages only reports no sliding bytes
    from ray_tpu.models.llama import Llama, llama_tiny
    lm = Llama(llama_tiny(dtype=jnp.float32))
    lp = jax.jit(lm.init)(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    dense = LLMEngine(lm, lp, max_slots=2, page_size=8, n_pages=32)
    assert dense.load_report()["sliding_bytes_per_slot"] == 0
    assert dense.ring_len == 0


def test_decode_sliding_keys_by_hand(tiny):
    """The ``round`` event's ``decode_sliding_keys`` is the sum over
    the riders of their own contexts CUT AT THE WINDOW, beside
    ``decode_context_tokens``: a rider of 5 tokens decoded in
    dispatches of 4 reads 9, then 12 for good; one of 40 reads 12 from
    the start. A model without sliding layers has no such key."""
    eng = _engine(tiny, chunk=4)
    eng.submit(_ids((5,), seed=70).tolist(), max_new_tokens=13)
    _drive(eng)
    dec = [r for r in _rounds(eng) if r["decode_steps"]]
    pos, want = 5, []
    for r in dec:
        pos += r["decode_steps"]
        want.append(min(pos, 12))
    assert [r["decode_sliding_keys"] for r in dec] == want
    assert want[0] == 9 and want[-1] == 12
    assert eng.stats["decode_sliding_keys"] == sum(want)
    eng = _engine(tiny, chunk=4)
    eng.submit(_ids((5,), seed=70).tolist(), max_new_tokens=13)
    eng.submit(_ids((40,), seed=71).tolist(), max_new_tokens=13)
    _drive(eng)
    both = [r for r in _rounds(eng) if r["decode_riders"] == 2]
    assert both and both[-1]["decode_sliding_keys"] == 24
    assert both[-1]["decode_context_tokens"] > 2 * 24
    from ray_tpu.models.llama import Llama, llama_tiny
    lm = Llama(llama_tiny(dtype=jnp.float32))
    lp = jax.jit(lm.init)(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    dense = LLMEngine(lm, lp, max_slots=2, page_size=8, n_pages=32,
                      chunk=4)
    dense.submit([3, 4, 5, 6], max_new_tokens=6)
    _drive(dense)
    assert all("decode_sliding_keys" not in r for r in _rounds(dense))


def test_sliding_kernel_keys_by_hand(tiny, monkeypatch):
    """The ``round`` event's ``sliding_kernel_keys`` beside
    ``decode_sliding_keys``: 0 where the decode program holds the
    ``jax.numpy`` form (the CPU); where the kernel's rule says yes,
    every rider's WHOLE ring (the kernel's own block arithmetic:
    ``kernel_keys``), asked of the shapes the layer hands the kernel;
    in ``stats`` and ``load_report()`` too."""
    from ray_tpu.ops import ring_window_attention as rw
    cfg = tiny[0]
    eng = _engine(tiny, chunk=4)
    eng.submit(_ids((5,), seed=70).tolist(), max_new_tokens=13)
    _drive(eng)
    dec = [r for r in _rounds(eng) if r["decode_steps"]]
    assert dec and all(r["sliding_kernel_keys"] == 0 for r in dec)
    assert eng.load_report()["sliding_kernel_keys"] == 0
    asked = []
    monkeypatch.setattr(rw, "applies",
                        lambda *a: asked.append(a) or True)
    eng.submit(_ids((5,), seed=70).tolist(), max_new_tokens=13)
    eng.submit(_ids((40,), seed=71).tolist(), max_new_tokens=13)
    _drive(eng)
    after = [r for r in _rounds(eng) if r["decode_steps"]][len(dec):]
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    assert L == 32 and rw.kernel_keys(2, L) == 64
    assert after and max(r["decode_riders"] for r in after) == 2
    for r in after:
        assert r["sliding_kernel_keys"] == r["decode_riders"] * L
        assert r["sliding_kernel_keys"] >= r["decode_sliding_keys"]
    total = sum(r["sliding_kernel_keys"] for r in after)
    assert eng.stats["sliding_kernel_keys"] == total
    assert eng.load_report()["sliding_kernel_keys"] == total
    # the layer's own question: a decode step's queries and new keys,
    # one layer's rings as the pool stores them
    q, k, v, ring_k, ring_v, window = asked[0]
    assert (q.shape, k.shape, v.shape) == (
        (4, 1, cfg.n_heads, cfg.head_dim),
        (4, 1, cfg.n_kv_heads, cfg.head_dim),
        (4, 1, cfg.n_kv_heads, cfg.head_dim))
    assert ring_k.shape == ring_v.shape == (4, cfg.n_kv_heads, L,
                                            cfg.head_dim)
    assert window == cfg.sliding_window and q.dtype == ring_k.dtype


def test_the_scopes_by_layer_type_reach_both_programs(tiny):
    """``attn_sliding`` and ``attn_full`` with their parts inside, in
    the lowering of both step programs: what the benchmark's readers
    split a device trace by."""
    from ray_tpu.serve import step_programs
    cfg, model, params = tiny
    L = sliding_ring_len(cfg, PAGE, CHUNK)
    pool = init_kv_pool(cfg, 16, PAGE, n_slots=2, ring_len=L)
    i32 = jnp.int32
    key = jax.random.PRNGKey(0)
    table = jnp.zeros((2, 8), i32)
    decode = step_programs._jit_decode(model, 0.0, 8, 2, False, None).lower(
        params, pool, table, jnp.zeros((2,), i32), jnp.zeros((2,), i32),
        key, jnp.int32(1))
    prefill = step_programs._jit_prefill(model, 0.0, 2, False, None).lower(
        params, pool, jnp.zeros((2, CHUNK), i32), jnp.zeros((2,), i32),
        jnp.zeros((2,), i32), table, key, jnp.zeros((2,), i32))
    for lowered in (decode, prefill):
        text = lowered.as_text(debug_info=True)
        for scope in ("attn_sliding/ring_append", "attn_sliding/ring_scores",
                      "attn_sliding/ring_pv", "attn_full/kv_append",
                      "attn_full/kv_gather", "attn_full/attn_scores",
                      "attn_full/attn_pv", "moe_experts"):
            assert scope in text, scope


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("option", [
    dict(prefix_cache=True), dict(spec_len=2), dict(kv_dtype="int8"),
    dict(sharding=object())],
    ids=["prefix_cache", "spec_len", "int8", "sharding"])
def test_the_engine_refuses_what_cannot_serve_entries_that_age(tiny,
                                                               option):
    name = next(iter(option))
    with pytest.raises(ValueError, match=name + ".*ring of their window"):
        _engine(tiny, **option)


@pytest.mark.parametrize("option,name", [
    (dict(disaggregate=True), "disaggregate"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_len=3), "spec_len"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(tensor_parallel=2), "sharding")],
    ids=["disaggregate", "prefix_cache", "spec_len", "int8",
         "tensor_parallel"])
def test_the_deployment_refuses_at_construction(tiny, option, name):
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    with pytest.raises(ValueError, match=name + ".*ring of their window"):
        LlamaDeployment(config=cfg, params=params, **option)


def test_each_list_alone_holds_to_its_own_kind(tiny, monkeypatch):
    """The sliding entries' list refuses five options for this config
    and nothing for a model without such layers; the two older lists
    refuse nothing for this config: none leans on another."""
    from ray_tpu.models import kv_cache
    from ray_tpu.models.kimi_linear import kimi_linear_tiny
    from ray_tpu.models.kv_cache import KIND_SLIDING, refuse_unsupported
    from ray_tpu.models.llama import llama_tiny
    cfg, _model, _params = tiny
    options = ("prefix_cache", "spec_len", "kv_migration", "kv_dtype",
               "sharding")
    for option in options:
        with pytest.raises(ValueError,
                           match=f"{option}=True.*MellumConfig.*ring"):
            refuse_unsupported(cfg, **{option: True})
    refuse_unsupported(cfg, **dict.fromkeys(options, False))
    everything = dict.fromkeys(options, True)
    refuse_unsupported(llama_tiny(), **everything)
    with pytest.raises(ValueError) as other:
        refuse_unsupported(kimi_linear_tiny(), **everything)
    assert "ring" not in str(other.value)
    # with the sliding row lifted, the older rows refuse it nothing
    monkeypatch.setitem(kv_cache.KIND_REFUSALS, KIND_SLIDING,
                        (kv_cache.KIND_REFUSALS[KIND_SLIDING][0], {}))
    refuse_unsupported(cfg, **dict(everything, kv_dtype="int8"))


def test_kv_export_is_refused(tiny):
    eng = _engine(tiny)
    with pytest.raises(ValueError, match="kv_migration.*ring"):
        eng.kv_export_pages([1])


def test_the_static_cache_path_refuses_it(tiny):
    cfg, model, params = tiny
    caches = [(jnp.zeros((1, 16, 2, 16)),) * 2] * cfg.n_layers
    with pytest.raises(TypeError, match="ring a slot"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32), kv_caches=caches,
                    cache_len=0)


# ------------------------------------------------------------ serve.run

def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """ray_tpu.init() -> serve.run() of LlamaDeployment, as a user
    deploys it: no side script, no option that selects a path."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    assert cfg.model_class is Mellum
    assert not hasattr(cfg, "serving_rules")
    holder = {}

    @serve.deployment
    class WindowedLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=8, n_pages=64, prefill_chunk=32)
            holder["dep"] = self

    try:
        handle = serve.run(WindowedLLM.bind(), timeout_s=300)
        prompt = _ids((83,), seed=80).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=300)
        assert out[:83] == prompt and len(out) == 93
        _held_to_the_reference(params, cfg, prompt, out[83:])
        eng = holder["dep"].engine()
        assert eng.ring_len == sliding_ring_len(cfg, 8, 32) == 56
        report = eng.load_report()
        assert report["sliding_bytes_per_slot"] == \
            sliding_bytes_per_slot(cfg, 56)
        assert report["state_bytes_total"] == 4 * state_bytes_per_slot(
            cfg, 56)
        assert len(report["moe_expert_share"]) == 8
    finally:
        serve.shutdown()
