"""Int8 paged KV cache: quantization contract, scale lifecycle, and
engine-level tolerance parity.

Op level (ops/paged_attention.py): symmetric absmax int8 round-trips
within scale/254 per element, per-PAGE scales isolate magnitude across
page boundaries, the reset-on-offset-0 rule retires a freed page's
stale scale with no host bookkeeping, spec-rollback garbage past
``pos`` is precision-only (masked at read by the block gather, never
attended).

Engine level (serve/engine.py kv_dtype="int8"): deterministic given a
write history (same engine + load twice -> identical tokens; prefix
hits replay the SAME quantized bytes -> identical tokens), tolerance-
equal vs fp (token agreement gated at the same floor the kvq A/B
artifact records — quantized bytes are write-history dependent, see
docs/serving.md), spec accept-rate preserved, tp-sharded pools with
scale rows pinned alongside their heads, and the bytes view
(kv_pool_page_bytes -> BlockAllocator -> load_report -> gauge).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.kv_cache import (BlockAllocator, check_kv_dtype,
                                     init_kv_pool, kv_layer_store,
                                     kv_layer_view, kv_pool_page_bytes,
                                     PagedKVLayer)
from ray_tpu.models.llama import Llama, llama_tiny
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         dequantize_pages,
                                         paged_append,
                                         PagedShapeError)
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.faults import check_quiesced

KH, PG, D = 2, 8, 16

# jitted: eager, every scatter/gather of the int8 append compiles and
# dispatches on its own (shape errors still raise, at trace time)
paged_append = jax.jit(paged_append)


def _fresh(n_pages=8, B=1, max_pages=4):
    pk = jnp.zeros((n_pages, PG, KH, D), jnp.int8)
    pv = jnp.zeros((n_pages, PG, KH, D), jnp.int8)
    sk = jnp.zeros((n_pages, KH), jnp.float32)
    sv = jnp.zeros((n_pages, KH), jnp.float32)
    pt = jnp.asarray(
        np.arange(1, 1 + B * max_pages).reshape(B, max_pages),
        jnp.int32)
    return pk, pv, sk, sv, pt


def _kv(rng, B, T, scale=1.0):
    k = (rng.standard_normal((B, T, KH, D)) * scale).astype(np.float32)
    v = (rng.standard_normal((B, T, KH, D)) * scale).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v)


# ------------------------------------------------- quantize round-trip

def test_bulk_roundtrip_within_half_step():
    rng = np.random.default_rng(0)
    pk, pv, sk, sv, pt = _fresh()
    k, v = _kv(rng, 1, 2 * PG)            # fills pages 1 and 2
    pk, pv, sk, sv = paged_append(pk, pv, pt, jnp.zeros(1, jnp.int32),
                                  k, v, sk, sv)
    deq = np.asarray(dequantize_pages(pk, sk))
    ref = np.asarray(k)[0]                         # [T, KH, D]
    for page, lo in ((1, 0), (2, PG)):
        # int8 rounding error is at most half a quantization step:
        # scale (= page absmax) / 254 per element
        tol = np.asarray(sk)[page] / 254.0 + 1e-6  # [KH]
        err = np.abs(deq[page] - ref[lo:lo + PG])
        assert (err <= tol[None, :, None]).all()


def test_per_page_scales_isolate_magnitude():
    # A huge page must not destroy a small page's resolution: that is
    # the entire point of per-PAGE (not per-pool) scales.
    rng = np.random.default_rng(1)
    pk, pv, sk, sv, pt = _fresh()
    k_big, v_big = _kv(rng, 1, PG, scale=100.0)
    k_small, v_small = _kv(rng, 1, PG, scale=0.01)
    k = jnp.concatenate([k_big, k_small], axis=1)   # spans 2 pages
    v = jnp.concatenate([v_big, v_small], axis=1)
    pk, pv, sk, sv = paged_append(pk, pv, pt, jnp.zeros(1, jnp.int32),
                                  k, v, sk, sv)
    sk_np = np.asarray(sk)
    assert (sk_np[1] > 1.0).all()          # big page's absmax
    assert (sk_np[2] < 0.1).all()          # small page kept its own
    deq = np.asarray(dequantize_pages(pk, sk))
    small_ref = np.asarray(k_small)[0]
    err = np.abs(deq[2] - small_ref)
    # resolution follows the SMALL page's scale; under one shared
    # scale the error would be ~100/254, four orders worse
    assert err.max() <= sk_np[2].max() / 254.0 + 1e-7


def test_incremental_scale_matches_bulk_and_is_monotone():
    rng = np.random.default_rng(2)
    pk, pv, sk, sv, pt = _fresh()
    k, v = _kv(rng, 1, PG)
    bk, bv, bsk, bsv = paged_append(pk, pv, pt,
                                    jnp.zeros(1, jnp.int32), k, v,
                                    sk, sv)
    ik, iv, isk, isv = pk, pv, sk, sv
    last = np.zeros((KH,))
    for t in range(PG):
        ik, iv, isk, isv = paged_append(
            ik, iv, pt, jnp.full((1,), t, jnp.int32),
            k[:, t:t + 1], v[:, t:t + 1], isk, isv)
        cur = np.asarray(isk)[1]
        assert (cur >= last - 1e-7).all()  # monotone while page live
        last = cur
    # same tokens -> same final absmax, both build orders
    np.testing.assert_allclose(np.asarray(isk), np.asarray(bsk),
                               rtol=1e-6)
    # BYTES may differ (write-history dependent re-rounding: the
    # incremental build re-codes earlier tokens at each scale growth,
    # double-rounding them) but values stay within one extra step
    deq_b = np.asarray(dequantize_pages(bk, bsk))[1]
    deq_i = np.asarray(dequantize_pages(ik, isk))[1]
    step = np.asarray(bsk)[1][None, :, None] / 127.0
    assert (np.abs(deq_b - deq_i) <= 1.5 * step + 1e-7).all()


def test_scale_resets_on_offset_zero_rewrite():
    # Allocator reuses page ids: the first write a fresh LOGICAL page
    # receives is always at offset 0, which must retire the previous
    # owner's scale — no host-side bookkeeping exists to do it.
    rng = np.random.default_rng(3)
    pk, pv, sk, sv, pt = _fresh()
    k_big, v_big = _kv(rng, 1, PG, scale=50.0)
    pk, pv, sk, sv = paged_append(pk, pv, pt, jnp.zeros(1, jnp.int32),
                                  k_big, v_big, sk, sv)
    assert np.asarray(sk)[1].max() > 10.0
    k_small, v_small = _kv(rng, 1, PG, scale=0.02)
    pk, pv, sk, sv = paged_append(pk, pv, pt, jnp.zeros(1, jnp.int32),
                                  k_small, v_small, sk, sv)
    sk_np = np.asarray(sk)
    assert sk_np[1].max() < 0.1            # old owner's scale is gone
    deq = np.asarray(dequantize_pages(pk, sk))[1]
    ref = np.asarray(k_small)[0]
    assert np.abs(deq - ref).max() <= sk_np[1].max() / 254.0 + 1e-7


def test_mid_page_append_grows_scale_without_reset():
    # A mid-page append (offset != 0) must KEEP earlier tokens
    # representable: scale grows, earlier bytes are re-coded.
    rng = np.random.default_rng(4)
    pk, pv, sk, sv, pt = _fresh()
    k1, v1 = _kv(rng, 1, 4, scale=0.5)
    pk, pv, sk, sv = paged_append(pk, pv, pt, jnp.zeros(1, jnp.int32),
                                  k1, v1, sk, sv)
    s1 = np.asarray(sk)[1].copy()
    k2, v2 = _kv(rng, 1, 4, scale=20.0)    # same page, offsets 4..7
    pk, pv, sk, sv = paged_append(pk, pv, pt,
                                  jnp.full((1,), 4, jnp.int32),
                                  k2, v2, sk, sv)
    s2 = np.asarray(sk)[1]
    assert (s2 >= s1 - 1e-7).all() and s2.max() > 5.0
    deq = np.asarray(dequantize_pages(pk, sk))[1, :4]
    ref = np.asarray(k1)[0]
    # earlier tokens survived the re-code at the grown scale: error
    # is one step of the NEW scale (coarser, but never garbage)
    assert np.abs(deq - ref).max() <= s2.max() / 127.0 + 1e-6


# --------------------------------------------- masking, shape errors

def _dense_ref_deq(q, pk, sk, pv, sv, pt, pos):
    """Dense softmax over the pool's fp view (an fp pool has no scales
    and is its own view)."""
    kg = np.asarray(pk if sk is None else dequantize_pages(pk, sk))
    vg = np.asarray(pv if sv is None else dequantize_pages(pv, sv))
    B, H, Dh = q.shape
    kh = kg.shape[2]
    L = pt.shape[1] * pk.shape[1]
    kq = kg[np.asarray(pt)].reshape(B, L, kh, Dh)
    vq = vg[np.asarray(pt)].reshape(B, L, kh, Dh)
    qg = np.asarray(q).reshape(B, kh, H // kh, Dh).astype(np.float32)
    s = np.einsum("bkrd,bskd->bkrs", qg, kq) / np.sqrt(Dh)
    valid = np.arange(L)[None] <= np.asarray(pos)[:, None]
    s = np.where(valid[:, None, None, :], s, -1e30)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bkrs,bskd->bkrd", p, vq).reshape(B, H, Dh)


def _window(q, pk, pv, pt, pos, sk, sv):
    """One decode step's attention through the block gather
    (q [B, H, D] -> [B, H, D])."""
    return np.asarray(jax.jit(_paged_window_attention)(
        q[:, None], pk, pv, sk, sv, pt, pos))[:, 0]


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_rollback_garbage_is_masked_and_precision_only(kv_dtype):
    # Spec rollback is a position clamp: rejected drafts stay in the
    # pool past ``pos``. In an int8 pool they may inflate the page
    # scale (precision) but in either pool they must never be
    # ATTENDED (correctness).
    rng = np.random.default_rng(5)
    pk, pv, sk, sv, pt = _fresh()
    scales = (sk, sv)
    if kv_dtype == "fp":
        pk, pv, scales = (pk.astype(jnp.float32),
                          pv.astype(jnp.float32), ())
    n_real = 6
    k, v = _kv(rng, 1, n_real)
    pk, pv, *scales = paged_append(pk, pv, pt, jnp.zeros(1, jnp.int32),
                                   k, v, *scales)
    kg, vg = _kv(rng, 1, 2, scale=30.0)    # rejected drafts, big
    pk2, pv2, *scales2 = paged_append(
        pk, pv, pt, jnp.full((1,), n_real, jnp.int32), kg, vg,
        *scales)
    sk, sv = scales or (None, None)
    sk2, sv2 = scales2 or (None, None)
    if kv_dtype == "int8":
        assert np.asarray(sk2)[1].max() > np.asarray(sk)[1].max()
    q = jnp.asarray(rng.standard_normal((1, 2 * KH, D)),
                    jnp.float32)
    pos = jnp.full((1,), n_real - 1, jnp.int32)
    out = _window(q, pk2, pv2, pt, pos, sk2, sv2)
    # reference over the (dequantized) REAL window of the garbage
    # pool: the garbage positions are masked, so only the re-rounding
    # of the real tokens (scale growth) can move the output
    ref = _dense_ref_deq(q, pk2, sk2, pv2, sv2, pt, pos)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    # and vs the garbage-free pool: bounded by one re-rounding step
    # (int8), nothing at all (fp: no scale for the garbage to grow)
    clean = _dense_ref_deq(q, pk, sk, pv, sv, pt, pos)
    assert np.abs(out - clean).max() < (
        0.5 if kv_dtype == "int8" else 1e-5)


def test_shape_errors():
    rng = np.random.default_rng(7)
    pk, pv, sk, sv, pt = _fresh()
    k, v = _kv(rng, 1, 2)
    pos = jnp.zeros(1, jnp.int32)
    with pytest.raises(PagedShapeError, match="without its per-page"):
        paged_append(pk, pv, pt, pos, k, v)     # int8 pool, no scales
    with pytest.raises(PagedShapeError, match="supplied together"):
        paged_append(pk, pv, pt, pos, k, v, sk, None)
    with pytest.raises(PagedShapeError):
        paged_append(pk, pv, pt, pos, k, v,     # bad scale shape
                     sk[..., None], sv[..., None])
    fpk = jnp.zeros(pk.shape, jnp.float32)
    with pytest.raises(PagedShapeError, match="int8"):
        paged_append(fpk, fpk, pt, pos, k, v, sk, sv)


# --------------------------------------------- pool shapes and bytes

def test_init_pool_shapes_and_layer_views():
    cfg = llama_tiny()
    pool = init_kv_pool(cfg, n_pages=16, page_size=8,
                        kv_dtype="int8")
    assert len(pool) == cfg.n_layers
    pk, pv, sk, sv = pool[0]
    assert pk.dtype == jnp.int8 and pv.dtype == jnp.int8
    assert pk.shape == (16, 8, cfg.n_kv_heads, cfg.head_dim)
    assert sk.shape == (16, cfg.n_kv_heads)
    assert sk.dtype == jnp.float32
    pt = jnp.zeros((2, 4), jnp.int32)
    cache = kv_layer_view(pool[0], pt)
    assert isinstance(cache, PagedKVLayer) and cache.quantized
    assert kv_layer_store(cache) == pool[0]
    fp = init_kv_pool(cfg, n_pages=16, page_size=8)
    assert len(fp[0]) == 2                 # fp pytree layout unchanged
    fpc = kv_layer_view(fp[0], pt)
    assert not fpc.quantized and fpc.scales_k is None
    with pytest.raises(ValueError):
        init_kv_pool(cfg, 16, 8, kv_dtype="int4")


def test_page_bytes_ratio_funds_the_capacity_claim():
    cfg = llama_tiny()                     # bf16 pages
    fp = kv_pool_page_bytes(cfg, 8, "fp")
    q = kv_pool_page_bytes(cfg, 8, "int8")
    # bf16: 2 bytes payload; int8: 1 byte + 2*KH fp32 scales/layer
    assert fp == cfg.n_layers * 2 * cfg.n_kv_heads * 8 * cfg.head_dim * 2
    assert q == cfg.n_layers * (
        2 * cfg.n_kv_heads * 8 * cfg.head_dim + 2 * cfg.n_kv_heads * 4)
    assert fp / q >= 1.9                   # the kvq A/B schema gate


def test_allocator_bytes_view():
    a = BlockAllocator(8)
    assert a.bytes_in_use() is None and a.bytes_total() is None
    a = BlockAllocator(8, page_bytes=100)
    assert a.bytes_total() == 800          # null page is real memory
    pages = a.alloc(3)
    assert a.bytes_in_use() == 300
    a.free(pages)
    assert a.bytes_in_use() == 0


# ------------------------------------------------- the argument

def test_kv_dtype_argument_validation(tiny):
    assert check_kv_dtype(None) == "fp"
    assert check_kv_dtype("fp") == "fp"
    assert check_kv_dtype("int8") == "int8"
    for junk in ("fp16", "int4", "", "INT8"):
        with pytest.raises(ValueError, match="kv_dtype"):
            check_kv_dtype(junk)
    # the engine refuses it before it makes a pool
    with pytest.raises(ValueError, match="kv_dtype"):
        _engine(tiny, kv_dtype="int4")


# ----------------------------------------------------- engine level

@pytest.fixture(scope="module")
def tiny():
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


def _engine(tiny, **kw):
    _, model, params = tiny
    opts = dict(max_slots=4, page_size=8, n_pages=64, chunk=4,
                prefill_chunk=16, temperature=0.0, seed=0,
                eos_id=-1, overlap=False)
    opts.update(kw)
    return LLMEngine(model, params, **opts)


def _run(eng, prompts, n=12):
    hs = [eng.submit(list(p), max_new_tokens=n) for p in prompts]
    while eng.step():
        pass
    return [h.result() for h in hs]


def _prompts(cfg, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size - 1, size=10).tolist()
            for _ in range(4)]


def test_engine_int8_deterministic(tiny):
    cfg = tiny[0]
    outs = []
    for _ in range(2):
        eng = _engine(tiny, kv_dtype="int8")
        outs.append(_run(eng, _prompts(cfg)[:2], n=8))
        eng.shutdown()
    assert outs[0] == outs[1]


def test_engine_int8_fp_token_agreement(tiny):
    # tolerance parity: the same floor the kvq A/B artifact records.
    # A random-weight 256-vocab model is the WORST case (near-uniform
    # logits, flips compound down the stream); real checkpoints with
    # peaked logits agree far higher.
    cfg = tiny[0]
    eng = _engine(tiny)
    fp = _run(eng, _prompts(cfg), n=16)
    eng.shutdown()
    eng = _engine(tiny, kv_dtype="int8")
    q = _run(eng, _prompts(cfg), n=16)
    eng.shutdown()
    total = sum(len(o) for o in fp)
    agree = sum(x == y for a, b in zip(fp, q) for x, y in zip(a, b))
    assert agree / total >= 0.8, (agree, total)


def test_prefix_hit_replays_identical_quantized_pages(tiny):
    # A radix-cache hit REUSES the quantized bytes + scale columns
    # the first request wrote (COW copies the scale column with the
    # page), so the replay is bit-exact — not merely tolerance-equal.
    cfg = tiny[0]
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, cfg.vocab_size - 1, size=24).tolist()
    eng = _engine(tiny, kv_dtype="int8", prefix_cache=True)
    first = _run(eng, [prompt], n=12)[0]
    assert eng.prefix_stats()["cached_pages"] > 0
    second = _run(eng, [prompt], n=12)[0]
    assert eng.prefix_stats()["hit_tokens"] > 0
    assert first == second
    check_quiesced(eng)
    eng.shutdown()


def test_int8_eviction_under_pressure_leak_free(tiny):
    # Small pool + many distinct prefixes: eviction must cycle
    # quantized pages through free/realloc (scale reset-on-offset-0
    # is what keeps reused pages honest) and quiesce leak-free.
    cfg = tiny[0]
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, cfg.vocab_size - 1, size=20).tolist()
               for _ in range(6)]
    # 11 usable pages: each request transiently needs 4 (32 tokens)
    # and retires 2 into the cache, so request 5 must evict
    eng = _engine(tiny, kv_dtype="int8", n_pages=12, max_slots=2,
                  prefix_cache=True)
    first = _run(eng, [prompts[0]], n=12)[0]
    for p in prompts[1:]:
        _run(eng, [p], n=12)
    assert eng.prefix_stats()["evictions"] > 0
    # re-run prompt 0 after its pages were evicted: a fresh prefill
    # replays the identical write history -> identical tokens
    again = _run(eng, [prompts[0]], n=12)[0]
    assert again == first
    check_quiesced(eng)
    eng.shutdown()


def test_spec_accept_rate_survives_int8(tiny):
    # Self-consistency gate: each arm's proposer drafts from its OWN
    # stream and its verify re-derives its OWN argmax — int8 rounding
    # must not break that loop (noise bound matches the kvq artifact)
    def accept(dt):
        eng = _engine(tiny, kv_dtype=dt, spec_len=4, max_slots=2)
        h = eng.submit([5, 6, 7, 8] * 5, max_new_tokens=40)
        while eng.step():
            pass
        h.result()
        sp = eng.spec_stats()
        eng.shutdown()
        assert sp["rounds"] > 0            # speculation engaged
        return sp["accept_rate"]

    fp, q = accept(None), accept("int8")
    assert q >= fp - 0.15, (fp, q)


def test_int8_load_report_bytes_and_gauge(tiny):
    from ray_tpu.serve.engine import KV_BYTES_TOTAL
    from ray_tpu.util import metrics
    cfg = tiny[0]
    eng = _engine(tiny, kv_dtype="int8", n_pages=32)
    rpt = eng.load_report()
    assert rpt["kv_dtype"] == "int8"
    assert rpt["kv_page_bytes"] == kv_pool_page_bytes(cfg, 8, "int8")
    assert rpt["kv_bytes_total"] == 32 * rpt["kv_page_bytes"]
    assert rpt["kv_bytes_in_use"] == 0
    _run(eng, _prompts(cfg))
    assert KV_BYTES_TOTAL in metrics.prometheus_text()
    eng.shutdown()


def test_tp4_int8_agreement(tiny, cpu_mesh_devices):
    # int8 under tensor parallelism: pools shard on the head axis,
    # scale rows ride P(None, "tensor") beside their heads.
    # tp=4 reduction order perturbs pre-quantization activations, so
    # the gate is agreement, not identity (unlike fp tp A/B).
    from ray_tpu.serve.sharding import EngineSharding
    cfg = llama_tiny(n_kv_heads=4, dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size - 1, size=12).tolist()
               for _ in range(4)]

    def run(sh):
        eng = LLMEngine(model, params, max_slots=4, page_size=8,
                        n_pages=64, chunk=4, prefill_chunk=16,
                        temperature=0.0, seed=0, eos_id=-1,
                        overlap=False, kv_dtype="int8", sharding=sh)
        outs = _run(eng, prompts, n=12)
        eng.shutdown()
        return outs

    tp1 = run(None)
    tp4 = run(EngineSharding.build(cfg, tp=4,
                                   devices=cpu_mesh_devices[:4]))
    total = sum(len(o) for o in tp1)
    agree = sum(x == y for a, b in zip(tp1, tp4)
                for x, y in zip(a, b))
    assert agree / total >= 0.9, (agree, total)
