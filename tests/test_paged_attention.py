"""The paged pool's two step-time operations (ops/paged_attention.py)
against plain references.

``_paged_window_attention`` over a PAGE-MAJOR pool [n_pages, Pg, KH, D],
as the engine stores it, fp and int8 (per-page scales [n_pages, KH]),
against a dense numpy softmax over the same (dequantized) pages;
``paged_append`` against plain numpy caches.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         dequantize_pages)


def _quantize_pages(pages):
    """A page-major fp pool coded as the int8 pool stores it: one
    absmax scale per (page, kv head), value = q * scale / 127."""
    scales = np.abs(pages).max(axis=(1, 3)).astype(np.float32)
    q = np.round(pages / np.maximum(scales, 1e-30)[:, None, :, None]
                 * 127.0).astype(np.int8)
    return q, scales


def _window(q, pk, pv, pt, pos, kv_dtype, dtype=None):
    """One decode step's attention through the block gather, and the
    fp pages the dense reference must read (the int8 pool's
    dequantized view: the coding is paged_append's business, not the
    gather's)."""
    to = (lambda a: jnp.asarray(a, dtype)) if dtype else jnp.asarray
    if kv_dtype == "int8":
        (pk, sk), (pv, sv) = _quantize_pages(pk), _quantize_pages(pv)
        ref_k = np.asarray(dequantize_pages(pk, sk))
        ref_v = np.asarray(dequantize_pages(pv, sv))
        pools = (jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(sk),
                 jnp.asarray(sv))
    else:
        ref_k, ref_v = (np.asarray(to(t), np.float32) for t in (pk, pv))
        pools = (to(pk), to(pv), None, None)
    out = jax.jit(_paged_window_attention)(
        to(q)[:, None], *pools, jnp.asarray(pt), jnp.asarray(pos))
    return out[:, 0], ref_k, ref_v


def _dense_ref(q, pages_k, pages_v, page_table, positions):
    B, H, D = q.shape
    _, Pg, KH, _ = pages_k.shape
    L = page_table.shape[1] * Pg
    rep = H // KH
    kg = pages_k[page_table].reshape(B, L, KH, D)
    vg = pages_v[page_table].reshape(B, L, KH, D)
    qg = q.reshape(B, KH, rep, D).astype(np.float32)
    s = np.einsum("bkrd,bskd->bkrs", qg,
                  kg.astype(np.float32)) / np.sqrt(D)
    valid = np.arange(L)[None] <= np.asarray(positions)[:, None]
    s = np.where(valid[:, None, None, :], s, -1e30)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=-1, keepdims=True)
    o = np.einsum("bkrs,bskd->bkrd", p, vg.astype(np.float32))
    return o.reshape(B, H, D)


def _random_layout(rng, B, n_pages, max_pages, Pg, KH, D, H,
                   dtype=np.float32):
    # Page 0 is the null page; each slot gets a distinct page chain.
    pages_k = rng.standard_normal((n_pages, Pg, KH, D)).astype(dtype)
    pages_v = rng.standard_normal((n_pages, Pg, KH, D)).astype(dtype)
    perm = rng.permutation(n_pages - 1)[: B * max_pages] + 1
    page_table = perm.reshape(B, max_pages).astype(np.int32)
    positions = rng.integers(0, max_pages * Pg, size=B).astype(np.int32)
    q = rng.standard_normal((B, H, D)).astype(dtype)
    return q, pages_k, pages_v, page_table, positions


KV_DTYPES = pytest.mark.parametrize("kv_dtype", ["fp", "int8"])


@KV_DTYPES
@pytest.mark.parametrize("rep", [1, 4])
def test_window_matches_dense(rep, kv_dtype):
    rng = np.random.default_rng(0)
    B, Pg, KH, D = 3, 8, 2, 16
    max_pages, n_pages = 4, 64
    H = KH * rep
    q, pk, pv, pt, pos = _random_layout(
        rng, B, n_pages, max_pages, Pg, KH, D, H)
    out, ref_k, ref_v = _window(q, pk, pv, pt, pos, kv_dtype)
    ref = _dense_ref(q, ref_k, ref_v, pt, pos)
    np.testing.assert_allclose(np.asarray(out), ref,
                               rtol=2e-4, atol=2e-4)


@KV_DTYPES
def test_position_zero_and_full(kv_dtype):
    # pos=0 attends exactly one key; pos=L-1 attends the full window
    # (a table three pages wide is one block: the straight-line path).
    rng = np.random.default_rng(1)
    B, Pg, KH, D, max_pages = 2, 4, 1, 8, 3
    H = 2
    q, pk, pv, pt, _ = _random_layout(
        rng, B, 32, max_pages, Pg, KH, D, H)
    pos = np.array([0, max_pages * Pg - 1], dtype=np.int32)
    out, ref_k, ref_v = _window(q, pk, pv, pt, pos, kv_dtype)
    ref = _dense_ref(q, ref_k, ref_v, pt, pos)
    np.testing.assert_allclose(np.asarray(out), ref,
                               rtol=2e-4, atol=2e-4)
    # Slot 0's output must equal V at position 0 exactly (softmax
    # over a single key).
    v0 = ref_v[pt[0, 0], 0, 0]
    np.testing.assert_allclose(np.asarray(out)[0, 0], v0,
                               rtol=1e-5, atol=1e-5)


@KV_DTYPES
def test_bf16_inputs(kv_dtype):
    rng = np.random.default_rng(2)
    B, Pg, KH, D, max_pages = 2, 8, 2, 16, 2
    H = 4
    q, pk, pv, pt, pos = _random_layout(
        rng, B, 16, max_pages, Pg, KH, D, H)
    out, ref_k, ref_v = _window(q, pk, pv, pt, pos, kv_dtype,
                                dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    ref = _dense_ref(q.astype(np.float32), ref_k, ref_v, pt, pos)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), ref, rtol=0.05, atol=0.05)


# One query head a KV head at T = 1 (OLMoE's decode step): the group
# is padded to two rows so that both contractions stay matrix products
# (ops/paged_attention.py). The window loop's block is patched to 16
# tokens: an 8-page table of 8-token pages is four blocks, block 1
# holding positions 16..31.
ONE_HEAD_CASES = {
    # each row's query position (None: a null row, its stale position
    # in the last block)
    "ends_inside_a_block": [20, 17, 9],
    "ends_on_a_block_edge": [31, 15, 30],
    "one_past_a_block_edge": [32, 16, 33],
    # the null row must not widen the window; its output is ignored
    "null_row_beside_live_rows": [20, None, 5],
    # a table of two pages is one block: the straight-line branch
    "pool_of_one_block": [15, 0, 9],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(ONE_HEAD_CASES))
def test_one_query_head_a_kv_head_decode(name, dtype, monkeypatch):
    from ray_tpu.ops import paged_attention as paged_mod
    monkeypatch.setattr(paged_mod, "_WINDOW_BLOCK_TOKENS", 16)
    case = ONE_HEAD_CASES[name]
    rng = np.random.default_rng(7)
    B, Pg, KH, D, n_pages = len(case), 8, 4, 16, 32
    max_pages = 2 if name == "pool_of_one_block" else 8
    q, pk, pv, pt, _ = _random_layout(rng, B, n_pages, max_pages, Pg,
                                      KH, D, KH)
    live = [b for b, at in enumerate(case) if at is not None]
    pt[[b for b in range(B) if b not in live]] = 0
    pos = np.asarray([max_pages * Pg - 1 if at is None else at
                      for at in case], np.int32)
    dt = jnp.dtype(dtype)
    out, ref_k, ref_v = _window(q, pk, pv, pt, pos, "fp", dtype=dt)
    assert out.dtype == dt
    ref = _dense_ref(np.asarray(jnp.asarray(q, dt), np.float32),
                     ref_k, ref_v, pt, pos)
    tol = 2e-4 if dtype == "float32" else 0.05
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32)[live], ref[live],
        rtol=tol, atol=tol)
    if len(live) < B:
        # the same rows with the dead row's position at 0: equal
        calm, _, _ = _window(q, pk, pv, pt,
                             np.where(pt[:, 0] == 0, 0, pos), "fp",
                             dtype=dt)
        np.testing.assert_array_equal(np.asarray(out)[live],
                                      np.asarray(calm)[live])


def _dot_operands(jaxpr):
    """(the group's operand, the block's) of every dot_general, loop
    bodies too; einsum hands them over in either order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(sorted((v.aval for v in eqn.invars),
                                key=lambda a: -a.ndim))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_dot_operands(sub))
    return found


# (heads, T, pool) -> the query operand of the scores' contraction
# [B, T, KH, rows, D]: a group of rep x T >= 2 rows, and every group
# over an int8 pool (whose dequantised blocks are float32 values, not
# bfloat16-valued), contracts as it always has, in float32 with no
# padded row; the one-row group over a bfloat16 pool is padded to two
# rows and reads the block as it is stored
@pytest.mark.parametrize("heads,T,kv_dtype,rows,operand", [
    (4, 1, "fp", 2, "float32"),        # rep 2
    (2, 2, "fp", 1, "float32"),        # rep 1, a two-token chunk
    (2, 1, "int8", 1, "float32"),      # rep 1, T 1, quantised pool
    (2, 1, "fp", 2, "bfloat16"),       # rep 1, T 1: the padded group
])
def test_scores_contraction_operands(heads, T, kv_dtype, rows, operand):
    B, Pg, KH, D, n_pages, max_pages = 3, 8, 2, 16, 16, 4
    q = jnp.zeros((B, T, heads, D), jnp.bfloat16)
    pool = jnp.zeros((n_pages, Pg, KH, D),
                     jnp.int8 if kv_dtype == "int8" else jnp.bfloat16)
    scales = (jnp.ones((n_pages, KH), jnp.float32)
              if kv_dtype == "int8" else None)
    jaxpr = jax.make_jaxpr(_paged_window_attention)(
        q, pool, pool, scales, scales,
        jnp.zeros((B, max_pages), jnp.int32), jnp.zeros((B,), jnp.int32))
    (q_op, k_op), (p_op, v_op) = _dot_operands(jaxpr.jaxpr)
    assert q_op.shape == (B, T, KH, rows, D)
    assert k_op.shape == v_op.shape == (B, max_pages * Pg, KH, D)
    assert q_op.dtype == k_op.dtype == jnp.dtype(operand)
    assert p_op.shape == (B, KH, rows, T, max_pages * Pg)
    assert v_op.dtype == (jnp.float32 if kv_dtype == "int8"
                          else jnp.bfloat16)


def test_paged_append_mid_page_span():
    """Append-at-offset: a chunk starting mid-page and spanning a page
    boundary lands token-exact in the right (page, offset) cells and
    touches nothing else."""
    from ray_tpu.ops.paged_attention import paged_append
    rng = np.random.default_rng(3)
    B, T, KH, D, Pg, n_pages, max_pages = 2, 6, 2, 8, 4, 16, 4
    pk = rng.standard_normal((n_pages, Pg, KH, D)).astype(np.float32)
    pv = rng.standard_normal((n_pages, Pg, KH, D)).astype(np.float32)
    pt = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    pos = np.array([3, 5], np.int32)      # both start mid-page
    k = rng.standard_normal((B, T, KH, D)).astype(np.float32)
    v = rng.standard_normal((B, T, KH, D)).astype(np.float32)
    nk, nv = paged_append(jnp.asarray(pk), jnp.asarray(pv),
                          jnp.asarray(pt), jnp.asarray(pos),
                          jnp.asarray(k), jnp.asarray(v))
    ref_k, ref_v = pk.copy(), pv.copy()
    for b in range(B):
        for t in range(T):
            p = pos[b] + t
            ref_k[pt[b, p // Pg], p % Pg] = k[b, t]
            ref_v[pt[b, p // Pg], p % Pg] = v[b, t]
    np.testing.assert_array_equal(np.asarray(nk), ref_k)
    np.testing.assert_array_equal(np.asarray(nv), ref_v)


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_append_then_block_gather_matches_plain_cache(kv_dtype):
    """paged_append then the window loop's block gather, against a
    plain numpy cache [B, L, KH, D]: a first chunk, then a chunk that
    starts mid-page and spans pages, land where a contiguous cache
    puts them, in the page-major pool and (int8) its page-major
    scales."""
    from ray_tpu.models.kv_cache import init_kv_pool, kv_layer_view
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.ops.paged_attention import paged_append
    rng = np.random.default_rng(11)
    B, KH, H, D, Pg, n_pages, max_pages = 2, 2, 4, 8, 4, 16, 4
    cfg = LlamaConfig(dim=H * D, n_heads=H, n_kv_heads=KH, n_layers=1,
                      dtype=jnp.float32)
    layer = init_kv_pool(cfg, n_pages, Pg, kv_dtype)[0]
    assert layer[0].shape == (n_pages, Pg, KH, D)
    pt = jnp.asarray([[3, 9, 1, 12], [5, 2, 14, 7]], jnp.int32)
    plain_k = np.zeros((B, max_pages * Pg, KH, D), np.float32)
    plain_v = np.zeros_like(plain_k)
    append = jax.jit(paged_append)
    # 3 tokens from 0, then 7 from position 3: mid-page, over two edges
    for start, T in ((0, 3), (3, 7)):
        k = rng.standard_normal((B, T, KH, D)).astype(np.float32)
        v = rng.standard_normal((B, T, KH, D)).astype(np.float32)
        plain_k[:, start:start + T], plain_v[:, start:start + T] = k, v
        layer = append(layer[0], layer[1], pt,
                       jnp.full((B,), start, jnp.int32),
                       jnp.asarray(k), jnp.asarray(v), *layer[2:])
    n = 10
    if kv_dtype == "fp":
        got_k, got_v = (np.asarray(t)[np.asarray(pt)].reshape(
            B, -1, KH, D) for t in layer)
        np.testing.assert_array_equal(got_k[:, :n], plain_k[:, :n])
        np.testing.assert_array_equal(got_v[:, :n], plain_v[:, :n])
        tol = 1e-5
    else:
        pk, pv, sk, sv = layer
        assert sk.shape == (n_pages, KH)
        got_k = np.asarray(dequantize_pages(pk, sk))[
            np.asarray(pt)].reshape(B, -1, KH, D)
        # a page's scale is its absmax per head; a step is scale / 127
        # and the second chunk re-coded the first once
        step = np.asarray(sk)[np.asarray(pt)].repeat(Pg, axis=1) / 127.0
        assert (np.abs(got_k - plain_k)[:, :n]
                <= 1.5 * step[:, :n, :, None] + 1e-7).all()
        plain_k = got_k
        plain_v = np.asarray(dequantize_pages(pv, sv))[
            np.asarray(pt)].reshape(B, -1, KH, D)
        tol = 1e-4
    # the block gather reads the same cache: attention of one query at
    # position n - 1 over the pool equals attention over the plain cache
    q = rng.standard_normal((B, 1, H, D)).astype(np.float32)
    pos = jnp.full((B,), n - 1, jnp.int32)
    cache = kv_layer_view(layer, pt)
    y = jax.jit(_paged_window_attention)(
        jnp.asarray(q), cache.pages_k, cache.pages_v, cache.scales_k,
        cache.scales_v, pt, pos)
    qg = q[:, 0].reshape(B, KH, H // KH, D)
    s = np.einsum("bkrd,bskd->bkrs", qg, plain_k[:, :n]) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bkrs,bskd->bkrd", p, plain_v[:, :n]).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(y)[:, 0], ref, rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_page_bytes_round_trip_lands_identical_pages(kv_dtype):
    """export_page_bytes -> page_cols_from_bytes -> the engine's
    _jit_write_page: a page leaves one pool as it lies ([Pg, KH, D],
    scales [KH]) and lands byte-identical in another page of a second
    pool, every layer, touching no other page; the frame's arity and
    byte counts are still checked."""
    from ray_tpu.models.kv_cache import (export_page_bytes, init_kv_pool,
                                         page_cols_from_bytes)
    from ray_tpu.models.llama import llama_tiny
    from ray_tpu.serve.step_programs import _jit_write_page
    cfg = llama_tiny(dtype=jnp.float32)
    Pg, n_pages, src, dst = 4, 8, 5, 2
    rng = np.random.default_rng(12)

    def filled():
        return [tuple(
            jnp.asarray(rng.integers(-127, 128, t.shape), t.dtype)
            if t.dtype == jnp.int8 else
            jnp.asarray(rng.standard_normal(t.shape), t.dtype)
            for t in layer)
            for layer in init_kv_pool(cfg, n_pages, Pg, kv_dtype)]

    donor, taker = filled(), filled()
    before = jax.tree_util.tree_map(np.asarray, taker)
    blobs = export_page_bytes(donor, src)
    k_bytes = Pg * cfg.n_kv_heads * cfg.head_dim * (
        1 if kv_dtype == "int8" else 4)
    assert [len(b) for b in blobs[0][:2]] == [k_bytes, k_bytes]
    cols = page_cols_from_bytes(cfg, Pg, kv_dtype, blobs)
    assert cols[0][0].shape == (Pg, cfg.n_kv_heads, cfg.head_dim)
    taker = _jit_write_page(None)(
        taker, jnp.int32(dst),
        [tuple(jnp.asarray(c) for c in layer) for layer in cols])
    for d_layer, t_layer, b_layer in zip(donor, taker, before):
        for d, t, b in zip(d_layer, t_layer, b_layer):
            t = np.asarray(t)
            assert t[dst].tobytes() == np.asarray(d)[src].tobytes()
            keep = np.arange(n_pages) != dst
            np.testing.assert_array_equal(t[keep], b[keep])
    with pytest.raises(ValueError, match="layers"):
        page_cols_from_bytes(cfg, Pg, kv_dtype, blobs[:-1])
    with pytest.raises(ValueError, match="byte"):
        page_cols_from_bytes(cfg, Pg, kv_dtype,
                             [[b[:-1] for b in layer] for layer in blobs])
    other = "fp" if kv_dtype == "int8" else "int8"
    with pytest.raises(ValueError, match="tensors"):
        page_cols_from_bytes(cfg, Pg, other, blobs)


def test_paged_append_tail_hits_null_page_only():
    """Positions past a slot's allocated pages resolve to page-table
    zeros (the null page) and clamped indices — an oversized padding
    tail can corrupt NO allocated page of any slot."""
    from ray_tpu.ops.paged_attention import paged_append
    rng = np.random.default_rng(4)
    B, T, KH, D, Pg, n_pages, max_pages = 1, 8, 1, 4, 4, 8, 2
    pk = rng.standard_normal((n_pages, Pg, KH, D)).astype(np.float32)
    pv = rng.standard_normal((n_pages, Pg, KH, D)).astype(np.float32)
    pt = np.zeros((B, max_pages), np.int32)
    pt[0, 0] = 3                          # ONE allocated page
    pos = np.array([2], np.int32)         # 8-token chunk overruns it
    k = rng.standard_normal((B, T, KH, D)).astype(np.float32)
    v = rng.standard_normal((B, T, KH, D)).astype(np.float32)
    nk, nv = paged_append(jnp.asarray(pk), jnp.asarray(pv),
                          jnp.asarray(pt), jnp.asarray(pos),
                          jnp.asarray(k), jnp.asarray(v))
    nk, nv = np.asarray(nk), np.asarray(nv)
    # page 3 got its two in-window tokens
    np.testing.assert_array_equal(nk[3, 2], k[0, 0])
    np.testing.assert_array_equal(nk[3, 3], k[0, 1])
    # every page except the null page and page 3 is untouched
    for pg in range(1, n_pages):
        if pg == 3:
            continue
        np.testing.assert_array_equal(nk[pg], pk[pg])
        np.testing.assert_array_equal(nv[pg], pv[pg])
