"""Pallas paged-attention decode kernel vs dense reference.

Kernel runs in interpreter mode on the CPU test mesh; the dense
reference is the same math the llama gather fallback uses.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.paged_attention import paged_decode_attention


def _dense_ref(q, pages_k, pages_v, page_table, positions):
    B, H, D = q.shape
    KH, _, Pg, _ = pages_k.shape
    L = page_table.shape[1] * Pg
    rep = H // KH
    kg = pages_k[:, page_table].reshape(KH, B, L, D)
    vg = pages_v[:, page_table].reshape(KH, B, L, D)
    qg = q.reshape(B, KH, rep, D).astype(np.float32)
    s = np.einsum("bkrd,kbsd->bkrs", qg,
                  kg.astype(np.float32)) / np.sqrt(D)
    valid = np.arange(L)[None] <= np.asarray(positions)[:, None]
    s = np.where(valid[:, None, None, :], s, -1e30)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=-1, keepdims=True)
    o = np.einsum("bkrs,kbsd->bkrd", p, vg.astype(np.float32))
    return o.reshape(B, H, D)


def _random_layout(rng, B, n_pages, max_pages, Pg, KH, D, H,
                   dtype=np.float32):
    # Page 0 is the null page; each slot gets a distinct page chain.
    pages_k = rng.standard_normal((KH, n_pages, Pg, D)).astype(dtype)
    pages_v = rng.standard_normal((KH, n_pages, Pg, D)).astype(dtype)
    perm = rng.permutation(n_pages - 1)[: B * max_pages] + 1
    page_table = perm.reshape(B, max_pages).astype(np.int32)
    positions = rng.integers(0, max_pages * Pg, size=B).astype(np.int32)
    q = rng.standard_normal((B, H, D)).astype(dtype)
    return q, pages_k, pages_v, page_table, positions


@pytest.mark.parametrize("rep", [1, 4])
def test_kernel_matches_dense(rep):
    rng = np.random.default_rng(0)
    B, Pg, KH, D = 3, 8, 2, 16
    max_pages, n_pages = 4, 64
    H = KH * rep
    q, pk, pv, pt, pos = _random_layout(
        rng, B, n_pages, max_pages, Pg, KH, D, H)
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(pt), jnp.asarray(pos), interpret=True)
    ref = _dense_ref(q, pk, pv, pt, pos)
    np.testing.assert_allclose(np.asarray(out), ref,
                               rtol=2e-4, atol=2e-4)


def test_position_zero_and_full():
    # pos=0 attends exactly one key; pos=L-1 attends the full window.
    rng = np.random.default_rng(1)
    B, Pg, KH, D, max_pages = 2, 4, 1, 8, 3
    H = 2
    q, pk, pv, pt, _ = _random_layout(
        rng, B, 32, max_pages, Pg, KH, D, H)
    pos = np.array([0, max_pages * Pg - 1], dtype=np.int32)
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(pt), jnp.asarray(pos), interpret=True)
    ref = _dense_ref(q, pk, pv, pt, pos)
    np.testing.assert_allclose(np.asarray(out), ref,
                               rtol=2e-4, atol=2e-4)
    # Slot 0's output must equal V at position 0 exactly (softmax
    # over a single key).
    v0 = pv[0, pt[0, 0], 0]
    np.testing.assert_allclose(np.asarray(out)[0, 0], v0,
                               rtol=1e-5, atol=1e-5)


def test_bf16_inputs():
    rng = np.random.default_rng(2)
    B, Pg, KH, D, max_pages = 2, 8, 2, 16, 2
    H = 4
    q, pk, pv, pt, pos = _random_layout(
        rng, B, 16, max_pages, Pg, KH, D, H)
    to = lambda a: jnp.asarray(a, dtype=jnp.bfloat16)
    out = paged_decode_attention(
        to(q), to(pk), to(pv), jnp.asarray(pt), jnp.asarray(pos),
        interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _dense_ref(q.astype(np.float32), pk.astype(np.float32),
                     pv.astype(np.float32), pt, pos)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), ref, rtol=0.05, atol=0.05)


def test_llama_decode_paths_agree(monkeypatch):
    """The llama paged branch must produce the same step output via
    the pallas kernel (forced) and the XLA gather fallback."""
    from ray_tpu.models.llama import LlamaConfig, Llama
    from ray_tpu.models.kv_cache import PagedKVLayer, init_kv_pool

    cfg = LlamaConfig(vocab_size=64, max_seq_len=64, dim=32,
                      n_layers=2, n_heads=4, n_kv_heads=2,
                      hidden_dim=64, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    model = Llama(cfg)
    rng = jax.random.PRNGKey(0)
    B = 2
    pages = init_kv_pool(cfg, n_pages=16, page_size=4)
    # Seed the pool with nonzero history so past positions matter.
    pages = [(pk + 0.1 * jax.random.normal(rng, pk.shape),
              pv + 0.1 * jax.random.normal(rng, pv.shape))
             for pk, pv in pages]
    page_table = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]],
                           dtype=jnp.int32)
    tok = jax.random.randint(rng, (B, 1), 0, cfg.vocab_size)
    params = jax.jit(model.init)(rng, tok)
    pos = jnp.array([0, 13], dtype=jnp.int32)

    def step(force):
        monkeypatch.setenv("RAY_TPU_PAGED_KERNEL", force)

        def fwd(params, pages):
            # a fresh function per call: the knob is read at trace
            # time, so each call traces its own branch
            kv = [PagedKVLayer(pk, pv, page_table) for pk, pv in pages]
            out, _ = model.apply(params, tok, kv_caches=kv,
                                 cache_len=pos)
            return out

        jaxpr = str(jax.make_jaxpr(fwd)(params, pages))
        assert ("pallas_call" in jaxpr) == (force == "1")
        return np.asarray(jax.jit(fwd)(params, pages),
                          dtype=np.float32)

    a = step("1")
    b = step("0")
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_paged_append_mid_page_span():
    """Append-at-offset: a chunk starting mid-page and spanning a page
    boundary lands token-exact in the right (page, offset) cells and
    touches nothing else."""
    from ray_tpu.ops.paged_attention import paged_append
    rng = np.random.default_rng(3)
    B, T, KH, D, Pg, n_pages, max_pages = 2, 6, 2, 8, 4, 16, 4
    pk = rng.standard_normal((KH, n_pages, Pg, D)).astype(np.float32)
    pv = rng.standard_normal((KH, n_pages, Pg, D)).astype(np.float32)
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
            ref_k[:, pt[b, p // Pg], p % Pg] = k[b, t]
            ref_v[:, pt[b, p // Pg], p % Pg] = v[b, t]
    np.testing.assert_array_equal(np.asarray(nk), ref_k)
    np.testing.assert_array_equal(np.asarray(nv), ref_v)


def test_paged_append_tail_hits_null_page_only():
    """Positions past a slot's allocated pages resolve to page-table
    zeros (the null page) and clamped indices — an oversized padding
    tail can corrupt NO allocated page of any slot."""
    from ray_tpu.ops.paged_attention import paged_append
    rng = np.random.default_rng(4)
    B, T, KH, D, Pg, n_pages, max_pages = 1, 8, 1, 4, 4, 8, 2
    pk = rng.standard_normal((KH, n_pages, Pg, D)).astype(np.float32)
    pv = rng.standard_normal((KH, n_pages, Pg, D)).astype(np.float32)
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
    np.testing.assert_array_equal(nk[:, 3, 2], k[0, 0])
    np.testing.assert_array_equal(nk[:, 3, 3], k[0, 1])
    # every page except the null page and page 3 is untouched
    for pg in range(1, n_pages):
        if pg == 3:
            continue
        np.testing.assert_array_equal(nk[:, pg], pk[:, pg])
        np.testing.assert_array_equal(nv[:, pg], pv[:, pg])
