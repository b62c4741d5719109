"""A prefill chunk's attention over latent pages: the Pallas kernel
(ops/latent_window_attention.py) in interpret mode against the block
loop the CPU serves (ops/paged_attention.py ``_paged_window_attention``)
on identical inputs, and the rule that chooses between them.

Blocks are a deployment's (512 tokens, pages of 64); the entries are
narrow (256 columns, values the first 128) so that the interpreter is
quick, except at the no-KV-layer cell's shape. float32 agrees to rtol
1e-4 as the other window tests; bfloat16 outputs (of order 1, one unit
in the last place 2**-7) to one such unit and a half.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_window_attention as lw
from ray_tpu.ops import paged_attention as paged_mod

PAGE, BLOCK = 64, 512
T, H, D, DV = 64, 16, 256, 128
SCALE = 0.11
TOKENS = 16                             # 4 query tiles a row
TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-5),
       jnp.bfloat16: dict(rtol=1e-2, atol=1.2e-2)}


def _inputs(ends, dtype, max_pages, T=T, H=H, D=D, seed=0,
            stale=100_000):
    """Rows whose chunks of T queries END at ``ends`` (None: a row no
    request owns, its page-table row null and its position stale), over
    a pool whose pages lie scattered."""
    rng = np.random.default_rng(seed)
    B = len(ends)
    n_pages = 1 + B * max_pages
    ids = 1 + rng.permutation(B * max_pages).reshape(B, max_pages)
    pt = np.zeros((B, max_pages), np.int32)
    pos = np.zeros((B,), np.int32)
    for b, end in enumerate(ends):
        if end is None:
            pos[b] = stale
            continue
        pos[b] = end - T
        n = -(-end // PAGE)
        pt[b, :n] = ids[b, :n]
    pages = jnp.asarray(0.5 * rng.standard_normal((n_pages, PAGE, D)),
                        dtype)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
    return q, pages, jnp.asarray(pt), jnp.asarray(pos)


@functools.partial(jax.jit, static_argnames=("dv",))
def _loop(q, pages, pt, pos, dv=DV):
    return paged_mod._paged_window_attention(
        q, pages, None, None, None, pt, pos, softmax_scale=SCALE,
        value_dim=dv)


@functools.partial(jax.jit, static_argnames=("dv", "tokens"))
def _kernel(q, pages, pt, pos, dv=DV, tokens=TOKENS):
    return lw.latent_window_attention(
        q, pages, pt, pos, softmax_scale=SCALE, value_dim=dv,
        block_pages=paged_mod.paged_window_block_pages(PAGE, pt.shape[1]),
        tokens=tokens, interpret=True)


def _agree(got, want, live, dtype):
    got, want = (np.asarray(a, np.float32)[live] for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, **TOL[dtype])


# the rows' chunks end at these positions; pages of 64, blocks of 512
WINDOWS = {
    # on, one short of and one past a block's edge: the last query sits
    # at 511 | 510 | 512
    "block_edge": [512, 511, 513],
    # the same at a page's edge inside the second block
    "page_edge": [576, 575, 577],
    # positions 480..543: the chunk starts in one block, ends in the next
    "chunk_crosses_a_block_edge": [544],
    "four_rows_four_windows": [512, 2304, 4352, 8192],
    "the_table_s_last_block": [64, 16384],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(WINDOWS))
def test_kernel_equals_the_block_loop(name, dtype):
    ends = WINDOWS[name]
    args = _inputs(ends, dtype, max_pages=256)
    _agree(_kernel(*args), _loop(*args), slice(None), dtype)


def test_a_null_row_with_a_stale_position_changes_nothing():
    """A row no request owns is not visited, whatever its position
    says: it reads out zeros, and the live rows read what they read
    beside a calm one."""
    ends = [1024, None, 300, None]
    args = _inputs(ends, jnp.float32, max_pages=256)
    got = np.asarray(_kernel(*args))
    _agree(got, _loop(*args), [0, 2], jnp.float32)
    assert not got[[1, 3]].any()
    calm = _inputs(ends, jnp.float32, max_pages=256, stale=0)
    np.testing.assert_array_equal(got, np.asarray(_kernel(*calm)))


def test_a_table_of_one_block():
    """``max_blocks == 1``: the loop is straight-line code there, the
    kernel a grid of one visit a tile."""
    args = _inputs([64, 200, 512], jnp.float32, max_pages=8)
    _agree(_kernel(*args), _loop(*args), slice(None), jnp.float32)


def test_the_no_kv_layer_cell_s_shape():
    """kimi-linear-d8.gen-sat: 32 heads over entries stored 640 wide
    with values of 512, a table 64 pages wide, windows of one and two
    blocks, in the tiles the deployment gets."""
    tokens = lw.tile_tokens(T, 32)
    assert tokens == 64
    args = _inputs([256, 512, 1024, 832], jnp.bfloat16, max_pages=64,
                   H=32, D=640)
    _agree(_kernel(*args, dv=512, tokens=tokens), _loop(*args, dv=512),
           slice(None), jnp.bfloat16)


def test_the_zero_columns_behind_an_entry_score_nothing():
    """An entry is stored in whole 128-lane tiles: zeros behind
    [c | k_r]. Whatever a query holds in those columns meets zeros."""
    q, pages, pt, pos = _inputs([700, 1500], jnp.float32, max_pages=256)
    pages = pages.at[..., D - 64:].set(0.0)
    want = _kernel(q.at[..., D - 64:].set(0.0), pages, pt, pos)
    got = _kernel(q.at[..., D - 64:].set(7.0), pages, pt, pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _agree(got, _loop(q, pages, pt, pos), slice(None), jnp.float32)


# ------------------------------------------------------------ the choice

def _spied(monkeypatch):
    """``calls``: the kernel's calls from here on (it returns zeros)."""
    calls = []

    def spy(q, pages, page_table, pos, *, value_dim, **kw):
        calls.append(q.shape)
        return jnp.zeros(q.shape[:3] + (value_dim,), q.dtype)
    monkeypatch.setattr(lw, "latent_window_attention", spy)
    return calls


def _call(T=256, H=64, kv=False, int8=False, dtype=jnp.bfloat16,
          pool_dtype=None, page=PAGE):
    """Trace one ``_paged_window_attention`` call of a [4, T] chunk."""
    pool_dtype = pool_dtype or dtype
    q = jax.ShapeDtypeStruct((4, T, H, 640), dtype)
    pt = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    pos = jax.ShapeDtypeStruct((4,), jnp.int32)
    if not kv:
        pk = jax.ShapeDtypeStruct((513, page, 640), pool_dtype)
        return jax.eval_shape(
            lambda q, pk, pt, pos: paged_mod._paged_window_attention(
                q, pk, None, None, None, pt, pos, softmax_scale=0.1,
                value_dim=512), q, pk, pt, pos)
    pk = jax.ShapeDtypeStruct((513, page, 1, 640),
                              jnp.int8 if int8 else pool_dtype)
    sk = jax.ShapeDtypeStruct((513, 1), jnp.float32) if int8 else None
    return jax.eval_shape(
        lambda q, pk, sk, pt, pos: paged_mod._paged_window_attention(
            q, pk, pk, sk, sk, pt, pos), q, pk, sk, pt, pos)


LOOP_CASES = {
    "a_decode_step": dict(T=1),
    "a_verify_of_four_tokens": dict(T=4),
    "a_kv_pool": dict(kv=True),
    "int8_scales": dict(kv=True, int8=True),
    "float32_operands": dict(dtype=jnp.float32),
    "a_pool_of_another_type": dict(pool_dtype=jnp.float32),
    "pages_of_half_a_sublane_tile": dict(page=8),
    "heads_that_merge_into_no_whole_tile": dict(H=24),
}


@pytest.mark.parametrize("name", list(LOOP_CASES))
def test_the_loop_keeps_what_the_kernel_is_not_for(name, monkeypatch):
    calls = _spied(monkeypatch)
    monkeypatch.setattr(lw, "_on_one_tpu", lambda: True)
    out = _call(**LOOP_CASES[name])
    assert not calls
    assert out.shape[:3] == (4, LOOP_CASES[name].get("T", 256),
                             LOOP_CASES[name].get("H", 64))


@pytest.mark.parametrize("T,H", [(256, 64), (64, 64), (256, 32),
                                 (256, 128)])
def test_a_chunk_over_latent_pages_on_one_tpu_takes_the_kernel(
        T, H, monkeypatch):
    calls = _spied(monkeypatch)
    monkeypatch.setattr(lw, "_on_one_tpu", lambda: True)
    assert _call(T=T, H=H).shape == (4, T, H, 512)
    assert calls == [(4, T, H, 640)]


def test_the_cpu_and_a_mesh_keep_the_loop(monkeypatch, cpu_mesh_devices):
    """The backend and the ambient mesh decide, by grouped_matmul's
    rule: the CPU (every other test here), and a multi-device mesh on a
    TPU, which GSPMD cannot partition a Mosaic kernel for."""
    from jax.sharding import Mesh
    calls = _spied(monkeypatch)
    _call()                                           # the CPU
    assert not calls
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(cpu_mesh_devices[:2]), ("tensor",))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        _call()
    assert not calls
    _call()
    assert calls == [(4, 256, 64, 640)]


# ----------------------------------------------- the engine's counter

def test_kernel_blocks_counts_each_row_to_its_own_last_block():
    count = functools.partial(lw.kernel_blocks, T=256, block=512,
                              max_blocks=32)
    assert count([0]) == 1                  # positions 0..255
    assert count([256]) == 1                # 256..511
    assert count([257]) == 2                # ..512
    assert count([256, 2048, 4096, 7936]) == 1 + 5 + 9 + 16
    assert count([16384 - 256, 20000]) == 32 + 32   # inside the table
    assert count([]) == 0


def test_the_round_event_carries_prefill_kernel_blocks(monkeypatch):
    """0 where the prefill program holds no kernel (the CPU); the live
    rows' blocks where it does, beside ``prefill_window_tokens``."""
    from ray_tpu.models.axk1 import AXK1, axk1_tiny
    from ray_tpu.serve.engine import LLMEngine
    cfg = axk1_tiny(dtype=jnp.float32)
    model = AXK1(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    eng = LLMEngine(model, params, max_slots=4, page_size=16, n_pages=65,
                    chunk=4, prefill_chunk=64).start()
    try:
        def rounds():
            return [(e[5]["prefill_kernel_blocks"],
                     e[5]["prefill_window_tokens"])
                    for e in eng.events.snapshot()
                    if e[2] == "round" and e[5]["prefill_width"]]

        eng.submit(list(range(1, 100)), max_new_tokens=2).result()
        assert eng.wait_idle(10)
        before = rounds()
        assert before and not any(k for k, _w in before)
        assert eng.stats["prefill_kernel_blocks"] == 0
        asked = []
        monkeypatch.setattr(
            lw, "serves", lambda *a: asked.append(a) or True)
        eng.submit(list(range(1, 100)), max_new_tokens=2).result()
        assert eng.wait_idle(10)
        # one row, its two chunks inside the table's one block
        assert rounds()[len(before):] == [(1, 512), (1, 512)]
        assert eng.stats["prefill_kernel_blocks"] == 2
        assert asked[0] == (64, cfg.n_heads, 128, cfg.kv_lora_rank, 16,
                            jnp.float32)
    finally:
        eng.shutdown()
