"""A decode step's attention over K/V or latent pages: the Pallas
kernel (ops/paged_decode_attention.py) in interpret mode against the
block loop the CPU serves (ops/paged_attention.py
``_paged_window_attention``) on identical inputs, the rule that chooses
between them, and the engine's count of the pages the kernel visits.

Pages and blocks are a deployment's (64 tokens, 512). float32 agrees to
rtol 1e-4 as the other window tests; bfloat16 outputs (of order 1, one
unit in the last place 2**-7) to one such unit and a half.
"""
import functools
import hashlib
import importlib.util
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as paged_mod
from ray_tpu.ops import paged_decode_attention as pd

PAGE, D = 64, 128
TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-5),
       jnp.bfloat16: dict(rtol=1e-2, atol=1.2e-2)}
# (query heads, KV heads): one, four and eight rows a KV head
GROUPS = {"rows_1": (16, 16), "rows_4": (32, 8), "rows_8": (32, 4)}
# (query positions a row, the mask's block length: 1 the causal mask):
# a decode step; a speculative verify's few queries; a block of a model
# that decodes by blocks (models/sdar.py), each query seeing its whole
# block
QUERIES = {"one_query": (1, 1),
           "T2_causal": (2, 1), "T2_block": (2, 2),
           "T4_causal": (4, 1), "T4_block": (4, 4),
           "T8_causal": (8, 1), "T8_block": (8, 8)}


def _rows(contexts, max_pages, rng, stale, T=1, block_len=1):
    """(page table, positions) of rows whose contexts hold ``contexts``
    tokens, the row's ``T`` queries the last among them (None: a row no
    request owns, its page-table row null and its position ``stale``),
    their pages scattered over a pool of ``1 + len(contexts) *
    max_pages``; a row holds the pages its last query sees, to its
    block's end under the block mask."""
    B = len(contexts)
    ids = 1 + rng.permutation(B * max_pages).reshape(B, max_pages)
    pt = np.zeros((B, max_pages), np.int32)
    pos = np.zeros((B,), np.int32)
    for b, n in enumerate(contexts):
        if n is None:
            pos[b] = stale
            continue
        pos[b] = n - T
        end = -(-n // block_len) * block_len
        held = min(-(-end // PAGE), max_pages)
        pt[b, :held] = ids[b, :held]
    return jnp.asarray(pt), jnp.asarray(pos)


def _inputs(contexts, dtype, max_pages, H=16, KH=16, seed=0,
            stale=100_000, T=1, block_len=1):
    """``T`` queries a row of ``H`` heads for ``_rows`` over a K/V pool
    of ``KH`` heads."""
    rng = np.random.default_rng(seed)
    B = len(contexts)
    pt, pos = _rows(contexts, max_pages, rng, stale, T, block_len)
    pk, pv = (jnp.asarray(
        0.5 * rng.standard_normal((1 + B * max_pages, PAGE, KH, D)), dtype)
        for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
    return q, pk, pv, pt, pos


@functools.partial(jax.jit, static_argnames=("block_len",))
def _loop(q, pk, pv, pt, pos, block_len=1):
    return paged_mod._paged_window_attention(q, pk, pv, None, None, pt,
                                             pos, block_len=block_len)


def _kernel(q, pk, pv, pt, pos, pages=0, block_len=1):
    """The kernel by its plan, or at ``pages`` a visit."""
    if not pages:
        return pd.paged_decode_attention(
            q, pk, pv, pt, pos, softmax_scale=D ** -0.5,
            block_len=block_len, interpret=True)
    return jax.jit(functools.partial(
        pd._attend, softmax_scale=D ** -0.5, pages=pages,
        block_len=block_len, interpret=True))(q, pk, pv, pt, pos)


def _agree(got, want, live, dtype):
    got, want = (np.asarray(a, np.float32)[live] for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, **TOL[dtype])


# the rows' contexts, the query's token among them; pages of 64, blocks
# of 512
CONTEXTS = {
    # on, one short of and one past a page's edge: the query sits at
    # 63 | 62 | 64 and at 319 | 318 | 320
    "page_edge": [64, 63, 65, 320, 319, 321],
    # the same at a block's edge
    "block_edge": [512, 511, 513],
    "a_row_of_one_token": [1, 300],
    "the_cell_s_contexts": [256, 288, 352, 301],
    "mixed_16_to_2560": [16, 2560, 700, 129],
}


def _several(T):
    """Contexts of rows of ``T`` queries: ragged, a row of its queries
    alone, queries on both sides of a page's edge (the first sits two
    short of it: under the block mask the later ones see a page the
    first does not) and of a 512-token block's, a page's last
    positions, a null row."""
    return [301, T, 62 + T, 510 + T, 64, None, 1288]


# one query a row over every set of contexts, several over their own
LOOP_EQUALS = [(name, "one_query") for name in CONTEXTS] + [
    ("several_queries", queries) for queries in QUERIES
    if queries != "one_query"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("group", list(GROUPS))
@pytest.mark.parametrize("name,queries", LOOP_EQUALS)
def test_kernel_equals_the_block_loop(name, queries, group, dtype):
    H, KH = GROUPS[group]
    T, block_len = QUERIES[queries]
    contexts = CONTEXTS[name] if T == 1 else _several(T)
    live = [b for b, n in enumerate(contexts) if n]
    args = _inputs(contexts, dtype, max_pages=64, H=H, KH=KH, T=T,
                   block_len=block_len)
    got = _kernel(*args, block_len=block_len)
    assert got.shape == (len(contexts), T, H, D)
    _agree(got, _loop(*args, block_len=block_len), live, dtype)


@pytest.mark.parametrize("queries", ["one_query", "T4_causal", "T4_block"])
@pytest.mark.parametrize("group", list(GROUPS))
def test_the_table_s_last_page(group, queries):
    """A context that fills the table, beside a short one: the last
    group of the widest row is the table's last columns, and a block's
    four queries are the table's last positions."""
    H, KH = GROUPS[group]
    T, block_len = QUERIES[queries]
    args = _inputs([64 * PAGE, 72], jnp.float32, max_pages=64, H=H,
                   KH=KH, T=T, block_len=block_len)
    _agree(_kernel(*args, block_len=block_len),
           _loop(*args, block_len=block_len), slice(None), jnp.float32)


@pytest.mark.parametrize("queries", ["one_query", "T4_block"])
@pytest.mark.parametrize("pages", [1, 2, 4, 8, 16])
def test_any_number_of_pages_a_visit(pages, queries):
    """The plan's count is a price, not a meaning: one page a visit or
    more than the table holds a row's groups read the same."""
    T, block_len = QUERIES[queries]
    args = _inputs([T, 64, 64 + T, 700, 128 + T], jnp.float32,
                   max_pages=12, T=T, block_len=block_len)
    _agree(_kernel(*args, pages=pages, block_len=block_len),
           _loop(*args, block_len=block_len), slice(None), jnp.float32)


@pytest.mark.parametrize("queries", ["one_query", "T4_causal", "T4_block"])
def test_a_null_row_with_a_stale_position_changes_nothing(queries):
    """A row no request owns is not walked, whatever its position says:
    it reads out zeros, and the live rows read what they read beside a
    calm one."""
    T, block_len = QUERIES[queries]
    contexts = [1024, None, 300, None]
    args = _inputs(contexts, jnp.float32, max_pages=64, T=T,
                   block_len=block_len)
    got = np.asarray(_kernel(*args, block_len=block_len))
    _agree(got, _loop(*args, block_len=block_len), [0, 2], jnp.float32)
    assert not got[[1, 3]].any()
    calm = _inputs(contexts, jnp.float32, max_pages=64, stale=0, T=T,
                   block_len=block_len)
    np.testing.assert_array_equal(
        got, np.asarray(_kernel(*calm, block_len=block_len)))


@functools.lru_cache(maxsize=None)
def _jitted_schedule(**static):
    return jax.jit(functools.partial(pd.visit_schedule, **static))


def _schedule(pt, pos, pages, page_size=PAGE, T=1, block_len=1):
    """``visit_schedule``'s results as NumPy, the ids as the [visits,
    pages] a visit FETCHES: the dense layout [rows, groups, pages] read
    at each run visit's (row, group), which is what the kernel's index
    map does."""
    ids, row_of, group_of, count, n = (
        np.asarray(a) for a in _jitted_schedule(
            page_size=page_size, pages=pages, queries=T,
            block_len=block_len)(jnp.asarray(pt), jnp.asarray(pos)))
    B, max_pages = pt.shape
    assert row_of.shape == group_of.shape == (B * -(-max_pages // pages),)
    rows, groups = row_of[:n], group_of[:n]
    return ids.reshape(B, -1, pages)[rows, groups], rows, groups, count


@pytest.mark.parametrize("pages,want", [(1, 16 + 1 + 5 + 1),
                                        (2, 8 + 1 + 3 + 1),
                                        (8, 2 + 1 + 1 + 1)])
def test_the_grid_is_each_row_s_own_pages(pages, want):
    """A rider is walked to its own last page and fetches its own pages
    alone; a null row gets one empty visit whatever its stale
    position."""
    pt, pos = _rows([1024, None, 300, None], 64, np.random.default_rng(0),
                    stale=100_000)
    table = np.asarray(pt)
    ids, rows, groups, count = _schedule(table, pos, pages)
    assert len(ids) == want
    assert count.tolist() == [16, 0, 5, 0]
    assert rows.tolist() == sorted(rows.tolist())
    for b in range(4):
        mine = ids[rows == b]
        assert groups[rows == b].tolist() == list(range(len(mine)))
        held = mine.reshape(-1)[:count[b]]
        assert held.tolist() == table[b, :count[b]].tolist()
    # a slot past a row's last page repeats what it fetched last: the
    # pipeline fetches no page for it
    at = groups[:, None] * pages + np.arange(pages)[None]
    past = at >= count[rows][:, None]
    assert (ids[1:][past[1:]] == ids[:-1][past[1:]]).all()


@pytest.mark.parametrize("T,block_len,want", [
    (1, 1, [1, 1, 2, 0]), (4, 1, [1, 2, 2, 0]), (4, 4, [1, 2, 2, 0]),
    (4, 12, [2, 2, 2, 0])],
    ids=["one_query", "T4_causal", "T4_block", "T4_in_blocks_of_twelve"])
def test_a_row_is_walked_to_the_last_page_any_of_its_queries_sees(
        T, block_len, want):
    """Rows whose first query sits at 59 | 62 | 64 (and a null one): the
    last of four queries sits at 62 | 65 | 67, past the first's page
    from 62 on. Blocks that divide a page end where the last query's
    page does; under blocks of twelve the query at 62 sees to 71, a
    page further than it sits."""
    pt = np.asarray([[3, 4, 0], [5, 6, 0], [7, 8, 0], [0, 0, 0]], np.int32)
    pos = np.asarray([59, 62, 64, 9999], np.int32)
    ids, rows, _g, count = _schedule(pt, pos, 1, T=T, block_len=block_len)
    assert count.tolist() == want
    assert len(ids) == sum(max(c, 1) for c in want)
    for b in range(3):
        assert ids[rows == b, 0].tolist() == pt[b, :want[b]].tolist()
    # the null row's one visit fetches what the row before it left
    assert ids[rows == 3, 0].tolist() == [8]


# a small table whose width is no whole number of groups at 8 or 16
# pages a visit, pages of 8 tokens
WALK_ROWS, WALK_COLUMNS, WALK_PAGE, WALK_FILLS = 6, 20, 8, 50


def _fills(pages, T, block_len, seed):
    """``WALK_FILLS`` seeded (table, pos) of ONE shape: ragged rows with
    null ones among them (a stale position each), and by turns rows of
    one page, rows that end on a group's edge, a null first row, a full
    table."""
    rng = np.random.default_rng(seed)
    B, mp, P = WALK_ROWS, WALK_COLUMNS, WALK_PAGE
    for fill in range(WALK_FILLS):
        held = rng.integers(1, mp + 1, B)
        held[rng.random(B) < 0.25] = 0
        if fill % 5 == 1:
            held[rng.permutation(B)[:2]] = 1
        elif fill % 5 == 2:
            held[rng.permutation(B)[:3]] = pages * rng.integers(
                1, mp // pages + 1, 3)
        elif fill % 5 == 3:
            held[0] = 0
        elif fill % 5 == 4:
            held[:] = mp
        ids = 1 + rng.permutation(B * mp).reshape(B, mp).astype(np.int32)
        table = np.where(np.arange(mp)[None] < held[:, None], ids, 0)
        # the row's LAST query's block ends in its last held page, on
        # any token of it the mask allows; a full row may sit past the
        # table's end, where the walk stops at the table
        end = held * P - rng.integers(0, P // block_len, B) * block_len
        end += np.where(held == mp, rng.integers(0, 3, B) * P, 0)
        pos = np.where(held > 0, end - T, rng.integers(0, 10_000, B))
        yield table.astype(np.int32), pos.astype(np.int32)


def _walk(table, pos, page_size, pages, T, block_len):
    """The visits by a plain walk of the table: row after row, group
    after group, each slot remembering the page it fetched last (the
    null page before any). ([visits, pages] page ids, rows, groups,
    pages a row is walked to)."""
    B, max_pages = table.shape
    slots = [0] * pages
    ids, rows, groups, counts = [], [], [], []
    for b in range(B):
        last = int(pos[b]) + T - 1
        last = (last // block_len + 1) * block_len - 1
        count = min(last // page_size + 1, max_pages) if table[b, 0] else 0
        counts.append(count)
        for g in range(max(-(-count // pages), 1)):
            for c in range(pages):
                if g * pages + c < count:
                    slots[c] = int(table[b, g * pages + c])
            ids.append(list(slots))
            rows.append(b)
            groups.append(g)
    return ids, rows, groups, counts


@pytest.mark.parametrize("queries", ["one_query", "T4_block"])
@pytest.mark.parametrize("pages", [1, 2, 4, 8, 16])
def test_the_schedule_is_a_plain_walk_of_the_table(pages, queries):
    """Every visit's row, group and the page each of its slots fetches,
    against a walk that remembers what each slot fetched last: one
    compiled shape a case, fed fifty tables. The pages FETCHED (a
    slot's id differing from the visit before; the first visit fetches
    every slot) are the rows' own, once each, and a null page for each
    slot nobody held at the first visit."""
    T, block_len = QUERIES[queries]
    for table, pos in _fills(pages, T, block_len, seed=pages):
        ids, rows, groups, count = _schedule(table, pos, pages, WALK_PAGE,
                                             T, block_len)
        want, want_rows, want_groups, want_count = _walk(
            table, pos, WALK_PAGE, pages, T, block_len)
        assert count.tolist() == want_count
        assert rows.tolist() == want_rows
        assert groups.tolist() == want_groups
        assert ids.tolist() == want
        fetched = pages + int((ids[1:] != ids[:-1]).sum())
        nobody_yet = int((np.arange(pages) >= count[0]).sum())
        assert fetched == sum(want_count) + nobody_yet


def test_the_pages_fetched_are_the_rows_own_once_each():
    """At a cell's own table (Mistral's 32 x 64 at eight pages a visit,
    contexts of 16-2,560 tokens, a quarter of the rows null): a slot's
    page changes between consecutive visits exactly ``sum(count)``
    times, plus at most ``pages`` for the slots nobody held yet at the
    first visit."""
    rng = np.random.default_rng(0)
    contexts = [None if rng.random() < 0.25 else int(n)
                for n in rng.integers(16, 2561, 32)]
    pt, pos = _rows(contexts, 64, rng, stale=100_000)
    ids, _rows_of, _groups, count = _schedule(np.asarray(pt), pos, 8)
    assert count.tolist() == [0 if n is None else -(-n // PAGE)
                              for n in contexts]
    fetched = 8 + int((ids[1:] != ids[:-1]).sum())
    assert 0 <= fetched - int(count.sum()) <= 8
    # no page is fetched twice
    changed = np.concatenate([np.ones((1, 8), bool), ids[1:] != ids[:-1]])
    held = ids[changed & (ids != 0)]
    assert len(held) == len(set(held.tolist())) == int(count.sum())


@pytest.mark.parametrize("rows,max_pages,pages,T,block_len", [
    (128, 512, 4, 4, 4), (32, 64, 8, 1, 1), (32, 256, 16, 1, 1),
    (128, 64, 16, 1, 1), (32, 3584, 16, 1, 1)],
    ids=["sdar", "mistral", "mellum2", "kimi_linear", "the_widest"])
def test_the_schedule_gathers_nothing_a_visit(rows, max_pages, pages, T,
                                              block_len):
    """The mechanism, pinned on the lowered text (no chip, nothing
    compiled): at a cell's table no ``gather`` of the schedule has a
    result as long as the visits (the form before gathered a table row
    and ``pages`` single ids a visit: 65,536 of them at SDAR's table,
    1.8 ms a forward); its two gathers are over [rows, pages]."""
    text = _jitted_schedule(
        page_size=PAGE, pages=pages, queries=T, block_len=block_len).lower(
        jax.ShapeDtypeStruct((rows, max_pages), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32)).as_text()
    visits = rows * -(-max_pages // pages)
    gathers = re.findall(r'"?stablehlo\.(?:dynamic_)?gather"?\(.*'
                         r'-> tensor<([0-9x]+)xi32>', text)
    assert gathers and "scatter" not in text
    for shape in gathers:
        dims = [int(d) for d in shape.split("x")]
        assert visits not in dims and dims == [rows, pages], shape


@pytest.mark.parametrize("queries", ["one_query", "T4_causal", "T4_block"])
def test_pos_advanced_inside_a_loop_across_a_page_s_edge(queries):
    """The decode dispatch's case: the schedule is part of the program,
    recomputed from ``pos`` every step, so a context that crosses a
    page's edge (and a group's) in the middle of a dispatch is still
    attended whole; a block program moves ``pos`` a block a commit."""
    T, block_len = QUERIES[queries]
    q, pk, pv, pt, pos = _inputs([125 + T, 59 + T, None], jnp.float32,
                                 max_pages=8, T=T, block_len=block_len)
    # the rows' tables hold the pages the steps walk into
    pt = pt.at[0, 2].set(5).at[1, 1].set(6)

    def steps(attend):
        def body(i, carry):
            pos, out = carry
            y = attend(q, pk, pv, pt, pos, block_len=block_len)
            return pos + T, out.at[i].set(y)
        out = jnp.zeros((6,) + q.shape, q.dtype)
        return jax.lax.fori_loop(0, 6, body, (pos, out))[1]

    got = jax.jit(lambda: steps(functools.partial(_kernel, pages=2)))()
    want = jax.jit(lambda: steps(_loop))()
    _agree(got, want, (slice(None), [0, 1]), jnp.float32)
    assert not np.asarray(got)[:, 2].any()


def test_pages_per_visit_follows_the_visit_s_scores():
    plan = functools.partial(pd.pages_per_visit, page_size=64,
                             max_pages=64)
    assert plan(16, kv_heads=16) == 8       # Ouro, OLMoE
    assert plan(32, kv_heads=8) == 8        # Mistral
    assert plan(32, kv_heads=4) == 16       # Mellum 2
    assert plan(64, kv_heads=8) == 4        # Solar-Open2
    assert plan(16, kv_heads=1) == 16       # no more operands than that
    assert plan(128, kv_heads=16) == 1
    # a block's query rows count as a step's heads do: SDAR's 4 x 32
    assert plan(4 * 32, kv_heads=4) == 4
    assert pd.pages_per_visit(16, 64, 16, max_pages=4) == 4


# ------------------------------------------------------- latent pages
# An entry [c | k_r] in whole lane tiles, its value the first columns,
# and A.X-K1's scale under YaRN (not ``W ** -0.5``)
W, DV, SCALE = 640, 512, 0.1309
# both latent cells' query heads: A.X-K1's, Kimi-Linear's
LATENT_HEADS = [64, 32]


def _latent_inputs(contexts, dtype, max_pages, H, seed=0, stale=100_000):
    """``_inputs`` over a pool of latent pages [n_pages, PAGE, W]."""
    rng = np.random.default_rng(seed)
    B = len(contexts)
    pt, pos = _rows(contexts, max_pages, rng, stale)
    pages = jnp.asarray(
        0.5 * rng.standard_normal((1 + B * max_pages, PAGE, W)), dtype)
    q = jnp.asarray(0.3 * rng.standard_normal((B, 1, H, W)), dtype)
    return q, pages, pt, pos


@jax.jit
def _latent_loop(q, pages, pt, pos):
    return paged_mod._paged_window_attention(
        q, pages, None, None, None, pt, pos, softmax_scale=SCALE,
        value_dim=DV)


def _latent_kernel(q, pages, pt, pos, pages_a_visit=0, span=None):
    """The kernel by its plan, or at ``pages_a_visit`` pages a visit,
    ``span`` of them a contraction."""
    if not pages_a_visit:
        return pd.paged_decode_attention(
            q, pages, None, pt, pos, softmax_scale=SCALE, value_dim=DV,
            interpret=True)
    return jax.jit(functools.partial(
        pd._attend, softmax_scale=SCALE, value_dim=DV, pages=pages_a_visit,
        span=span, interpret=True))(q, pages, None, pt, pos)


# a visit is 16 pages of 64 at both cells' heads: 1,024 tokens
LATENT_CONTEXTS = {
    # inside a page, on its edge, one short of and one past it
    "page_edge": [40, 64, 63, 65, 320, 321],
    # the same at a visit's edge
    "visit_edge": [1024, 1023, 1025, 2048, 2049],
    "a_row_of_one_token": [1, 300],
    "mixed_16_to_2560": [16, 2560, 700, 129],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("H", LATENT_HEADS)
@pytest.mark.parametrize("name", list(LATENT_CONTEXTS))
def test_kernel_equals_the_block_loop_over_latent_pages(name, H, dtype):
    args = _latent_inputs(LATENT_CONTEXTS[name], dtype, max_pages=64, H=H)
    assert pd.pages_per_visit(H, PAGE, 1, 64) == 16
    got = _latent_kernel(*args)
    assert got.shape == (len(LATENT_CONTEXTS[name]), 1, H, DV)
    _agree(got, _latent_loop(*args), slice(None), dtype)


@pytest.mark.parametrize("H", LATENT_HEADS)
def test_the_latent_table_s_last_page(H):
    args = _latent_inputs([64 * PAGE, 70], jnp.float32, max_pages=64, H=H)
    _agree(_latent_kernel(*args), _latent_loop(*args), slice(None),
           jnp.float32)


@pytest.mark.parametrize("pages,span", [(1, 1), (4, 1), (4, 2), (8, 8),
                                        (16, 2), (32, 32)])
def test_any_pages_a_visit_and_a_contraction_over_latent_pages(pages,
                                                               span):
    """How many pages a visit fetches, and how many of them one
    contraction spans, are prices, not meanings."""
    args = _latent_inputs([1, 64, 65, 700, 129], jnp.float32,
                          max_pages=12, H=32)
    _agree(_latent_kernel(*args, pages_a_visit=pages, span=span),
           _latent_loop(*args), slice(None), jnp.float32)


def test_pages_a_contraction_over_kv_pages():
    """The same over K/V pages, whose plan is a page a contraction."""
    args = _inputs([1, 64, 65, 700, 129], jnp.float32, max_pages=12)
    assert pd.pages_per_dot(PAGE * 16, 8) == 1
    assert pd.pages_per_dot(PAGE, 16) == 16        # a latent page's rows
    got = jax.jit(functools.partial(
        pd._attend, softmax_scale=D ** -0.5, pages=4, span=2,
        interpret=True))(*args)
    _agree(got, _loop(*args), slice(None), jnp.float32)


@pytest.mark.parametrize("H", LATENT_HEADS)
def test_a_null_latent_row_with_a_stale_position_changes_nothing(H):
    contexts = [1100, None, 300, None]
    args = _latent_inputs(contexts, jnp.float32, max_pages=64, H=H)
    got = np.asarray(_latent_kernel(*args))
    _agree(got, _latent_loop(*args), [0, 2], jnp.float32)
    assert not got[[1, 3]].any()
    calm = _latent_inputs(contexts, jnp.float32, max_pages=64, H=H,
                          stale=0)
    np.testing.assert_array_equal(got, np.asarray(_latent_kernel(*calm)))


def test_latent_pos_advanced_inside_a_loop_across_a_page_s_edge():
    q, pages, pt, pos = _latent_inputs([126, 60, None], jnp.float32,
                                       max_pages=8, H=32)
    pt = pt.at[0, 2].set(5).at[1, 1].set(6)

    def steps(attend):
        def body(i, carry):
            pos, out = carry
            return pos + 1, out.at[i].set(attend(q, pages, pt, pos))
        out = jnp.zeros((6,) + q.shape[:3] + (DV,), q.dtype)
        return jax.lax.fori_loop(0, 6, body, (pos, out))[1]

    got = jax.jit(lambda: steps(functools.partial(
        _latent_kernel, pages_a_visit=2)))()
    want = jax.jit(lambda: steps(_latent_loop))()
    _agree(got, want, (slice(None), [0, 1]), jnp.float32)
    assert not np.asarray(got)[:, 2].any()


# ------------------------------------------------------------ the choice

def _spied(monkeypatch):
    """``calls``: the kernel's calls from here on (it returns zeros)."""
    calls = []

    def spy(q, pk, pv, page_table, pos, *, softmax_scale, value_dim,
            block_len):
        calls.append((q.shape, pk.shape) + ((block_len,)
                                            if block_len > 1 else ()))
        return jnp.zeros(q.shape[:3] + (value_dim or q.shape[3],),
                         q.dtype)
    monkeypatch.setattr(pd, "paged_decode_attention", spy)
    return calls


def _call(T=1, H=32, KH=8, D=128, int8=False, latent=False,
          dtype=jnp.bfloat16, pool_dtype=None, page=PAGE, max_pages=64,
          value_dim=DV, rows=32, block_len=1):
    """Trace one ``_paged_window_attention`` call of ``rows`` rows of T
    queries."""
    pool_dtype = pool_dtype or dtype
    q = jax.ShapeDtypeStruct((rows, T, H, D), dtype)
    pt = jax.ShapeDtypeStruct((rows, max_pages), jnp.int32)
    pos = jax.ShapeDtypeStruct((rows,), jnp.int32)
    if latent:
        pk = jax.ShapeDtypeStruct((513, page, D), pool_dtype)
        return jax.eval_shape(
            lambda q, pk, pt, pos: paged_mod._paged_window_attention(
                q, pk, None, None, None, pt, pos, softmax_scale=0.1,
                value_dim=value_dim, block_len=block_len), q, pk, pt, pos)
    pk = jax.ShapeDtypeStruct((513, page, KH, D),
                              jnp.int8 if int8 else pool_dtype)
    sk = jax.ShapeDtypeStruct((513, KH), jnp.float32) if int8 else None
    return jax.eval_shape(
        lambda q, pk, sk, pt, pos: paged_mod._paged_window_attention(
            q, pk, pk, sk, sk, pt, pos, block_len=block_len),
        q, pk, sk, pt, pos)


LOOP_CASES = {
    # 256 x 32 query rows against one page's 512 rows: 16 MiB of
    # scores, thirty-two times a visit's
    "a_prefill_chunk": dict(T=256),
    "a_prefill_chunk_of_blocks": dict(T=256, KH=4, block_len=4),
    # a speculative verify's few queries under the causal mask, though
    # five of them fit a visit's scores
    "a_verify_of_five_tokens": dict(T=5),
    "a_verify_over_sixteen_kv_heads": dict(T=5, H=16, KH=16),
    "a_verify_past_a_visit_s_scores": dict(T=16),
    "one_query_past_a_visit_s_scores": dict(H=128, KH=32),
    "a_verify_whose_rows_fill_no_whole_sublane_tile": dict(T=3, H=8, KH=8),
    "int8_scales": dict(int8=True),
    "a_block_over_int8_scales": dict(int8=True, T=4, KH=4, block_len=4),
    # what the loop keeps of a latent pool
    "a_latent_verify_of_five_tokens": dict(latent=True, H=64, D=W, T=5),
    "a_latent_block_of_four": dict(latent=True, H=32, D=W, T=4,
                                   block_len=4),
    "a_float32_latent_pool": dict(latent=True, H=64, D=W,
                                  dtype=jnp.float32),
    "a_latent_value_of_no_whole_lane_tile": dict(latent=True, H=64, D=W,
                                                 value_dim=192),
    "latent_heads_that_fill_no_whole_sublane_tile": dict(
        latent=True, H=8, D=W),
    "float32_operands": dict(dtype=jnp.float32),
    "a_pool_of_another_type": dict(pool_dtype=jnp.float32),
    "a_head_of_half_a_lane_tile": dict(D=64),
    "heads_that_fill_no_whole_sublane_tile": dict(H=8, KH=8),
    "a_page_of_no_whole_sublane_tile": dict(page=2, KH=4),
    # 32 x 4,096 pages: a schedule of 590 KB of the chip's 1 MiB of
    # scalar memory
    "a_table_wider_than_the_scalar_memory": dict(max_pages=4096),
    # SDAR's block at TWO pages a visit would be 525,312 B of schedule:
    # a page of twice the rows halves the plan, and the cell's table
    # (128 x 512) no longer fits
    "a_block_whose_schedule_is_wider_than_the_scalar_memory": dict(
        T=4, KH=4, rows=128, max_pages=512, page=128, block_len=4),
}


@pytest.mark.parametrize("name", list(LOOP_CASES))
def test_the_loop_keeps_what_the_kernel_is_not_for(name, monkeypatch):
    calls = _spied(monkeypatch)
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    case = LOOP_CASES[name]
    out = _call(**case)
    assert not calls
    width = (case.get("value_dim", DV) if case.get("latent")
             else case.get("D", 128))
    assert out.shape == (case.get("rows", 32), case.get("T", 1),
                         case.get("H", 32), width)


@pytest.mark.parametrize("H,KH", [(16, 16), (32, 8), (32, 4), (64, 8)])
def test_a_decode_step_over_kv_pages_on_one_tpu_takes_the_kernel(
        H, KH, monkeypatch):
    calls = _spied(monkeypatch)
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    assert _call(H=H, KH=KH).shape == (32, 1, H, 128)
    assert calls == [((32, 1, H, 128), (513, PAGE, KH, 128))]


# what fits a visit's scores beside one query a row: ``sdar-30b-d6
# .gen-sat``'s block (128 rows of 4 positions, 32 heads on 4, a table of
# 512 columns, the block mask passed on) and a smaller model's block of
# eight
@pytest.mark.parametrize("case", [
    dict(T=4, H=32, KH=4, rows=128, max_pages=512, block_len=4),
    dict(T=8, H=16, KH=4, block_len=8)],
    ids=["sdar_s_block", "a_block_of_eight"])
def test_a_few_queries_a_row_over_kv_pages_on_one_tpu_take_the_kernel(
        case, monkeypatch):
    calls = _spied(monkeypatch)
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    rows, T, H, KH = (case.get("rows", 32), case["T"], case["H"],
                      case["KH"])
    assert _call(**case).shape == (rows, T, H, 128)
    assert calls == [((rows, T, H, 128), (513, PAGE, KH, 128),
                      case["block_len"])]
    # off the chip the same call is the loop's
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: False)
    assert _call(**case).shape == (rows, T, H, 128)
    assert len(calls) == 1


@pytest.mark.parametrize("rows,H,max_pages", [(32, 64, 256),
                                              (128, 32, 64)],
                         ids=["axk1", "kimi_linear"])
def test_a_decode_step_over_latent_pages_on_one_tpu_takes_the_kernel(
        rows, H, max_pages, monkeypatch):
    """Both latent cells' decode steps: the absorbed queries [rows, 1,
    H, 640] over pages [64, 640] whose value is 512 wide."""
    calls = _spied(monkeypatch)
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    bf16, i32 = jnp.bfloat16, jnp.int32
    out = jax.eval_shape(
        lambda q, pk, pt, pos: paged_mod._paged_window_attention(
            q, pk, None, None, None, pt, pos, softmax_scale=SCALE,
            value_dim=DV),
        jax.ShapeDtypeStruct((rows, 1, H, W), bf16),
        jax.ShapeDtypeStruct((513, PAGE, W), bf16),
        jax.ShapeDtypeStruct((rows, max_pages), i32),
        jax.ShapeDtypeStruct((rows,), i32))
    assert out.shape == (rows, 1, H, DV)
    assert calls == [((rows, 1, H, W), (513, PAGE, W))]


@pytest.mark.parametrize("rows,T,max_pages,pages,groups", [
    (32, 1, 3584, 16, 224), (128, 4, 512, 4, 128)],
    ids=["one_query_32_heads", "sdar_s_block_of_128_query_rows"])
def test_the_widest_table_the_kernel_serves(rows, T, max_pages, pages,
                                            groups, monkeypatch):
    """The schedule goes in by scalar prefetch, so the rule bounds it:
    32 rows x 3,584 pages (229,376 tokens a row) is the kernel's,
    which tests/test_chip_compile.py builds for the chip (32 x 4,096
    is among the loop's cases above); and SDAR's 128 rows x 512 pages
    at the FOUR pages a visit that 4 x 32 query rows against a page's
    256 plan (394,240 B; at two it would not fit, and the cell would
    keep the loop without a word)."""
    calls = _spied(monkeypatch)
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    _call(T=T, H=32, KH=4, rows=rows, max_pages=max_pages, block_len=T)
    assert len(calls) == 1
    assert pd.pages_per_visit(T * 32, PAGE, 4, max_pages) == pages
    assert pd.schedule_bytes(rows, max_pages, pages) == 4 * (
        rows * groups * (pages + 1 + 1) + 2 * rows) <= pd._SCHEDULE_BYTES
    assert pd.schedule_bytes(rows, max_pages, pages // 2) > \
        pd._SCHEDULE_BYTES
    # what visit_schedule hands over is what the rule counted
    out = jax.eval_shape(
        functools.partial(pd.visit_schedule, page_size=PAGE, pages=pages,
                          queries=T, block_len=T),
        jax.ShapeDtypeStruct((rows, max_pages), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32))
    ids, row_of, group_of, count, _n = out
    assert 4 * (ids.size + row_of.size + group_of.size + count.size
                + rows) == pd.schedule_bytes(rows, max_pages, pages)


@pytest.mark.parametrize("pool", [dict(), dict(latent=True, H=64, D=W)],
                         ids=["kv_pages", "latent_pages"])
def test_the_cpu_and_a_mesh_keep_the_loop(pool, monkeypatch,
                                          cpu_mesh_devices):
    """The backend and the ambient mesh decide, by grouped_matmul's
    rule: the CPU (every other test here), and a multi-device mesh on a
    TPU, which GSPMD cannot partition a Mosaic kernel for."""
    from jax.sharding import Mesh
    calls = _spied(monkeypatch)
    _call(**pool)                                     # the CPU
    assert not calls
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(cpu_mesh_devices[:2]), ("tensor",))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        _call(**pool)
    assert not calls
    _call(**pool)
    assert len(calls) == 1


def _kernel_shaped():
    """A dense model whose decode step the kernel serves on one TPU:
    bfloat16, 16 heads of 128 on 4 KV heads (tp=4 divides them)."""
    from ray_tpu.models.llama import Llama, llama_tiny
    cfg = llama_tiny(dim=2048, n_layers=1, n_heads=16, n_kv_heads=4,
                     hidden_dim=128, max_seq_len=256,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    return cfg, Llama(cfg)


@pytest.mark.parametrize("tp", [1, 4])
def test_a_sharded_replica_s_decode_program_keeps_the_loop(
        tp, monkeypatch, cpu_mesh_devices):
    """The rule reads the AMBIENT mesh, and a step program makes its
    replica's mesh ambient for a dense model as for a mixture
    (serve/step_programs.py ``ambient_mesh``): traced as the engine
    builds it on a TPU, a tensor-parallel replica's decode program
    holds no Pallas call, and a one-chip replica's holds the
    kernel."""
    from ray_tpu.models.kv_cache import init_kv_pool
    from ray_tpu.serve import step_programs
    from ray_tpu.serve.sharding import EngineSharding
    cfg, model = _kernel_shaped()
    mesh = None if tp == 1 else EngineSharding.build(
        cfg, tp=tp, devices=cpu_mesh_devices[:tp]).mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S = 4
    # the programs are cached by (model, knobs): nothing traced here
    # may be found by another test
    decode = step_programs._jit_decode.__wrapped__(
        model, 0.0, 8, S, False, mesh)
    i32 = jnp.int32
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), i32))
    pages = jax.eval_shape(lambda: init_kv_pool(cfg, 17, PAGE))
    text = str(jax.make_jaxpr(decode)(
        params, pages, jax.ShapeDtypeStruct((S, 4), i32),
        jax.ShapeDtypeStruct((S,), i32), jax.ShapeDtypeStruct((S,), i32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((), i32)))
    assert ("pallas_call" in text) == (tp == 1)


def test_the_kernel_sits_under_the_loop_s_scope(monkeypatch):
    """A device trace's split of a step by scope keeps counting the
    call as attention: it is named under ``attn_scores``."""
    monkeypatch.setattr(pd, "_on_one_tpu", lambda: True)
    q = jnp.zeros((2, 1, 16, 128), jnp.bfloat16)
    pool = jnp.zeros((9, PAGE, 16, 128), jnp.bfloat16)
    pt, pos = jnp.ones((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(paged_mod._paged_window_attention)(
        q, pool, pool, None, None, pt, pos)
    scopes = [str(e.source_info.name_stack) for e in jaxpr.eqns]
    assert scopes and set(scopes) == {"attn_scores"}, scopes
    inner = [e for e in jaxpr.eqns
             if e.primitive.name in ("jit", "pjit")]
    assert len(inner) == 1 and inner[0].params["name"] == (
        "paged_decode_attention")
    calls = [e for e in inner[0].params["jaxpr"].eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert "paged_decode" in str(calls[0].params)


# ----------------------------------------------- the engine's counter

def test_kernel_pages_counts_each_rider_to_its_own_last_page():
    count = functools.partial(pd.kernel_pages, page_size=64,
                              max_pages=64)
    assert count([1]) == 1
    assert count([64]) == 1
    assert count([65]) == 2
    assert count([256, 300, 352]) == 4 + 5 + 6
    assert count([4096, 9999]) == 64 + 64       # inside the table
    assert count([]) == 0


def test_the_round_event_carries_decode_kernel_pages(monkeypatch):
    """0 where the decode program holds no kernel (the CPU); each
    rider's pages to its own last one where it does, beside
    ``decode_context_tokens``."""
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    eng = LLMEngine(model, params, max_slots=4, page_size=16, n_pages=33,
                    chunk=4).start()
    try:
        def rounds():
            return [(e[5]["decode_kernel_pages"],
                     e[5]["decode_context_tokens"])
                    for e in eng.events.snapshot()
                    if e[2] == "round" and e[5]["decode_steps"]]

        eng.submit(list(range(1, 40)), max_new_tokens=6).result()
        assert eng.wait_idle(10)
        before = rounds()
        assert before and not any(k for k, _c in before)
        assert eng.stats["decode_kernel_pages"] == 0
        asked = []
        monkeypatch.setattr(
            pd, "applies", lambda *a: asked.append(a) or True)
        eng.submit(list(range(1, 40)), max_new_tokens=6).result()
        assert eng.wait_idle(10)
        after = rounds()[len(before):]
        # one rider: the pages of 16 that hold its context
        assert after and all(k == -(-c // 16) for k, c in after)
        assert eng.stats["decode_kernel_pages"] == sum(
            k for k, _c in after)
        # the program's own question, of the pool's own layout
        q, k, v, sk, table, value_dim, block_len = asked[0]
        assert value_dim is None and block_len == 1
        assert (q.shape, q.dtype) == (
            (4, 1, cfg.n_heads, cfg.head_dim), jnp.float32)
        assert k.shape == v.shape == (1, 16, cfg.n_kv_heads, cfg.head_dim)
        assert k.dtype == v.dtype == jnp.float32 and sk is None
        assert table.shape == (4, eng.max_pages)
    finally:
        eng.shutdown()


def test_a_verify_counts_no_kernel_pages(monkeypatch):
    """A speculative verify is one forward of ``spec_len + 1`` queries a
    row under the causal mask, which the rule leaves to the loop: the
    counter does not ask for it and counts no pages for it, whatever
    the decode program's layers answer."""
    from ray_tpu.models.llama import Llama, llama_tiny
    from ray_tpu.serve.engine import LLMEngine
    cfg = llama_tiny(dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    eng = LLMEngine(model, params, max_slots=4, page_size=16, n_pages=33,
                    chunk=4, spec_len=4, spec_ngram=2).start()
    prompt = [3, 4, 5, 6, 7] * 5
    try:
        def rounds():
            return [e[5] for e in eng.events.snapshot()
                    if e[2] == "round" and e[5]["decode_steps"]]

        eng.submit(prompt, max_new_tokens=24).result()
        assert eng.wait_idle(10)
        before, verifies = rounds(), eng.stats["spec_rounds"]
        assert verifies and not any(
            r["decode_kernel_pages"] for r in before)
        asked = []
        monkeypatch.setattr(
            pd, "applies", lambda q, *a: asked.append(q.shape[1]) or True)
        eng.submit(prompt, max_new_tokens=24).result()
        assert eng.wait_idle(10)
        after = rounds()[len(before):]
        served = [r for r in after if r["decode_kernel_pages"]]
        assert all(r["decode_kernel_pages"]
                   == -(-r["decode_context_tokens"] // 16) for r in served)
        # the decode dispatches asked, with one query a row; the
        # verifies (one step each) did not
        assert set(asked) == {1} and len(asked) == len(served)
        assert len(after) - len(served) \
            == eng.stats["spec_rounds"] - verifies > 0
    finally:
        eng.shutdown()


def test_a_sharded_engine_counts_no_kernel_pages(monkeypatch,
                                                 cpu_mesh_devices):
    """On a TPU a tensor-parallel replica's decode program holds the
    loop, and the engine's counter says so: it asks the rule under the
    replica's mesh, as the program did. (That the engine decodes at
    all here says the same: a Pallas call could not run on these
    devices.) The same model on one chip would count its pages, and an
    int8 pool would not: the pool's own layout is what is asked."""
    from ray_tpu.serve.engine import LLMEngine
    from ray_tpu.serve.sharding import EngineSharding
    cfg, model = _kernel_shaped()
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    sh = EngineSharding.build(cfg, tp=4, devices=cpu_mesh_devices[:4])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = LLMEngine(model, params, sharding=sh, max_slots=4, page_size=16,
                    n_pages=33, chunk=4).start()
    try:
        eng.submit(list(range(1, 40)), max_new_tokens=6).result()
        assert eng.wait_idle(10)
        assert eng.stats["decode_steps"] and not eng.stats[
            "decode_kernel_pages"]
        assert not any(e[5]["decode_kernel_pages"]
                       for e in eng.events.snapshot() if e[2] == "round")
        assert not eng.accounts.decode_kernel_serves()
        monkeypatch.setattr(eng.accounts, "mesh", None)
        assert eng.accounts.decode_kernel_serves()
        monkeypatch.setattr(eng.accounts, "kv_dtype", "int8")
        assert not eng.accounts.decode_kernel_serves()
    finally:
        eng.shutdown()


def test_the_round_event_carries_decode_kernel_pages_of_latent_pages(
        monkeypatch, cpu_mesh_devices):
    """A latent-attention engine's counter: 0 where the decode program
    holds the loop (the CPU), each rider's pages to its own last one
    where the rule says the kernel serves, and the rule is asked of the
    latent pool's own layout: absorbed queries as wide as a stored
    entry, no V pages, the value the entry's latent. Under a
    multi-device mesh the same question reads no."""
    from jax.sharding import Mesh
    from ray_tpu.models.axk1 import AXK1, axk1_tiny
    from ray_tpu.models.kv_cache import latent_page_width
    from ray_tpu.serve.engine import LLMEngine

    def engine(cfg):
        model = AXK1(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32))
        return LLMEngine(model, params, max_slots=4, page_size=16,
                         n_pages=33, chunk=4)

    cfg = axk1_tiny(dtype=jnp.float32, n_layers=2)
    eng = engine(cfg).start()
    try:
        def rounds():
            return [(e[5]["decode_kernel_pages"],
                     e[5]["decode_context_tokens"])
                    for e in eng.events.snapshot()
                    if e[2] == "round" and e[5]["decode_steps"]]

        eng.submit(list(range(1, 40)), max_new_tokens=6).result()
        assert eng.wait_idle(10)
        before = rounds()
        assert before and not any(k for k, _c in before)
        asked = []
        monkeypatch.setattr(
            pd, "applies", lambda *a: asked.append(a) or True)
        eng.submit(list(range(1, 40)), max_new_tokens=6).result()
        assert eng.wait_idle(10)
        after = rounds()[len(before):]
        assert after and all(k == -(-c // 16) for k, c in after)
        assert eng.stats["decode_kernel_pages"] == sum(
            k for k, _c in after)
        q, pages, v, sk, table, value_dim, _block_len = asked[0]
        width = latent_page_width(cfg)
        assert (q.shape, q.dtype) == ((4, 1, cfg.n_heads, width),
                                      jnp.float32)
        assert (pages.shape, pages.dtype) == ((1, 16, width), jnp.float32)
        assert v is None and sk is None
        assert value_dim == cfg.kv_lora_rank
        assert table.shape == (4, eng.max_pages)
    finally:
        eng.shutdown()
    monkeypatch.undo()
    # a latent model at widths the kernel takes (bfloat16, 16 heads, an
    # entry of 128 + 16 columns in two lane tiles), never run here
    shaped = engine(axk1_tiny(
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, n_layers=1,
        n_heads=16, kv_lora_rank=128))
    assert not shaped.accounts.decode_kernel_serves()    # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert shaped.accounts.decode_kernel_serves()
    monkeypatch.setattr(shaped.accounts, "mesh", Mesh(
        np.asarray(cpu_mesh_devices[:2]), ("tensor",)))
    assert not shaped.accounts.decode_kernel_serves()


# ----------------------------------- the cells' programs, by their text

_ROOT = pathlib.Path(__file__).resolve().parent.parent
_PINS = json.loads(
    (_ROOT / "tests" / "data" / "paged_decode_lowered.json").read_text())


def _lowered_tool():
    spec = importlib.util.spec_from_file_location(
        "paged_decode_lowered", _ROOT / "tools" / "paged_decode_lowered.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("config", sorted(_PINS["sha256"]))
def test_a_cells_one_query_a_row_lowers_to_the_pinned_text(config):
    """One query a row is the decode step of every serving cell but
    SDAR's, and a wider query tile (PR 64) is none of their business:
    at each cell's own shape (the engine's question of the rule, over
    the deployment's pool) the call lowered for a TPU, the Mosaic
    kernel's body without its debug info, is the text pinned at PR 63's
    tree. A change that MEANS to move these programs re-pins
    (``python tools/paged_decode_lowered.py --write``) and measures the
    cells; one that does not finds out here, not on the chip."""
    if jax.__version__ != _PINS["jax"]:
        pytest.skip("pinned under jax %s" % _PINS["jax"])
    tool = _lowered_tool()
    asked = tool.question(config)
    assert asked[0].shape[1] == 1 and asked[-1] == 1
    text, kernels = tool.lowered(*asked)
    assert kernels == 1
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _PINS["sha256"][config]


def test_every_one_query_cell_is_pinned():
    """The pins name every serving configuration whose decode step asks
    the rule with one query a row (SDAR asks with a block of four;
    DeepSeek-V3.2's layers attend under a choice and ask nothing)."""
    tool = _lowered_tool()
    rest = set(tool.configs()) - set(_PINS["sha256"])
    assert rest == {"sdar-30b-a3b-chat-d6", "deepseek-v3.2-d5-ep32"}
    assert tool.question("deepseek-v3.2-d5-ep32") is None
    q, *_pools, block_len = tool.question("sdar-30b-a3b-chat-d6")
    assert (q.shape[1], block_len) == (4, 4)
