"""A decode step's attention over a K/V or a latent page pool as one
Pallas kernel.

ops/paged_attention.py ``_paged_window_attention`` walks the batch's
pages a block of 512 tokens at a time, for EVERY row up to the longest
live context: a decode step of 16 riders at 256-352 tokens gathers
16 x 512 x 8,192 B = 67 MB a layer where 40 MB are live, first into a
block in page order and then, re-laid head-major, into a second one
(PERF.md section 6, PRs 46 and 47). A decode step is the one shape
where the bytes are all there is: a query row a head against each key
is 2 FLOPs a byte fetched, so what a step costs is what it fetches and
how often it fetches it.

The kernel is the same mathematics (bfloat16 operands as stored,
float32 accumulation in both contractions, float32 scores x the scale,
the causal mask on absolute positions, the online softmax in float32,
``p`` cast to the pool's type before the read-out), each rider's own
pages read once, where they lie:

- the grid is the call's VISITS, one (row, group of ``pages`` logical
  pages) each, a row's groups in order, and its length is a value
  computed on the device from ``page_table`` and ``pos``: a rider is
  walked to ``ceil((pos + 1) / page_size)`` pages and no further, a
  row whose page-table row is null gets one visit that fetches nothing,
  scores nothing and writes zeros. (A grid of fixed length with the
  visits left over skipped is what PR 30's kernel ran, 32 slots x 64
  table columns whatever the context: 15.7 ms a step.)
- the page ids and the visits' schedule go in by scalar prefetch and
  the index maps fetch a page BY ITS ID, as it lies: a page
  ``[page_size, KH, D]`` is one contiguous slab, read as the
  ``[page_size x KH, D]`` matrix the same bytes are (a free view of
  the page-major pool: nothing transposed, nothing copied; PR 30's
  kernel wanted a head-major pool, 5.1 ms a step in copies). A slot of
  a row's last group past its last page repeats the page that slot
  fetched last, which the pipeline does not fetch again. The ids stay
  laid out AS THE TABLE LIES, ``[row, group, slot]``, and a page's
  index map reads ``ids[(row_of[v] x groups + group_of[v]) x pages +
  slot]`` (``visit_schedule``): nothing in the schedule is gathered a
  visit, because the most visits a table allows are its rows x its
  groups whatever the riders hold, and a schedule that gathered a
  table row and ``pages`` ids for each of them cost what the TABLE is
  wide (1.80 ms a forward at 128 rows x 512 columns, 7.6 % of SDAR's;
  0.033 so). The map's two more scalar reads and shifts a slot are not
  free: the pipeline evaluates every map several times a visit and
  shares no read between them, ~0.05 us a visit (SDAR's kernel 1.02 ->
  1.08 ms a layer-step at ~780 visits, a fifth of what the schedule
  saved there; level where a row is one visit: PERF.md section 6, PR
  66, which also has the reading of a visit's BASE handed over in place
  of its group, 1.04);
- both contractions run on the matrix unit over the WHOLE page: every
  query head against every (token, KV head) row of it, the products of
  a head with another KV head's keys masked out of the softmax like
  the keys past the row's position, so that their ``p`` is 0 and the
  read-out ``p @ page`` sums a head's own KV head alone. No head is
  sliced out of a page (a strided read across its sublanes) and no
  page is copied to float32 for the vector unit, which has no
  bfloat16 on a TPU v5e (PR 33's finding, inside a kernel too). It is
  ``KH`` times the needed FLOPs, which are nothing here: 22 MFLOP a
  layer against 40 MB fetched at Ouro's shape.

A row of ``T`` query positions (a block of a model that decodes by
blocks, models/sdar.py: 4 positions under the BLOCK-causal mask) is the
same kernel with a taller query tile: ``q`` [B, T, H, D] is the
``[T x H, D]`` matrix the same bytes are, query row r is position
``r // H`` under head ``r % H``, and the last key it sees is its
block's last (its own position's under the causal mask, which the
kernel computes too and ``applies`` leaves to the loop: a speculative
verify, that no cell runs and one reading had slower), a vector down
the rows where one query a row has a scalar. The
row is walked to the last page ANY of its queries sees, its pages are
still fetched once, and the loop it replaces gathered a 512-token block
of K and of V a rider a layer for four queries as for one (9.6 of
SDAR's 14.0 ms of attention a forward: PERF.md section 6, PR 64). At
``T == 1`` under the causal mask the traced kernel is what it was.

A pool of LATENT pages ``[n_pages, page_size, W]`` (``pv`` None;
models/axk1.py, models/kimi_linear.py: one entry ``[c | k_r]`` a token
that every query head reads, absorbed) is the ``KH = 1`` case of the
same kernel: a page is already the matrix it is read as, no product is
another head's, the value is the first ``value_dim`` columns of the
SAME fetched page (whole lane tiles: a free slice), so a visit fetches
``pages`` operand blocks and not twice that, and the accumulator and
the result are ``value_dim`` wide. Here the bytes are NOT all there is:
64 (or 32) query rows against an entry are 121 (60) FLOPs a byte
fetched where the chip's ridge is 240, and with so few rows streamed
against each 128 x 128 tile of entries the matrix unit is bound by its
tile loads, about the bytes' own time. So a page of 64 entries, half a
tile, is not contracted alone: the visit's pages are one ``[pages x
page_size, W]`` matrix (``pages_per_dot``; a layer-step at A.X-K1's
shape 0.428 ms so, 0.575 a page a ``dot``, the loop 0.804: PERF.md
section 6, PR 48).

How many pages a visit fetches and folds follows from the shapes
(``pages_per_visit``: what a visit's float32 scores come to), and
which calls the kernel serves is ``applies``'s rule, read by
``_paged_window_attention``: shapes, types, the backend and the ambient
mesh (a step program makes its replica's ambient for every model:
serve/step_programs.py ``ambient_mesh``), never a flag.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the backend is a TPU and no multi-device mesh is ambient: one rule
# for every Mosaic kernel that has an XLA form
from ray_tpu.ops.grouped_matmul import on_one_tpu as _on_one_tpu

_NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_NN = (((1,), (0,)), ((), ()))        # a @ b

# Bytes of float32 scores one visit folds at the most: every query head
# against every (token, KV head) row of the visit's pages. Twice the
# vector registers' 256 KiB: measured on v5e (PR 47, PERF.md section 6;
# pages a visit 2 | 4 | 8 | 16, ms a layer-step at the cells' shapes and
# contexts) Ouro's 16 heads on pages of 1,024 rows 0.0703 | 0.0733 |
# **0.0687** | 0.0785, Mistral's 32 on 512 0.0886 | 0.0890 | **0.0688**
# | 0.1012, Mellum 2's 32 on 256 1.023 | 0.710 | 0.603 | **0.604**,
# Solar-Open2's 64 on 512 0.277 | **0.233** | 0.247 | 0.282: each best
# where a visit's scores come to 512 KiB. A latent pool's pages of 64
# rows reach the limit of sixteen first (PR 48; 4 | 8 | 16 | 32: A.X-K1's
# 64 heads at 8,192-8,704 tokens 0.657 | 0.472 | **0.428** | 0.440,
# Kimi-Linear's 32 at 1,024-2,048 0.618 | 0.481 | **0.456** | 0.396: a
# visit folds all its slots, a row's last visit the empty ones too, so
# past sixteen a long row's last visit wastes what a short row's one
# visit saves).
_SCORE_BYTES = 512 << 10
_MAX_PAGES_A_VISIT = 16


def pages_per_visit(H: int, page_size: int, kv_heads: int,
                    max_pages: int) -> int:
    """Logical pages of a row one visit fetches (of K and of V each) and
    folds in one step of the online softmax: the power of two whose
    scores, ``H`` query rows (a row's heads, times its query positions
    where it has several) x ``page_size x kv_heads`` rows a page in
    float32, fill ``_SCORE_BYTES``, inside the table. More pages a visit
    pay a visit's fixed cost and the fold's latency less often (a fold
    is a chain: scores, max, exp, sum, read-out; 0.5 us a page folded
    alone, whatever its size); past what the registers and their spills
    hold the fold itself slows. A slot past a row's last page costs no
    fetch, so a short context loses nothing to a wide visit. 16 heads
    on 16 KV heads (Ouro, OLMoE) and 32 on 8 (Mistral) go eight pages a
    visit, 32 on 4 (Mellum 2) sixteen, 64 on 8 (Solar-Open2) four, 64
    or 32 on a latent pool's one (A.X-K1, Kimi-Linear) sixteen, a block
    of 4 positions x 32 heads on 4 (SDAR) four."""
    want = max(1, _SCORE_BYTES // (H * page_size * kv_heads * 4))
    return min(1 << (want.bit_length() - 1), _MAX_PAGES_A_VISIT,
               max_pages)


def pages_per_dot(page_rows: int, pages: int) -> int:
    """Pages of a visit ONE contraction spans. A page of at least a
    lane tile's rows (every K/V cell's: 64 tokens x 4-16 KV heads) is
    contracted as it was fetched. A page of fewer (a latent pool's 64
    entries) would fill half the matrix unit's tile in the scores'
    token dimension and half the read-out's contracted depth, so the
    visit's pages are contracted as one ``[pages x page_rows, D]``
    matrix, copied once inside VMEM (1.3 MB a visit beside its fetch).
    Measured on v5e (PR 48; ms a layer-step, 16 pages a visit
    contracted 1 | 2 | 16 at a time): A.X-K1's shape 0.575 | 0.427 |
    **0.428**, Kimi-Linear's 0.486 | 0.456 | **0.456**."""
    return 1 if page_rows >= _LANES else pages


# Scalar memory the visits' schedule may take. It goes in by scalar
# prefetch (``visit_schedule``: a page id a slot, a row and a group a
# visit, a count and a position a row), and a TPU v5e has 1 MiB of it
# for the whole program: compiled for a described v5e (PR 47) a table
# of 32 rows x 4,096 pages (590 KB of schedule at 16 pages a visit)
# builds and one of 32 x 8,192 "ran out of memory in memory space
# smem: used 1.25M of 1.00M". Half of it is the kernel's: 32 rows x
# 3,584 pages of 64 tokens, 229,376 tokens a row, and
# tests/test_chip_compile.py builds that table. A wider one keeps the
# loop.
_SCHEDULE_BYTES = 512 << 10


def schedule_bytes(rows: int, max_pages: int, pages: int) -> int:
    """Bytes of int32 scalars ``visit_schedule`` hands the kernel for a
    table of ``rows`` x ``max_pages`` at ``pages`` a visit."""
    visits = rows * -(-max_pages // pages)
    return 4 * (visits * (pages + 2) + 2 * rows)


def applies(q, pk, pv, sk, page_table, value_dim=None,
            block_len: int = 1) -> bool:
    """Whether the kernel serves ``q`` [B, T, H, D] over the pool
    ``pk``/``pv`` [n_pages, Pg, KH, D], or over the latent pages ``pk``
    [n_pages, Pg, D] whose values are their first ``value_dim`` columns
    (``pv`` None), with the int8 scales ``sk`` (the loop's) under
    ``page_table`` [B, max_pages] and the mask of ``block_len``: a
    row's ``T x H`` query rows against ONE page's ``Pg x KH`` rows fit
    a visit's scores (a decode step; a block of a model that decodes by
    blocks over a K/V pool; a prefill chunk keeps the loop), one query
    a row under the causal mask and over a latent pool (a speculative
    verify's few keep the loop: the kernel serves them, but the one
    reading of it, tools/paged_decode_bench.py ``verify``, was 0.145 ms
    a layer-step against the loop's 0.131 at the plan, and no cell runs
    one: PERF.md section 7), queries and pool bfloat16, whole query
    groups, a head and a value of whole 128-lane tiles, a row's query
    rows and a page's rows in whole sublane tiles, a schedule that fits
    the scalar memory, and a TPU outside any multi-device mesh. Only
    shapes, types and the mask are read: ``_paged_window_attention``
    asks it of its arguments, and the engine of the same shapes for its
    ``decode_kernel_pages``."""
    latent = pv is None
    if sk is not None or pk.ndim != (3 if latent else 4):
        return False
    (T, H, D), Pg = q.shape[1:], pk.shape[1]
    if latent and (not value_dim or value_dim > D or value_dim % _LANES):
        return False
    KH = 1 if latent else pk.shape[2]
    B, max_pages = page_table.shape
    rows = T * H
    return ((T == 1 or (block_len > 1 and not latent))
            and q.dtype == pk.dtype == jnp.bfloat16
            and (latent or pv.dtype == jnp.bfloat16)
            and H % KH == 0 and D % _LANES == 0 and rows % 16 == 0
            and (Pg * KH) % 16 == 0
            and rows * Pg * KH * 4 <= _SCORE_BYTES
            and schedule_bytes(B, max_pages,
                               pages_per_visit(rows, Pg, KH, max_pages))
            <= _SCHEDULE_BYTES
            and _on_one_tpu())


def kernel_pages(ends, page_size: int, max_pages: int) -> int:
    """Pages ONE K/V or latent layer's kernel visits (of K and of V
    each, where there is a V) for
    riders whose last query of a decode dispatch sits at ``end - 1``
    (host integers): each rider to its own last page, inside the table.
    The engine's ``decode_kernel_pages``."""
    return sum(min(-(-int(e) // page_size), max_pages) for e in ends)


def _last_seen(pos, block_len: int):
    """The last key position the query at ``pos`` sees: its own under
    the causal mask (``block_len`` 1), its block's last under the
    block-causal one."""
    if block_len == 1:
        return pos
    return (_split(pos, block_len)[0] + 1) * block_len - 1


def visit_schedule(page_table, pos, page_size: int, pages: int,
                   queries: int = 1, block_len: int = 1):
    """The call's visits, from ``page_table`` [B, max_pages] and ``pos``
    [B] on the device: (the page ids, a visit's row, its group of pages
    within the row, the pages a row is walked to [B], the number of
    visits). A row is walked to the last page ANY of its ``queries``
    sees: its last query's, at ``pos + queries - 1``, under the mask of
    ``block_len``. ``row_of`` and ``group_of`` are as long as the most
    visits the table allows, ``B x G`` at ``G = ceil(max_pages /
    pages)`` groups a row; only the first ``n_visits`` are run.

    ``ids`` [B x G x pages] lies as the TABLE does, by (row, group,
    slot): the visit of group g of row b fetches into slot c the page
    ``ids[(b x G + g) x pages + c]``, which is the table's own entry
    wherever the row holds a page there (``g x pages + c < count[b]``).
    A slot the row does not hold names THE PAGE THAT SLOT FETCHED LAST,
    so that consecutive visits see an unchanged block index there and
    the pipeline fetches nothing: in group g >= 1 the same slot of
    group g - 1 (g is then the row's last group, and the one before it
    is full), in group 0 what the nearest earlier row that held the
    slot fetched into it last, and page 0, the null page, where no
    earlier row held it. Groups past a row's visits are never read.

    Nothing here is as long as the visits but one compare against the
    rows' cumulative visits: the form before gathered a table row and
    ``pages`` single ids a VISIT, so its cost followed the table's
    size (ms a call on a v5e, before | so: 1.797 | 0.033 at SDAR's 128
    rows x 512 columns at four pages a visit, 0.216 | 0.018 at 32 x
    256 at sixteen, 0.075 | 0.013 at 32 x 64 at eight, 2.511 | 0.020
    at the widest table the rule allows, 32 x 3,584: PERF.md section
    6, PR 66)."""
    B, max_pages = page_table.shape
    G = -(-max_pages // pages)
    i32 = jnp.int32
    pos = pos.astype(i32)
    live = page_table[:, 0] != 0
    last_query = pos + (queries - 1) if queries > 1 else pos
    last = _last_seen(last_query, block_len)
    count = jnp.where(live, jnp.minimum(last // page_size + 1, max_pages),
                      0)
    # visit v is group ``group_of[v]`` of row ``row_of[v]``: a row has as
    # many visits as groups of pages, and one where it has none. Its row
    # is the number of rows whose visits have all ended by v, its group
    # v less those rows' visits
    visits = jnp.maximum(-(-count // pages), 1)
    after = jnp.cumsum(visits, dtype=i32)
    visit = jnp.arange(B * G, dtype=i32)
    ended = after[None, :] <= visit[:, None]                  # [B x G, B]
    row_of = jnp.minimum(jnp.sum(ended, axis=1, dtype=i32), B - 1)
    group_of = visit - jnp.sum(jnp.where(ended, visits[None], 0), axis=1,
                               dtype=i32)
    table = jnp.pad(page_table.astype(i32),
                    ((0, 0), (0, G * pages - max_pages)))
    # the last page a row fetches into each slot, and for each row what
    # the nearest EARLIER row that held the slot left there
    slot = jnp.arange(pages, dtype=i32)[None]
    holds = count[:, None] > slot                             # [B, pages]
    last_id = jnp.take_along_axis(
        table, jnp.where(
            holds, slot + pages * ((count[:, None] - 1 - slot) // pages),
            0), axis=1)
    holder = jax.lax.cummax(
        jnp.where(holds, jnp.arange(B, dtype=i32)[:, None], -1), axis=0)
    holder = jnp.concatenate(
        [jnp.full((1, pages), -1, i32), holder[:-1]], axis=0)
    left = jnp.where(
        holder >= 0,
        jnp.take_along_axis(last_id, jnp.maximum(holder, 0), axis=0), 0)
    # a slot past its row's last page: the same slot a group earlier
    table = table.reshape(B, G, pages)
    at = jnp.arange(G * pages, dtype=i32).reshape(1, G, pages)
    ids = jnp.where(at < count[:, None, None], table,
                    jnp.concatenate([left[:, None], table[:, :-1]], axis=1))
    return ids.reshape(-1), row_of, group_of, count, after[-1]


def _split(col, n: int):
    """(col // n, col % n) of non-negative ``col``."""
    if n & (n - 1) == 0:
        return col >> (n.bit_length() - 1), col & (n - 1)
    return jax.lax.div(col, n), jax.lax.rem(col, n)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _decode_kernel(ids_ref, row_ref, group_ref, count_ref, pos_ref,
                   q_ref, *rest, scale: float, pages: int, kv_heads: int,
                   span: int, queries: int, block_len: int):
    del ids_ref                                 # the index maps' alone
    # a latent pool has no V pages: an entry's value is its own first
    # columns, as many as the accumulator is wide
    k_refs, v_refs = rest[:pages], rest[pages:-4]
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    v = pl.program_id(0)
    row, group = row_ref[v], group_ref[v]  # this visit's row, page group
    count = count_ref[row]                 # pages this row visits
    last = pos_ref[row]                    # its (first) query's position
    # a row's query rows: query position r // H under head r % H
    R, dv = q_ref.shape[2], acc_scr.shape[1]
    H = R // queries
    page_rows = k_refs[0].shape[1]         # (token, KV head) rows a page
    page_size = page_rows // kv_heads

    @pl.when(group == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                          # [R, D]
    # column c of a contraction's scores (``span`` pages' rows, one
    # after another) is token c // KH under KV head c % KH; query head
    # h reads KV head h // rep, and where there is one KV head every
    # head reads every row
    col = jax.lax.broadcasted_iota(jnp.int32, (R, span * page_rows), 1)
    token, kv_head = _split(col, kv_heads)
    if kv_heads > 1 or queries > 1:
        head = jax.lax.broadcasted_iota(jnp.int32, col.shape, 0)
    if queries > 1:
        # one query position a row no longer: the last key a query row
        # sees is its own position's, a vector down the rows
        ahead, head = _split(head, H)
        last = last + ahead
    last = _last_seen(last, block_len)
    if kv_heads > 1:
        own = kv_head == _split(head, H // kv_heads)[0]

    @pl.when(count > 0)
    def _():
        # Fold the row's scores against the visit's pages into its
        # running statistics and accumulator, all pages in ONE fold: a
        # fold is a chain (scores, max, exp, sum, read-out) whose
        # latency, not its work, is what a page of a few hundred rows
        # pays (0.5 us a page folded alone, whatever its size). A slot
        # past the row's last page holds a page fetched earlier, whose
        # every position lies past the row's: masked like any other.
        def block(refs, c):
            # a page of fewer rows than the matrix unit's tile is wide
            # is contracted together with its neighbours: one matrix of
            # ``span`` pages, copied once inside VMEM
            return (refs[c][0] if span == 1 else jnp.concatenate(
                [r[0] for r in refs[c * span:(c + 1) * span]], axis=0))

        keys, scores = [], []
        for c in range(pages // span):
            k = block(k_refs, c)
            s = _dot(q, k, _NT) * scale                      # [R, rows]
            first = (group * pages + c * span) * page_size
            seen = first + token <= last
            scores.append(jnp.where(own & seen if kv_heads > 1 else seen,
                                    s, _NEG_INF))
            keys.append(k)
        # across the pages elementwise first: one reduction over the
        # lanes a visit, not one a page
        m_prev = m_scr[:, :1]
        m = jnp.maximum(m_prev, jnp.max(
            functools.reduce(jnp.maximum, scores), axis=-1, keepdims=True))
        ps = [jnp.exp(s - m) for s in scores]
        alpha = jnp.exp(m_prev - m)
        l = l_scr[:, :1] * alpha + jnp.sum(
            functools.reduce(jnp.add, ps), axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + sum(
            _dot(p.astype(k_refs[c].dtype),
                 block(v_refs, c) if v_refs else keys[c][:, :dv], _NN)
            for c, p in enumerate(ps))
        m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(group == jnp.maximum(pl.cdiv(count, pages), 1) - 1)
    def _():
        # key 0 is visible to a live row's every query; a row that is
        # not live scored nothing and reads out zeros
        l = l_scr[:, :1]
        y = acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = y.astype(o_ref.dtype)


def _attend(q, pk, pv, page_table, pos, *, softmax_scale: float,
            pages: Optional[int] = None, value_dim: Optional[int] = None,
            span: Optional[int] = None, block_len: int = 1,
            interpret: bool = False):
    """``paged_decode_attention`` at ``pages`` logical pages a visit,
    ``span`` of them a contraction (None: ``pages_per_visit``'s and
    ``pages_per_dot``'s plan): what tools/paged_decode_bench.py and the
    tests call with another plan to measure or check the rule's."""
    B, T, H, D = q.shape
    n_pages, Pg = pk.shape[:2]
    if pv is None:
        # latent pages [n_pages, Pg, D]: one KV head without an axis
        KH, Dv = 1, value_dim
        assert T == 1 and pk.ndim == 3 and 0 < Dv <= D, (
            q.shape, pk.shape, value_dim)
    else:
        KH, Dv = pk.shape[2], D
        assert (pv.shape == pk.shape and H % KH == 0
                and value_dim is None), (q.shape, pk.shape, pv.shape)
    # a row's T x H query rows are ONE tile of the kernel's: the
    # [T * H, D] matrix the same bytes are
    R = T * H
    pages = pages or pages_per_visit(R, Pg, KH, page_table.shape[1])
    span = span or pages_per_dot(Pg * KH, pages)
    assert pages % span == 0, (pages, span)
    ids, row_of, group_of, count, n_visits = visit_schedule(
        page_table, pos, Pg, pages, T, block_len)
    groups = -(-page_table.shape[1] // pages)

    def page(c):
        # slot c of the visit's (row, group), where the ids lie as the
        # table does
        return pl.BlockSpec(
            (1, Pg * KH, D), lambda v, ids, row_of, group_of, *_: (
                ids[(row_of[v] * groups + group_of[v]) * pages + c], 0, 0))

    def row(width):
        return pl.BlockSpec(
            (1, 1, R, width),
            lambda v, ids, row_of, *_: (row_of[v], 0, 0, 0))
    page_bytes = Pg * KH * D * pk.dtype.itemsize
    # a page-major page IS the [Pg x KH, D] matrix of its (token, KV
    # head) rows: a free view
    flat = (n_pages, Pg * KH, D)
    pools = (pk,) if pv is None else (pk, pv)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=softmax_scale,
                          pages=pages, kv_heads=KH, span=span,
                          queries=T, block_len=block_len),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_visits,),
            in_specs=([row(D)]
                      + [page(c) for c in range(pages)] * len(pools)),
            out_specs=row(Dv),
            scratch_shapes=[
                pltpu.VMEM((R, _LANES), jnp.float32),          # m
                pltpu.VMEM((R, _LANES), jnp.float32),          # l
                pltpu.VMEM((R, Dv), jnp.float32)]),            # acc
        out_shape=jax.ShapeDtypeStruct((B, 1, R, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a row's groups in order: its scratch carries them
            dimension_semantics=("arbitrary",),
            # the pages double-buffered (K's and V's, or a latent
            # pool's and their one matrix), a visit's scores (float32,
            # their exponentials, those in the pool's type), and room
            # for what the compiler spills
            vmem_limit_bytes=(4 * pages * page_bytes
                              + 12 * pages * R * Pg * KH + (8 << 20))),
        interpret=interpret, name="paged_decode",
    )(ids, row_of, group_of, count, pos.astype(jnp.int32),
      q.reshape(B, 1, R, D),
      *(x for pool in pools for x in [pool.reshape(flat)] * pages)
      ).reshape(B, T, H, Dv)


@functools.partial(jax.jit, static_argnames=("softmax_scale", "value_dim",
                                             "block_len", "interpret"))
def paged_decode_attention(q, pk, pv, page_table, pos, *,
                           softmax_scale: float,
                           value_dim: Optional[int] = None,
                           block_len: int = 1,
                           interpret: bool = False):
    """Grouped-query attention of ``q`` [B, T, H, D] (row b's queries
    at absolute positions ``pos[b] + t``) over its page-table row's
    K/V in the page-major pool ``pk``/``pv`` [n_pages, Pg, KH, D],
    causal, or block-causal where ``block_len`` > 1 (the query at i
    sees the keys below ``(i // block_len + 1) * block_len``).
    Returns [B, T, H, D] in ``q``'s type. A row whose page-table row is
    null (its first page is page 0) reads out zeros. ``pv`` None: ``pk``
    is a pool of latent pages [n_pages, Pg, D], an entry's value its
    first ``value_dim`` columns, ``T`` is 1 and [B, 1, H, value_dim]
    comes back.

    One jitted function: the layers of a step program that call it
    with equal shapes share one trace and one lowering."""
    return _attend(q, pk, pv, page_table, pos, softmax_scale=softmax_scale,
                   value_dim=value_dim, block_len=block_len,
                   interpret=interpret)
