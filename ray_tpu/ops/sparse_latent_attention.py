"""Learned sparse attention over latent pages: a lightning indexer's
scores over pages of index keys, the exact choice of the ``k`` best
entries a query, and the latent attention of those entries alone.

A layer of this kind (models/deepseek_v32.py; models/kv_cache.py
``KIND_INDEXED``) keeps TWO entries a token a layer, both in pages that
share one page id under one page table: the latent entry ``[c | k_r]``
that ops/paged_attention.py reads for every model of latent attention,
and an INDEX KEY ``k_I`` (128 wide). A query does not attend its whole
context but the ``index_topk`` entries whose index keys score highest
against its own index queries,

    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s]),   s <= t

(float32 sums of bfloat16 products, as every contraction over a pool
here), the ``min(k, t + 1)`` largest, a tie to the lower ``s``. Three
operations, each under its named scope (PERF.md section 3):

- ``index_scores`` (``dsa_index_scores``): a walk over the rows' index
  key pages in the blocks of the page window's loop, to the block of
  the longest live row's last query, that leaves one float32 score a
  (query, position) and ``-inf`` where the position is not visible;
- ``topk_mask`` (``dsa_topk``): which of a row of scores are its ``k``
  largest. No sort: the ``k``-th largest is found a digit at a time on
  the scores' order-preserving integer keys, the entries above it are
  in, and of those equal to it the lowest positions that fill the
  count. Exact for every input, ties included. It is the DEFINITION,
  and the form that runs as XLA (the CPU, and so every test; a mesh;
  the cache-less expanded forward; shapes the kernel refuses): eight
  passes of fifteen counts, each a reduction over the row in HBM. On
  one TPU the step programs' choice is ``topk_select``, ONE Pallas
  kernel a layer that a tile of rows enters once: the keys are made in
  VMEM and every pass of the same search, a bit at a time, is a
  compare and a count over the resident tile, to the chunk of columns
  the tile's longest row can see; it writes the mask as the walk's
  kernel takes it and is held to ``topk_mask`` bit for bit
  (tests/test_deepseek_v32.py, in interpret mode);
- ``sparse_attention`` (``dsa_attn``): the absorbed latent attention of
  the chosen entries alone, as ONE form for a decode step and for a
  chunk: the row's pages WALKED a block at a time as
  ``_paged_window_attention`` walks them, with the choice as the mask.
  On one TPU that is the Pallas kernel of
  ops/latent_window_attention.py (its ``member`` operand; a decode
  step's one query a row is a tile of one token), elsewhere the loop
  below. It reads every entry up to its tile's last query and attends
  the chosen ones. (A decode step that GATHERED its chosen entries by
  (page, offset) behind a sort ran at 7-8 % of the HBM's peak and 1.4
  times the walk's time a rider at contexts of 4 x ``index_topk``:
  PERF.md section 6, PR 56; ROADMAP M8 keeps it for the contexts that
  can show it winning.)

Each call says what it READ beside what was CHOSEN, so that the
engine's counters (``SELECTION_STATS``) tell a masked walk from a
gather, and for which queries the kernel chose.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import latent_window_attention as latent_window
# the backend is a TPU and no multi-device mesh is ambient: one rule
# for every Mosaic kernel that has an XLA form
from ray_tpu.ops.grouped_matmul import on_one_tpu as _on_one_tpu
from ray_tpu.ops.paged_attention import (PagedShapeError,
                                         paged_window_block_pages)

_NEG_INF = -1e30


# The collection a latent layer that chooses its entries
# (models/axk1.py ``MLAttention`` with an ``indexer``) sows what its
# call scored, chose and read into, for a caller that asks for it
# (``mutable=[SELECTION_STATS]``): each layer's ``counts`` [4, B, T].
# The serving engine's programs reduce it to four counters over their
# live tokens (``selection_stats_vector``).
SELECTION_STATS = "selection_stats"


def selection_stats_vector(stats, live):
    """What the layers that choose their entries scored, chose and
    read in one forward pass, over live tokens only and summed over the
    layers, as one int32 vector [4]: index keys scored, entries chosen
    (the sum of ``|S_t|``), entries the attention fetched, queries
    whose choice the kernel made. ``stats`` is the ``SELECTION_STATS``
    collection of an apply, ``live`` [B, T]."""
    total = jnp.zeros((len(SelectionStats.names),), jnp.int32)
    for counts in jax.tree_util.tree_leaves(stats):            # [4, B, T]
        total = total + jnp.sum(jnp.where(live[None], counts, 0),
                                axis=(1, 2), dtype=jnp.int32)
    return total


@dataclasses.dataclass(frozen=True)
class SelectionStats:
    """``selection_stats_vector``'s result as a SECTION of the vector a
    step program returns (models/mixtral.py ``MoEStats`` is the other,
    of the same members): the ``round`` event reports ``names``, and
    the decode dispatches' part alone under them after ``decode_``. A
    gather reads what was chosen, a masked walk its whole window; the
    last counts the live (query, layer) pairs ``topk_select`` chose
    for, 0 where ``topk_mask`` did."""
    collection = SELECTION_STATS
    prefix = ""
    head = 0                        # no entry is one of many alike
    names = ("index_keys_scored", "sparse_entries_chosen",
             "sparse_entries_read", "selection_kernel_rows")

    def __len__(self) -> int:
        return len(self.names)

    def reduce(self, sown, live):
        return selection_stats_vector(sown, live)

    def read(self, vec) -> dict:
        return dict(zip(self.names, (int(x) for x in vec)))


def _blocks(page_table, pos, T: int, Pg: int):
    """The page window's walk, as ops/paged_attention.py has it: the
    table padded to whole blocks, a block's pages and tokens, and how
    many blocks hold a position some live row's last query can see."""
    max_pages = page_table.shape[1]
    block_pages = paged_window_block_pages(Pg, max_pages)
    Lb = block_pages * Pg
    max_blocks = -(-max_pages // block_pages)
    table = jnp.pad(page_table,
                    ((0, 0), (0, max_blocks * block_pages - max_pages)))
    live = page_table[:, 0] != 0
    last = jnp.max(jnp.where(live, pos + (T - 1), 0))
    n_blocks = jnp.minimum(last // Lb + 1, max_blocks)
    return table, block_pages, Lb, max_blocks, n_blocks


def index_scores(q_idx, w_idx, index_pages, page_table, pos):
    """The indexer's scores of ``q_idx`` [B, T, Hi, Di] (row b's
    queries at absolute positions ``pos[b] + t``), weighted a head by
    ``w_idx`` [B, T, Hi] float32, against its page-table row's index
    keys in ``index_pages`` [n_pages, Pg, Di] (AFTER the chunk's own
    keys were appended): [B, T, S] float32 over the table's positions
    in whole blocks, ``-inf`` at every position a query cannot see (a
    later one, one past the walk, every one of a row that carries no
    request)."""
    B, T, Hi, Di = q_idx.shape
    if index_pages.ndim != 3 or index_pages.shape[-1] != Di:
        raise PagedShapeError(
            f"index keys {index_pages.shape} do not pair with index "
            f"queries {q_idx.shape}")
    Pg = index_pages.shape[1]
    table, block_pages, Lb, max_blocks, n_blocks = _blocks(
        page_table, pos, T, Pg)
    q_pos = pos[:, None] + jnp.arange(T)[None]                   # [B, T]
    live = page_table[:, 0] != 0
    w = w_idx.astype(jnp.float32)

    def block(j, scores):
        cols = jax.lax.dynamic_slice_in_dim(
            table, j * block_pages, block_pages, axis=1)
        kg = index_pages[cols].reshape(B, Lb, Di)
        s = jnp.einsum("bthd,bsd->bths", q_idx, kg.astype(q_idx.dtype),
                       preferred_element_type=jnp.float32)
        s = jnp.einsum("bths,bth->bts", jax.nn.relu(s), w)
        seen = ((j * Lb + jnp.arange(Lb))[None, None] <= q_pos[:, :, None]
                ) & live[:, None, None]
        return jax.lax.dynamic_update_slice_in_dim(
            scores, jnp.where(seen, s, -jnp.inf), j * Lb, axis=2)

    scores = jnp.full((B, T, max_blocks * Lb), -jnp.inf, jnp.float32)
    if max_blocks == 1:
        return block(0, scores)
    return jax.lax.fori_loop(0, n_blocks, block, scores)


def _order_keys(scores):
    """float32 -> uint32 whose unsigned order is the floats' order
    (``-inf`` lowest; -0.0 under +0.0, which no sum of relus makes)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


# Bits of the k-th largest key found a pass over a row's scores AS XLA
# (``topk_mask``): a pass counts the keys at or over each of the
# 2**bits - 1 candidates that set the next digit, and what it costs is
# reading the scores from HBM, so 32 / bits passes read them where a
# bit-by-bit search read them 32 times (of a [4, 256] call's 92 ms, 15
# were that search's at one bit a pass: PERF.md section 6, PR 56). The
# kernel reads them once whatever the digit and turns the argument
# over: ``_SELECT_DIGIT_BITS``.
_DIGIT_BITS = 4


def topk_mask(scores, k: int):
    """[..., S] bool: the ``k`` largest of each row of ``scores``
    [..., S] float32, or every entry above ``-inf`` where a row has
    fewer than ``k`` of them; among equal scores the lower positions.
    Exact, and no sort: the ``k``-th largest key is built a digit of
    ``_DIGIT_BITS`` bits at a time from the top (the digit is the
    number of its candidates that at least ``k`` keys reach: the counts
    fall as the candidates rise), then the keys above it are in, and of
    those equal to it the lowest positions that fill the count (a
    running count over the row, made only where some row has more
    equal keys than places left)."""
    keys = _order_keys(scores)
    want = jnp.minimum(
        jnp.sum(scores > -jnp.inf, axis=-1, dtype=jnp.int32), k)
    u32 = jnp.uint32

    def digit(i, kth):
        shift = (32 - _DIGIT_BITS * (i + 1)).astype(u32)
        reached = jnp.zeros(want.shape, u32)
        for d in range(1, 2 ** _DIGIT_BITS):
            with_digit = kth | (u32(d) << shift)
            n = jnp.sum(keys >= with_digit[..., None], axis=-1,
                        dtype=jnp.int32)
            reached = reached + (n >= want).astype(u32)
        return kth | (reached << shift)
    kth = jax.lax.fori_loop(0, 32 // _DIGIT_BITS, digit,
                            jnp.zeros(want.shape, u32))
    above = keys > kth[..., None]
    equal = keys == kth[..., None]
    left = want - jnp.sum(above, axis=-1, dtype=jnp.int32)
    spare = jnp.sum(equal, axis=-1, dtype=jnp.int32) > left

    def lowest():
        return equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                        <= left[..., None])
    # (an entry at ``-inf`` has the lowest key of all, under the k-th of
    # a row that sees anything; a row that sees nothing wants none and
    # its k-th is the largest key there is)
    return above | jax.lax.cond(jnp.any(spare), lowest, lambda: equal)


# ------------------------------------------------ the choice as a kernel

_LANES = 128
_MIN_KEY = -(1 << 31)
# The columns one step of the kernel's loops takes (four vector
# registers of keys a sublane tile of 8 rows), and what a tile of rows
# may take of VMEM: its scores and its mask, both double-buffered, and
# its keys. A tile is 64 rows where the call has them and they fit (a
# pass's own work, the count across lanes and the prefix's update, is
# paid a tile: at [1024, 8192] 0.31 ms against 0.41 in tiles of 32 and
# 0.66 of 16, tools/topk_select_bench.py, PERF.md section 6, PR 59),
# else 32 or 16.
_SELECT_CHUNK = 512
_SELECT_ROWS = (64, 32, 16)
_SELECT_VMEM = 24 << 20
# Bits of the k-th key a pass of the KERNEL finds. Nothing is read from
# HBM again, so a pass costs what it compares, 2**bits - 1 candidates
# an element, 32 / bits times: one bit is 32 compares an element, two
# are 48, four 120, and the timer read 0.41 | 0.41 | 0.91 ms for a
# call's choice in tiles of 32 and 26 | 27 | 45 us for a step's (as
# above).
_SELECT_DIGIT_BITS = 1


def _select_rows(rows: int, width: int, dtype) -> Optional[int]:
    """Rows of scores to a tile of the kernel whose mask is of
    ``dtype``: the most of ``_SELECT_ROWS`` that divide ``rows`` and
    fit ``_SELECT_VMEM``, or None."""
    row = width * (2 * 4 + 4 + 2 * jnp.dtype(dtype).itemsize)
    return next((r for r in _SELECT_ROWS
                 if rows % r == 0 and r * row <= _SELECT_VMEM), None)


def select_serves(rows: int, width: int, dtype, mask_dtype) -> bool:
    """Whether ``topk_select`` makes the choice of ``rows`` rows of
    ``width`` scores of ``dtype`` as a mask of ``mask_dtype``: float32
    scores in whole 128-lane tiles, rows in whole tiles that fit, a
    mask of numbers, and a TPU outside any multi-device mesh.
    Everything else keeps ``topk_mask``."""
    return (dtype == jnp.float32 and width % _LANES == 0
            and jnp.issubdtype(mask_dtype, jnp.floating)
            and _select_rows(rows, width, mask_dtype) is not None
            and _on_one_tpu())


def _tree_sum(parts):
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + (
            [parts[-1]] if len(parts) % 2 else [])
    return parts[0]


def _select_kernel(chunks_ref, x_ref, member_ref, chosen_ref, keys_scr, *,
                   k: int, digit_bits: int, chunk: int):
    """One tile of rows: ``topk_mask`` of ``x_ref`` [R, S] into
    ``member_ref`` [R, S] ({0, 1}) and the count chosen a row into
    ``chosen_ref`` [R, 128]. The keys are made once, into ``keys_scr``;
    every pass after that is a compare and a count over the tile's
    first ``chunks_ref[tile]`` chunks of columns, the rest being
    ``-inf`` by the caller's word."""
    n = chunks_ref[pl.program_id(0)]
    R, S = x_ref.shape
    i32 = jnp.int32
    tiles = chunk // _LANES

    def cols(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def wide(v):                              # [R, 1] -> [R, 128]
        return jnp.broadcast_to(v, (R, _LANES))

    def count(hits, m: int, of=keys_scr):
        """How many of a row's entries each of ``m`` tests holds for:
        ``hits(tile [R, 128], first column)`` -> ``m`` masks."""
        def step(c, accs):
            x = of[:, cols(c)]
            found = [hits(x[:, j * _LANES:(j + 1) * _LANES],
                          c * chunk + j * _LANES) for j in range(tiles)]
            return tuple(
                acc + _tree_sum([jnp.where(f[t], 1, 0) for f in found])
                for t, acc in enumerate(accs))
        accs = jax.lax.fori_loop(
            0, n, step, (jnp.zeros((R, _LANES), i32),) * m)
        return [jnp.sum(acc, axis=-1, keepdims=True) for acc in accs]

    # the scores' order as SIGNED integers (``_order_keys`` with the top
    # bit flipped), and how many entries a row sees
    def make(c, _):
        bits = jax.lax.bitcast_convert_type(x_ref[:, cols(c)], i32)
        keys_scr[:, cols(c)] = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
        return 0
    jax.lax.fori_loop(0, n, make, 0)
    (seen,) = count(lambda x, _: (x > -jnp.inf,), 1, of=x_ref)
    want = jnp.minimum(seen, k)

    # the k-th largest key, ``digit_bits`` bits a pass from the top, as
    # ``topk_mask`` builds it (``kth`` holds the unsigned key's bits)
    def digit(p, kth):
        shift = 32 - digit_bits * (p + 1)
        tries = [wide((kth | jnp.left_shift(i32(d), shift)) ^ _MIN_KEY)
                 for d in range(1, 2 ** digit_bits)]
        ns = count(lambda x, _: tuple(x >= t for t in tries), len(tries))
        reached = _tree_sum([jnp.where(n_ >= want, 1, 0) for n_ in ns])
        return kth | jnp.left_shift(reached, shift)
    kth = jax.lax.fori_loop(0, 32 // digit_bits, digit,
                            jnp.zeros((R, 1), i32)) ^ _MIN_KEY
    kth_w = wide(kth)
    at_least, above = count(lambda x, _: (x >= kth_w, x > kth_w), 2)
    left = want - above

    # of the keys equal to the k-th, the lowest positions that fill the
    # count: every one under ``cut``, found a bit a pass as the largest
    # position with at most ``left`` equal keys under it; past the row
    # where no row of the tile has more equal keys than places left
    lane = jax.lax.broadcasted_iota(i32, (R, _LANES), 1)
    everything = jnp.full((R, 1), 2 ** S.bit_length() - 1, i32)

    def lowest():
        def bit(p, cut):
            further = cut | jnp.left_shift(i32(1), S.bit_length() - 1 - p)
            bound = wide(further)
            (under,) = count(lambda x, first: (
                (x == kth_w) & (lane + first < bound),), 1)
            return jnp.where(under <= left, further, cut)
        return jax.lax.fori_loop(0, S.bit_length(), bit,
                                 jnp.zeros((R, 1), i32))
    spare = jnp.max(jnp.where(at_least - above > left, 1, 0))
    cut = jax.lax.cond(spare > 0, lowest, lambda: everything)

    def emit(c, _):
        x = keys_scr[:, cols(c)]
        place = c * chunk + jax.lax.broadcasted_iota(i32, x.shape, 1)
        mine = (x > kth) | ((x == kth) & (place < cut))
        member_ref[:, cols(c)] = jnp.where(mine, 1.0, 0.0).astype(
            member_ref.dtype)
        return 0
    jax.lax.fori_loop(0, n, emit, 0)

    def blank(c, _):
        member_ref[:, cols(c)] = jnp.zeros((R, chunk), member_ref.dtype)
        return 0
    jax.lax.fori_loop(n, S // chunk, blank, 0)
    chosen_ref[...] = wide(want)


def topk_select(scores, k: int, ends=None, *, dtype=jnp.bfloat16,
                interpret: bool = False):
    """``topk_mask`` of ``scores`` [rows, S] float32 as one Pallas
    kernel: ([rows, S] ``dtype``, 1 where chosen and 0 elsewhere;
    [rows] int32, how many a row chose). A tile of rows enters VMEM
    once and the whole search for its rows' k-th keys runs there.
    ``ends`` [rows] int32, where given: a row's scores at and past
    ``ends[row]`` are all ``-inf`` (the positions its query cannot
    see), so a tile's passes stop at the chunk its longest row ends
    in."""
    rows, S = scores.shape
    R = _select_rows(rows, S, dtype)
    chunk = next(c for c in (_SELECT_CHUNK, 256, _LANES) if S % c == 0)
    assert R and S % _LANES == 0, scores.shape
    if ends is None:
        chunks = jnp.full((rows // R,), S // chunk, jnp.int32)
    else:
        chunks = jnp.clip(-(-jnp.max(ends.reshape(rows // R, R), axis=1)
                            // chunk), 0, S // chunk).astype(jnp.int32)

    def tile(width):
        return pl.BlockSpec((R, width), lambda i, chunks: (i, 0))
    member, chosen = pl.pallas_call(
        functools.partial(_select_kernel, k=k, chunk=chunk,
                          digit_bits=_SELECT_DIGIT_BITS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // R,),
            in_specs=[tile(S)],
            out_specs=[tile(S), tile(_LANES)],
            scratch_shapes=[pltpu.VMEM((R, S), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, S), dtype),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a tile's blocks, and room for what the compiler spills
            vmem_limit_bytes=_SELECT_VMEM + (8 << 20)),
        interpret=interpret, name="topk_select",
    )(chunks, scores)
    return member, chosen[:, 0]


def _walked(q, pages, page_table, pos, member, scale: float, Dv: int):
    """The walk as plain XLA (no TPU, a mesh, shapes the kernel does
    not take): the page window's loop over the row's latent pages with
    ``member`` [B, T, S] for its mask, so every entry up to the longest
    live row's last query is read and the chosen ones are attended."""
    B, T, H, D = q.shape
    Pg = pages.shape[1]
    table, block_pages, Lb, max_blocks, n_blocks = _blocks(
        page_table, pos, T, Pg)

    def block(j, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(
            table, j * block_pages, block_pages, axis=1)
        kg = pages[cols].reshape(B, Lb, D)
        s = jnp.einsum("bthd,bsd->bhts", q, kg,
                       preferred_element_type=jnp.float32) * scale
        mask = jax.lax.dynamic_slice_in_dim(member, j * Lb, Lb, axis=2)
        s = jnp.where(mask[:, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a block that holds none of a query's entries adds nothing
        p = jnp.where(mask[:, None], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhts,bsv->bhtv", p.astype(kg.dtype), kg[..., :Dv],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    stat = (B, H, T)
    carry = (jnp.full(stat, _NEG_INF, jnp.float32),
             jnp.zeros(stat, jnp.float32),
             jnp.zeros(stat + (Dv,), jnp.float32))
    if max_blocks == 1:
        carry = block(0, carry)
    else:
        carry = jax.lax.fori_loop(0, n_blocks, block, carry)
    _, l, acc = carry
    y = (acc / jnp.where(l > 0.0, l, 1.0)[..., None]).astype(q.dtype)
    read = jnp.broadcast_to(n_blocks * Lb, (B, T)).astype(jnp.int32)
    return y.transpose(0, 2, 1, 3), read


def _chosen_of_the_walk(scores, page_table, pos, page_size: int, k: int,
                        dtype=jnp.bool_):
    """``topk_mask`` of a chunk's ``scores`` [B, T, S] (as ``dtype``
    where the kernel makes it: 1 where chosen), each query's count of
    chosen entries [B, T], and whether the kernel chose
    (``select_serves``), at the price of the WALK's width and not the
    table's: past the last block ``index_scores`` walked every score is
    ``-inf``, and nothing beyond it is chosen. The kernel is told where
    each query's sight ends, so a tile of rows passes over the chunks
    its own longest row sees; as XLA the choice is made over the
    narrowest of four widths (quarters of the table, in whole blocks)
    that holds the walk. (The passes over a [4, 256, 16384] row were 14
    of a call's 85 ms where the contexts end at 8.7k: PERF.md section
    6, PR 56.)"""
    B, T, S = scores.shape
    if select_serves(B * T, S, scores.dtype, dtype):
        ends = jnp.where(page_table[:, :1] != 0,
                         pos[:, None] + jnp.arange(1, T + 1)[None], 0)
        member, chosen = topk_select(scores.reshape(B * T, S), k,
                                     ends.reshape(-1), dtype=dtype)
        return member.reshape(B, T, S), chosen.reshape(B, T), True
    _, _, Lb, max_blocks, n_blocks = _blocks(page_table, pos, T, page_size)
    widths = sorted({-(-max_blocks * q // 4) * Lb for q in (1, 2, 3, 4)})

    def over(width):
        def choose():
            member = topk_mask(scores[..., :width], k)
            return (jnp.pad(member, ((0, 0), (0, 0), (0, S - width))),
                    jnp.sum(member, axis=-1, dtype=jnp.int32))
        return choose
    if len(widths) == 1:
        return *over(S)(), False
    walked = n_blocks * Lb
    narrowest = sum((walked > w).astype(jnp.int32) for w in widths[:-1])
    return *jax.lax.switch(narrowest, [over(w) for w in widths]), False


def _tile_tokens(T: int, H: int):
    """Whole tokens to a query tile of the kernel under a choice: a
    chunk's own (``latent_window.tile_tokens``), and for a call of
    fewer rows than one tile (a decode step, a speculative verify) the
    call's ``T``: a row is one tile. (Without a choice such a call is
    ops/paged_decode_attention.py's, whose mask is a position and not
    a query's own.) None where the heads fill no whole sublane tile."""
    return latent_window.tile_tokens(T, H) or (
        T if H % 16 == 0 and T * H <= latent_window._TILE_ROWS else None)


def sparse_attention(q, pages, page_table, pos, scores, k: int, *,
                     softmax_scale: float, value_dim: int):
    """The absorbed latent attention of ``q`` [B, T, H, D] over the
    ``k`` entries of its page-table row's latent ``pages`` [n_pages,
    Pg, D] that ``scores`` [B, T, S] (``index_scores``) ranks highest;
    a key's value is its first ``value_dim`` columns. Returns ([B, T,
    H, value_dim], [B, T] entries chosen, [B, T] entries read, [B, T]
    1 where the kernel made the choice and 0 where XLA did)."""
    B, T, H, D = q.shape
    if pages.ndim != 3 or pages.shape[-1] != D:
        raise PagedShapeError(
            f"latent pages {pages.shape} do not pair with queries "
            f"{q.shape}")
    with jax.named_scope("dsa_topk"):
        # (as the walk's kernel takes its ``member``: in the queries'
        # type)
        member, chosen, by_kernel = _chosen_of_the_walk(
            scores, page_table, pos, pages.shape[1], k, q.dtype)
    with jax.named_scope("dsa_attn"):
        tokens = _tile_tokens(T, H)
        if tokens and latent_window.applies(q, pages, value_dim, tokens):
            block_pages = paged_window_block_pages(pages.shape[1],
                                                   page_table.shape[1])
            y = latent_window.latent_window_attention(
                q, pages, page_table, pos, value_dim=value_dim,
                softmax_scale=softmax_scale, block_pages=block_pages,
                tokens=tokens, member=member)
            read = latent_window.entries_read(
                page_table, pos, T, H, block_pages, pages.shape[1],
                tokens)
        else:
            y, read = _walked(q, pages, page_table, pos,
                              member.astype(bool), softmax_scale,
                              value_dim)
    return y, chosen, read, jnp.full((B, T), int(by_kernel), jnp.int32)
