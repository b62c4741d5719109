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
  the scores' order-preserving integer keys (eight passes over the
  row), the entries above it are in, and of those equal to it the
  lowest positions that fill the count. Exact for every input, ties
  included;
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
gather.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops import latent_window_attention as latent_window
from ray_tpu.ops.paged_attention import (PagedShapeError,
                                         paged_window_block_pages)

_NEG_INF = -1e30


# The collection a latent layer that chooses its entries
# (models/axk1.py ``MLAttention`` with an ``indexer``) sows what its
# call scored, chose and read into, for a caller that asks for it
# (``mutable=[SELECTION_STATS]``): each layer's ``counts`` [3, B, T].
# The serving engine's programs reduce it to three counters over their
# live tokens (``selection_stats_vector``).
SELECTION_STATS = "selection_stats"


def selection_stats_vector(stats, live):
    """What the layers that choose their entries scored, chose and
    read in one forward pass, over live tokens only and summed over the
    layers, as one int32 vector [3]: index keys scored, entries chosen
    (the sum of ``|S_t|``), entries the attention fetched. ``stats`` is
    the ``SELECTION_STATS`` collection of an apply, ``live`` [B, T]."""
    total = jnp.zeros((len(SelectionStats.names),), jnp.int32)
    for counts in jax.tree_util.tree_leaves(stats):            # [3, B, T]
        total = total + jnp.sum(jnp.where(live[None], counts, 0),
                                axis=(1, 2), dtype=jnp.int32)
    return total


@dataclasses.dataclass(frozen=True)
class SelectionStats:
    """``selection_stats_vector``'s result as a SECTION of the vector a
    step program returns (models/mixtral.py ``MoEStats`` is the other,
    of the same members): the ``round`` event reports ``names``, and
    the decode dispatches' part alone under them after ``decode_``. A
    gather reads what was chosen, a masked walk its whole window."""
    collection = SELECTION_STATS
    prefix = ""
    head = 0                        # no entry is one of many alike
    names = ("index_keys_scored", "sparse_entries_chosen",
             "sparse_entries_read")

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


# Bits of the k-th largest key found a pass over a row's scores: a pass
# counts the keys at or over each of the 2**bits - 1 candidates that
# set the next digit, so 32 / bits passes read the scores where a
# bit-by-bit search read them 32 times (of a [4, 256] call's 92 ms, 15
# were that search's at one bit a pass: PERF.md section 6, PR 56).
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


def _chosen_of_the_walk(scores, page_table, pos, page_size: int, k: int):
    """``topk_mask`` of a chunk's ``scores`` [B, T, S] and each query's
    count of chosen entries [B, T], at the price of the WALK's width
    and not the table's: past the last block ``index_scores`` walked
    every score is ``-inf``, so the choice is made over the narrowest
    of four widths (quarters of the table, in whole blocks) that holds
    the walk, and nothing beyond it is chosen. (The passes over a
    [4, 256, 16384] row were 14 of a call's 85 ms where the contexts
    end at 8.7k: PERF.md section 6, PR 56.)"""
    B, T, S = scores.shape
    _, _, Lb, max_blocks, n_blocks = _blocks(page_table, pos, T, page_size)
    widths = sorted({-(-max_blocks * q // 4) * Lb for q in (1, 2, 3, 4)})

    def over(width):
        def choose():
            member = topk_mask(scores[..., :width], k)
            return (jnp.pad(member, ((0, 0), (0, 0), (0, S - width))),
                    jnp.sum(member, axis=-1, dtype=jnp.int32))
        return choose
    if len(widths) == 1:
        return over(S)()
    walked = n_blocks * Lb
    narrowest = sum((walked > w).astype(jnp.int32) for w in widths[:-1])
    return jax.lax.switch(narrowest, [over(w) for w in widths])


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
    H, value_dim], [B, T] entries chosen, [B, T] entries read)."""
    B, T, H, D = q.shape
    if pages.ndim != 3 or pages.shape[-1] != D:
        raise PagedShapeError(
            f"latent pages {pages.shape} do not pair with queries "
            f"{q.shape}")
    with jax.named_scope("dsa_topk"):
        member, chosen = _chosen_of_the_walk(scores, page_table, pos,
                                             pages.shape[1], k)
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
            y, read = _walked(q, pages, page_table, pos, member,
                              softmax_scale, value_dim)
    return y, chosen, read
