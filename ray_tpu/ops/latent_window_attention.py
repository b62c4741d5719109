"""A prefill chunk's attention over LATENT pages as one Pallas kernel.

ops/paged_attention.py ``_paged_window_attention`` walks a row's pages a
block at a time. As plain XLA every iteration of that walk writes its
float32 scores to HBM, reduces them there and carries a float32
accumulator through the loop: for a [4, 256] chunk of 64 heads that is
134 MB of scores and 134 MB of accumulator a 512-key block, and the two
contractions ran at 33-37 % of the matrix unit behind that traffic
(PERF.md section 6, PR 43). A latent pool is the one shape where a
kernel pays: every (token, head) of a row reads the SAME key, so a
fetched block of 512 keys x 640 columns serves thousands of query rows,
and a score costs ~2,300 FLOPs of matrix work against one ``exp`` and
two reductions of vector work.

The kernel is the same mathematics (bfloat16 operands as stored,
float32 accumulation in both contractions, float32 scores x the scale,
the causal mask on absolute positions, the online softmax in float32,
``p`` cast to the pool's type before the read-out, the value the first
``value_dim`` columns of the one fetched key tile) with nothing
block-sized in HBM:

- a query TILE is ``tile_tokens`` whole tokens x every head, read from
  ``q`` [B, T, H, D] and written to [B, T, H, value_dim] as the model
  has them (whole tokens x a multiple of 16 heads merge into rows
  without a relayout), so neither is transposed in HBM; its running
  max / sum and its accumulator rest in VMEM scratch across its key
  blocks and are written out once;
- the grid is the call's VISITS, one (tile, key block) each, a tile's
  blocks in order, and its length is a value: work follows each ROW's
  own window, a tile at a time, up to the block holding the tile's last
  query and no further; a row whose page-table row is null gets one
  visit a tile, which scores nothing and writes zeros. (A grid of fixed
  length with the visits left over skipped cost 0.35 us a skipped visit:
  1.0 ms a layer-call at a table of 32 blocks, more than a one-block
  window's whole work.)
- the page table and the visits' schedule go in by scalar prefetch and
  the index maps fetch a block's pages BY THEIR ID, as they lie (a page
  is one contiguous slab, an operand block each); the pool is neither
  re-laid out nor copied;
- only the block that holds a tile's own positions builds a mask.

Which form serves a call is ``serves``'s rule, read by
``_paged_window_attention``: shapes, types, the backend and the ambient
mesh, never a flag.
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

# (token, head) rows of one query tile: what a fetched block of keys is
# scored against, in one go, before the next is fetched.
_TILE_ROWS = 2048


def tile_tokens(T: int, H: int) -> Optional[int]:
    """Whole tokens to a query tile for a chunk of ``T`` tokens of ``H``
    heads, or None where the chunk holds less than one tile (a decode
    step, a speculative verify of a few tokens: one query row a head
    and key is a gather of bytes, and the XLA loop is right for it,
    PR 30) or does not divide into whole tiles.

    Measured on v5e (PR 43; one layer's [4, 256] call, 64 heads | 32,
    every row's window ending at 8,192, ms; PERF.md section 6 has the
    table): tiles of 2,048 rows **7.33 | 3.66**, of 1,024 7.87 | 4.03,
    of 512 8.93 | 4.55, of 4,096 11.43 | 5.79 (the loop 16.90 | 5.78).
    A larger tile fetches and turns a block of keys less often; at
    4,096 rows the scores of one visit no longer fit beside the tile.
    Scored a quarter of a tile at a time 8.95 against 8.05 whole (tiles
    of 1,024), and the diagonal block in slabs of 256 | 128 keys, the
    slabs above a tile's last query skipped, 8.95 | 9.29 against 8.73
    whole and masked: what a tile pays a fold (the statistics, the
    accumulator's rescale) outweighs the scores skipped."""
    if H % 16 or _TILE_ROWS % H:
        return None
    tokens = _TILE_ROWS // H
    return tokens if T >= tokens and T % tokens == 0 else None


def serves(T: int, H: int, D: int, value_dim: int, page_size: int,
           dtype, tokens: Optional[int] = None) -> bool:
    """Whether the kernel serves a chunk of ``T`` tokens of ``H`` heads
    over latent pages of ``page_size`` entries ``D`` wide, queries and
    pool both of ``dtype``: whole query tiles (``tile_tokens``), widths
    of whole 128-lane tiles, pages of whole bfloat16 sublane tiles, and
    a TPU outside any multi-device mesh. Everything else keeps the XLA
    loop. The engine asks the same question for its
    ``prefill_kernel_blocks``. ``tokens``: the caller's own tile where
    not ``tile_tokens``'s (ops/sparse_latent_attention.py: a decode
    step under a choice is a tile of one token a row)."""
    return ((tokens or tile_tokens(T, H)) is not None
            and dtype == jnp.bfloat16
            and D % _LANES == 0 and value_dim % _LANES == 0
            and page_size % 16 == 0 and _on_one_tpu())


def applies(q, pages, value_dim: int,
            tokens: Optional[int] = None) -> bool:
    """``serves`` for ``q`` [B, T, H, D] over ``pages`` [n_pages, Pg,
    D]."""
    T, H, D = q.shape[1:]
    return q.dtype == pages.dtype and serves(
        T, H, D, value_dim, pages.shape[1], q.dtype, tokens)


def kernel_blocks(starts, T: int, block: int, max_blocks: int) -> int:
    """Key blocks ONE latent layer's kernel visits for live rows whose
    chunks of ``T`` queries start at ``starts`` (host integers): each
    row to the block holding ITS last query's position, inside the
    table. The engine's ``prefill_kernel_blocks``; a row's block is
    ``T x H`` query rows against ``block`` keys."""
    return sum(min((int(s) + T - 1) // block + 1, max_blocks)
               for s in starts)


def _vmem_bytes(rows: int, D: int, Dv: int, block: int) -> int:
    """The query and output tiles and the key pages, each
    double-buffered, the block of keys in one piece and turned, the
    float32 accumulator and statistics, a visit's scores (float32, their
    exponentials, those in the pool's type) and read-out, and room for
    what the compiler spills."""
    return (2 * rows * (D + Dv) * 2 + 4 * block * D * 2
            + rows * (Dv + 2 * _LANES) * 4
            + rows * (block * 10 + Dv * 8) + (8 << 20))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _window_kernel(table_ref, tile_ref, block_ref, count_ref, pos_ref,
                   q_ref, *rest, scale: float, dv: int, n_q: int,
                   chosen: bool = False):
    del table_ref                               # the index maps' alone
    *k_refs, o_ref, m_scr, l_scr, acc_scr = rest
    if chosen:
        # the tile's tokens' chosen keys of this block, {0, 1}
        *k_refs, member_ref = k_refs
    v = pl.program_id(0)
    tile, j = tile_ref[v], block_ref[v]  # this visit's tile, key block
    tokens, H, D = q_ref.shape[1:]
    rows = tokens * H
    lb = len(k_refs) * k_refs[0].shape[1]
    count = count_ref[tile]              # blocks this tile visits
    # the tile's first query's position
    first = pos_ref[tile // n_q] + (tile % n_q) * tokens

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def visit(masked: bool):
        """Fold the tile's scores against block ``j`` into its running
        statistics and accumulator."""
        keys = jnp.concatenate([r[0] for r in k_refs], axis=0)
        s = _dot(q_ref[0].reshape(rows, D), keys, _NT) * scale
        if chosen:
            # a token's choice holds for all its heads. A block with
            # none of a query's keys folds junk into its statistics at
            # the floor, which the first block that holds one wipes
            # (``alpha`` underflows to 0), and every live query has one
            mine = jnp.broadcast_to(
                member_ref[0].astype(jnp.float32)[:, None, :],
                (tokens, H, lb)).reshape(rows, lb)
            s = jnp.where(mine > 0.0, s, _NEG_INF)
        elif masked:
            q_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (tokens, H, lb), 0).reshape(rows, lb)
            k_pos = j * lb + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m)
        alpha = jnp.exp(m_prev - m)
        l = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot(
            p.astype(keys.dtype), keys[:, :dv], _NN)
        m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    if chosen:
        pl.when(j < count)(functools.partial(visit, False))
    else:
        # no mask where every key of the block is at or under the
        # tile's first query
        below = (j + 1) * lb - 1 <= first
        pl.when((j < count) & below)(functools.partial(visit, False))
        pl.when((j < count) & ~below)(functools.partial(visit, True))

    @pl.when(j == jnp.maximum(count, 1) - 1)
    def _():
        # key 0 is visible to every query of a live row; a row that is
        # not live scored nothing and reads out zeros
        l = l_scr[:, :1]
        y = acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = y.astype(o_ref.dtype).reshape(o_ref.shape[1:])


def latent_window_attention(q, pages, page_table, pos, *,
                            softmax_scale: float, value_dim: int,
                            block_pages: int,
                            tokens: Optional[int] = None,
                            interpret: bool = False, member=None):
    """Causal attention of ``q`` [B, T, H, D] (row b's queries at
    absolute positions ``pos[b] + t``) over its page-table row's
    entries in the latent pool ``pages`` [n_pages, Pg, D], in blocks of
    ``block_pages`` pages; a key's value is its first ``value_dim``
    columns. Returns [B, T, H, value_dim] in ``q``'s type. A row whose
    page-table row is null (its first page is page 0) reads out zeros.
    ``tokens``: a query tile's, where not ``tile_tokens``'s.
    ``member`` [B, T, S] bool (S the table's positions in whole blocks):
    the keys each query attends, where that is not every key at or
    before it (ops/sparse_latent_attention.py: a learned choice, causal
    by construction); the walk is the same, the mask is the caller's."""
    B, T, H, D = q.shape
    Pg = pages.shape[1]
    max_pages = page_table.shape[1]
    lb = block_pages * Pg
    max_blocks = -(-max_pages // block_pages)
    tokens = tokens or tile_tokens(T, H)
    assert tokens and T % tokens == 0, (tokens, T, H)
    n_q = T // tokens
    i32 = jnp.int32
    # a whole number of blocks: columns past the table are null pages,
    # which the mask never lets a live query see
    table = jnp.pad(
        page_table.astype(i32),
        ((0, 0), (0, max_blocks * block_pages - max_pages))).reshape(-1)
    live = page_table[:, 0] != 0
    ends = pos.astype(i32)[:, None] + (
        jnp.arange(1, n_q + 1, dtype=i32) * tokens - 1)[None]
    count = jnp.where(live[:, None],
                      jnp.minimum(ends // lb + 1, max_blocks),
                      0).reshape(-1)
    # visit v is block ``block_of[v]`` of tile ``tile_of[v]``: a tile has
    # as many visits as blocks, and one where it has none
    visits = jnp.maximum(count, 1)
    after = jnp.cumsum(visits, dtype=i32)
    visit = jnp.arange(B * n_q * max_blocks + 1, dtype=i32)
    tile_of = jnp.minimum(
        jnp.searchsorted(after, visit, side="right").astype(i32),
        B * n_q - 1)
    block_of = visit - (after - visits)[tile_of]

    def page(c):
        def index(v, table, tile_of, block_of, count, pos):
            at = (tile_of[v] // n_q) * max_blocks + block_of[v]
            return table[at * block_pages + c], 0, 0
        return pl.BlockSpec((1, Pg, D), index)

    def tile(width):
        return pl.BlockSpec(
            (1, tokens, H, width),
            lambda v, table, tile_of, *_: (tile_of[v] // n_q,
                                           tile_of[v] % n_q, 0, 0))
    rows = tokens * H
    sparse = ()
    if member is not None:
        sparse = (member.astype(q.dtype),)
        chosen_keys = pl.BlockSpec(
            (1, tokens, lb),
            lambda v, table, tile_of, block_of, *_: (
                tile_of[v] // n_q, tile_of[v] % n_q, block_of[v]))
    return pl.pallas_call(
        functools.partial(_window_kernel, scale=softmax_scale,
                          dv=value_dim, n_q=n_q, chosen=bool(sparse)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(after[-1],),
            in_specs=[tile(D)] + [page(c) for c in range(block_pages)] + (
                [chosen_keys] if sparse else []),
            out_specs=tile(value_dim),
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),       # m
                pltpu.VMEM((rows, _LANES), jnp.float32),       # l
                pltpu.VMEM((rows, value_dim), jnp.float32)]),  # acc
        out_shape=jax.ShapeDtypeStruct((B, T, H, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a tile's key blocks in order: its scratch carries them
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(rows, D, value_dim, lb)),
        interpret=interpret, name="latent_window",
    )(table, tile_of, block_of, count, pos.astype(i32), q,
      *([pages] * block_pages), *sparse)


def entries_read(page_table, pos, T: int, H: int, block_pages: int,
                 page_size: int, tokens: Optional[int] = None):
    """[B, T] int32: the entries the kernel fetches for each query of a
    [B, T] chunk of ``H`` heads: its tile's blocks of ``block_pages``
    pages, to the one holding the tile's last query, inside the table;
    none for a row whose page-table row is null."""
    tokens = tokens or tile_tokens(T, H)
    block = block_pages * page_size
    max_blocks = -(-page_table.shape[1] // block_pages)
    ends = pos.astype(jnp.int32)[:, None] + (
        jnp.arange(T, dtype=jnp.int32) // tokens + 1)[None] * tokens - 1
    blocks = jnp.minimum(ends // block + 1, max_blocks)
    return jnp.where(page_table[:, :1] != 0, blocks, 0) * block
