"""The paged KV pool's two step-time operations, and the one layout
they read and write.

The continuous-batching engine keeps every layer's K and V in a pool
of fixed-size pages (models/kv_cache.py makes it), PAGE-MAJOR:

  pages_k/pages_v:   [n_pages, page_size, n_kv_heads, head_dim]
  scales_k/scales_v: [n_pages, n_kv_heads] fp32 (int8 pools only)
  page_table:        [n_slots, max_pages] int32 (0 = the null page)

A layer of LATENT attention (models/axk1.py) keeps ONE such pool and no
V pool: ``pages_k`` [n_pages, page_size, width] (no head axis; the
width a whole number of 128-lane tiles, models/kv_cache.py
``latent_page_width``), ``pages_v`` None.
Its entries are one "KV head" that every query head reads, its values the
first ``value_dim`` columns of the same gathered entries, and its score
scale is handed in: the same two operations below, told so by their
arguments.

A page is one contiguous slab holding every KV head of its tokens, so
a step program scatters and gathers whole pages by their id and never
re-lays the pool out (PERF.md section 6, PRs 26 and 29). Every step
program (decode, chunked prefill, speculative verify; fp and int8;
one chip or tensor-parallel over the KV-head axis) does exactly two
things with the pool, both here, and nothing else at step time knows
the layout:

- ``paged_append`` scatters a chunk of new K/V at each slot's write
  offset;
- ``_paged_window_attention`` attends a chunk of queries over each
  slot's pages. It has THREE FORMS and a rule between them that reads
  only what the function can see (its arguments' shapes and types, the
  backend, the ambient mesh; never a flag). The LOOP, plain XLA: blocks
  of pages gathered by page id up to the longest live context, the
  online softmax's running statistics and accumulator carried through
  a loop with a runtime trip count. It serves every prefill chunk and
  speculative verify over a K/V pool, a verify over a latent one, every
  int8 and float32 pool, the CPU and any mesh. The LATENT KERNEL
  (ops/latent_window_attention.py): a PREFILL CHUNK over a LATENT pool
  on one TPU, where thousands of (token, head) query rows read one key
  and the loop's float32 scores and accumulator, 134 MB each a block in
  HBM, held the two contractions at a third of the matrix unit; one
  Pallas call keeps them in VMEM and walks each row to its own last
  block (PERF.md section 6, PR 43). The DECODE KERNEL
  (ops/paged_decode_attention.py): a DECODE STEP (one query a row, or
  the few of a block of a model that decodes by blocks under its
  block-causal mask: as many as fit a visit's scores against one
  page) over
  a bfloat16 K/V pool on one TPU, where the bytes fetched are all the
  step costs and the loop gathers a whole 512-token block for every
  row up to the longest rider's context (67 MB a layer where 40 are
  live at 16 riders of 256-352 tokens: 38 % of Ouro's decode step,
  PERF.md section 6, PRs 46 and 47); one Pallas call reads each
  rider's own pages once, where they lie, and no page of a row without
  a rider. A decode step over a bfloat16 LATENT pool is the same
  kernel's ``KH = 1`` case (PR 48): every query head reads the one
  entry a token, whose value is a slice of the same fetched page, where
  the loop wrote a ``[rows, 512, 640]`` block by page id and read it
  back twice (2.4 of A.X-K1's 4.2 ms of attention a step). A Pallas
  decode kernel lost to the loop once (PR 30: 32.8
  against 10.6-11.1 ms a step) for two reasons, and this one answers
  both: that grid ran 32 slots x 64 table columns whatever the context
  (this one's length is a value, each row walked to its own last page),
  and it wanted a head-major pool, 32 transposed copies a step (this
  one reads a page as the [page_size x KH, D] matrix it already is).
  The three share the mathematics and no code.

Inactive slots point at the null page: their writes land there, the
causal mask hides it from every live query, and their outputs are
ignored host-side.

A layer of SLIDING-WINDOW attention (models/mellum.py) keeps no pages
but a ring a decode slot (models/kv_cache.py ``SlidingRing``:
[n_slots, n_kv_heads, L, head_dim] for k and for v, position p at
index p mod L). ``ring_append`` and ``ring_attention`` here are the
``jax.numpy`` FORM of its one call, ops/ring_window_attention.py
``ring_window_attention``: what runs off the chip and what the tests
hold that module's Pallas kernel to, which on one TPU writes and reads
a ring where it lies (PR 52). The ring is HEAD-major inside a slot: a
KV head is a batch dimension of both contractions and its ring one
[L, head_dim] slab; position-major, the chip's compiler re-laid every
ring out on every decode step (PR 42; PRs 29 and 34's lesson again).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import latent_window_attention as latent_window
from ray_tpu.ops import paged_decode_attention as paged_decode

# Int8 pages use a symmetric absmax code: value = q * scale / 127 with
# q in [-127, 127] (-128 unused so the code is symmetric). One fp32
# scale per (kv_head, physical page) — coarse enough to cost 4 bytes
# per page per head, fine enough that one outlier page cannot poison
# the whole pool's precision.
_QMAX = 127.0


class PagedShapeError(ValueError):
    """Typed shape/dtype mismatch between a KV chunk and the page pool.

    Raised at trace time by ``paged_append`` — shapes are static under
    jit, so every check below fires before lowering, replacing the
    opaque XLA scatter errors (dimension-numbers mismatches deep in
    HLO) these bugs used to surface as. The message names the operand
    and both shapes so a head-count or head-dim mismatch (the classic
    tensor-parallel wiring bug: sharded pool, unsharded chunk) reads
    as what it is.
    """


def _check_append_shapes(pages_k, pages_v, page_table, pos, k, v):
    if (pages_v is None) != (v is None):
        raise PagedShapeError(
            "a pool without V pages (latent pages) takes a chunk "
            "without v, and a K/V pool a chunk with both; got pages_v "
            f"{'None' if pages_v is None else pages_v.shape}, v "
            f"{'None' if v is None else v.shape}")
    if pages_v is None:
        if pages_k.ndim != 3:
            raise PagedShapeError(
                f"latent pages must be rank-3 [n_pages, Pg, D]; got "
                f"{pages_k.shape}")
        # one KV head, as the chunk [B, T, 1, D] has it
        pages_k = pages_k[:, :, None]
        pages_v, v = pages_k, k
    if pages_k.ndim != 4 or pages_v.ndim != 4:
        raise PagedShapeError(
            f"pages_k/pages_v must be rank-4 [n_pages, Pg, KH, D]; "
            f"got pages_k {pages_k.shape}, pages_v {pages_v.shape}")
    if pages_k.shape != pages_v.shape:
        raise PagedShapeError(
            f"pages_k and pages_v disagree: {pages_k.shape} vs "
            f"{pages_v.shape}")
    if k.ndim != 4 or v.ndim != 4:
        raise PagedShapeError(
            f"k/v chunks must be rank-4 [B, T, KH, D]; got k "
            f"{k.shape}, v {v.shape}")
    if k.shape != v.shape:
        raise PagedShapeError(
            f"k and v chunks disagree: {k.shape} vs {v.shape}")
    _, _, KH, D = pages_k.shape
    if k.shape[2] != KH:
        raise PagedShapeError(
            f"chunk has {k.shape[2]} kv heads but the page pool holds "
            f"{KH} (pool {pages_k.shape}, chunk {k.shape}) — under "
            f"tensor parallelism both must be the per-device count")
    if k.shape[3] != D:
        raise PagedShapeError(
            f"chunk head_dim {k.shape[3]} != pool head_dim {D} "
            f"(pool {pages_k.shape}, chunk {k.shape})")
    if page_table.ndim != 2:
        raise PagedShapeError(
            f"page_table must be rank-2 [B, max_pages]; got "
            f"{page_table.shape}")
    if page_table.shape[0] != k.shape[0]:
        raise PagedShapeError(
            f"page_table has {page_table.shape[0]} rows but the chunk "
            f"has batch {k.shape[0]}")
    if not jnp.issubdtype(page_table.dtype, jnp.integer):
        raise PagedShapeError(
            f"page_table must be integer, got {page_table.dtype}")
    if pos.shape != (k.shape[0],):
        raise PagedShapeError(
            f"pos must be [B]={k.shape[0]}; got shape {pos.shape}")


def _check_scale_shapes(pages_k, scales_k, scales_v, want):
    for name, s in (("scales_k", scales_k), ("scales_v", scales_v)):
        if s.shape != want:
            raise PagedShapeError(
                f"{name} must be {want} to pair with pool "
                f"{pages_k.shape}; got {s.shape}")
    if pages_k.dtype != jnp.int8:
        raise PagedShapeError(
            f"per-page scales supplied but the pool is {pages_k.dtype}"
            f", not int8 — scales only pair with quantized pools")


def paged_append(pages_k, pages_v, page_table, pos, k, v,
                 scales_k=None, scales_v=None):
    """Scatter a [B, T] chunk of new K/V into the page-major page pool
    at each slot's current write offset (append-at-offset: the chunk
    may START mid-page and SPAN page boundaries — the partial-prompt
    case chunked prefill creates).

    pages_k/pages_v: [n_pages, Pg, KH, D] (page-major pool)
    page_table:      [B, max_pages] int32 (0 = null page)
    pos:             [B] int32 — first token of the chunk lands at
                     logical position ``pos[b]``
    k/v:             [B, T, KH, D] new keys/values

    A pool of latent pages [n_pages, Pg, D] has no V and no head axis:
    ``pages_v`` and ``v`` are None, ``k`` [B, T, 1, D] is the chunk of
    latent entries, and a 1-tuple comes back.

    Token t of row b goes to physical page
    ``page_table[b, (pos[b]+t) // Pg]`` at offset ``(pos[b]+t) % Pg``.
    Positions past the row's allocated pages resolve to page-table
    entries of 0 (the null page), so oversized/padding tails scatter
    harmlessly — the same null-page discipline the decode step uses
    for inactive slots. Logical positions are clamped to the
    addressable window so a padded tail can never alias another
    slot's pages through index clamping.

    Int8 pools pass ``scales_k``/``scales_v`` ([n_pages, KH] fp32
    per-page absmax) and get a 4-tuple back (pages + updated scales).
    The append then does three scatters per tensor:

    1. SCALE RESET: any token landing at in-page offset 0 marks its
       page "starting over" — its old scale contribution came from a
       previous owner (the allocator reuses page ids) and is zeroed.
       This is the whole scale lifecycle: no host-side bookkeeping on
       free/realloc, because the first write a fresh logical page ever
       receives is always at offset 0.
    2. RUNNING ABSMAX: per-token absmax is scatter-MAXed into the
       (reset-adjusted) page scales — the page scale only grows while
       a page is live, so earlier tokens stay representable.
    3. REQUANTIZE + STORE: pages the chunk touches are re-coded from
       the old scale to the new one (``round(q_old * s_old/s_new)``,
       0 where the page was reset), then the chunk tokens are
       quantized at the new scale and scattered on top. Duplicate
       page entries write byte-identical values, so scatter order
       cannot matter.

    Quantized bytes are WRITE-HISTORY dependent: appending one token
    at a time re-rounds earlier tokens at each scale growth, so an
    incrementally-built page need not match a bulk-built one bit for
    bit. That is why engine-level parity with fp KV is tolerance-gated
    (docs/serving.md) while replica failover stays bit-exact (same
    write history on every replica).

    Raises :class:`PagedShapeError` at trace time on any rank / head /
    head-dim / batch mismatch between the chunk and the pool, or when
    scales are supplied for a non-int8 pool (and vice versa).
    """
    _check_append_shapes(pages_k, pages_v, page_table, pos, k, v)
    quantized = scales_k is not None or scales_v is not None
    if quantized and (scales_k is None or scales_v is None):
        raise PagedShapeError(
            "scales_k and scales_v must be supplied together")
    if quantized and pages_v is None:
        raise PagedShapeError(
            "per-page scales supplied for a pool without V pages: "
            "latent pages are not quantized")
    if not quantized and pages_k.dtype == jnp.int8:
        raise PagedShapeError(
            "int8 pool appended without its per-page scales — pass "
            "scales_k/scales_v (kv_dtype='int8' wiring bug)")
    n_pages, Pg = pages_k.shape[:2]
    KH, D = k.shape[2:]
    if quantized:
        _check_scale_shapes(pages_k, scales_k, scales_v,
                            (n_pages, KH))
    B, T = k.shape[:2]
    max_pages = page_table.shape[1]
    tpos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]  # [B, T]
    tpos = jnp.minimum(tpos, max_pages * Pg - 1)
    pidx = jnp.take_along_axis(page_table, tpos // Pg, axis=1)  # [B, T]
    off = tpos % Pg
    flat_p = pidx.reshape(-1)
    flat_o = off.reshape(-1)
    # [B, T, KH, D] -> [B*T, KH, D]: a token's row as it lies in a page
    kT = k.reshape(B * T, KH, D)
    if pages_v is None:
        return (pages_k.at[flat_p, flat_o].set(
                    kT.reshape(B * T, D).astype(pages_k.dtype)),)
    vT = v.reshape(B * T, KH, D)
    if not quantized:
        return (pages_k.at[flat_p, flat_o].set(
                    kT.astype(pages_k.dtype)),
                pages_v.at[flat_p, flat_o].set(
                    vT.astype(pages_v.dtype)))

    # (1) pages whose offset-0 slot this chunk writes start over.
    reset = jnp.zeros((n_pages,), jnp.bool_).at[flat_p].max(
        flat_o == 0)                                   # [n_pages]

    def _one(pages, scales, xT):
        xT32 = xT.astype(jnp.float32)                  # [B*T, KH, D]
        s_base = jnp.where(reset[:, None], 0.0,
                           scales.astype(jnp.float32))
        # (2) running absmax, monotone while the page is live.
        amax = jnp.max(jnp.abs(xT32), axis=2)          # [B*T, KH]
        s_new = s_base.at[flat_p].max(amax)            # [n_pages, KH]
        # (3a) re-code touched pages old-scale -> new-scale. Gathering
        # per token (not per unique page) keeps this jit-static;
        # duplicates recompute identical bytes.
        old_q = pages[flat_p].astype(jnp.float32)      # [BT, Pg, KH, D]
        sb = s_base[flat_p]                            # [BT, KH]
        sn = s_new[flat_p]
        ratio = jnp.where(sn > 0.0, sb / jnp.maximum(sn, 1e-30), 0.0)
        req = jnp.clip(jnp.round(old_q * ratio[:, None, :, None]),
                       -_QMAX, _QMAX).astype(jnp.int8)
        pages = pages.at[flat_p].set(req)
        # (3b) quantize the chunk tokens at the new scale. A zero page
        # scale implies the token itself is all-zero (absmax was maxed
        # in above), so the guarded divide is exact, not a fudge.
        inv = jnp.where(sn > 0.0, _QMAX / jnp.maximum(sn, 1e-30), 0.0)
        q_tok = jnp.clip(jnp.round(xT32 * inv[..., None]),
                         -_QMAX, _QMAX).astype(jnp.int8)
        pages = pages.at[flat_p, flat_o].set(q_tok)
        return pages, s_new.astype(scales.dtype)

    new_pk, new_sk = _one(pages_k, scales_k, kT)
    new_pv, new_sv = _one(pages_v, scales_v, vT)
    return new_pk, new_pv, new_sk, new_sv


# Tokens of context one iteration of the paged window loop gathers and
# attends (rounded to whole pages): the unit in which the attended
# window follows the live contexts. Smaller blocks waste less on the
# last, partly filled block and pay the loop's fixed cost more often
# (PERF.md section 6, PR 26 has the chip's readings).
_WINDOW_BLOCK_TOKENS = 512


# Rows a KV head's query group presents to the block loop's two
# contractions at the least. A group of ONE row (one query head a KV
# head, one token a row: a decode step over a cache with as many KV
# heads as heads) is a matrix-vector product; XLA reduces it to a
# multiply-and-reduce on the vector unit, which on a TPU v5e has no
# bfloat16, behind a float32 copy of the whole gathered K block and V
# block: two thirds of the bytes attention moves, 1.07 ms a layer-step
# where the padded form takes 0.35 (PERF.md section 6, PR 33). Two is
# the smallest count the chip's compiler keeps on the matrix unit, and
# four and eight measured no faster (0.35 and 0.37 ms).
_MIN_GROUP_ROWS = 2


def paged_window_block_pages(page_size: int, max_pages: int) -> int:
    """Logical pages one iteration of the paged window loop covers: a
    constant of the shapes, not a knob."""
    return min(max_pages, max(1, _WINDOW_BLOCK_TOKENS // page_size))


def _paged_window_attention(q, pk, pv, sk, sv, page_table, pos,
                            softmax_scale=None, value_dim=None,
                            block_len: int = 1):
    """Causal grouped-query attention of ``q`` [B, T, H, D] (row b's
    queries at absolute positions ``pos[b] + t``) over its page-table
    row's K/V in the page-major pool ``pk``/``pv`` [n_pages, Pg, KH, D]
    (``sk``/``sv``: an int8 pool's per-page scales [n_pages, KH], else
    None). Each page is gathered whole, by its id, as it lies.

    ``block_len`` (static): the mask is BLOCK-CAUSAL, the query at
    position i sees the keys below ``(i // block_len + 1) * block_len``:
    every key of its own block of ``block_len`` positions, later ones
    among them, and of every block before (a model that decodes by
    blocks, models/kv_cache.py ``BlockDecode``: the caller has appended
    the whole block). 1, every other model's: the causal mask, and the
    traced program is what it was before the argument existed. The loop
    and the decode kernel serve ``block_len > 1``; the latent kernel
    masks causally.

    ``pv`` None (a pool of latent pages ``pk`` [n_pages, Pg, D], one
    KV head with no axis of its own): a key's value is the first
    ``value_dim`` columns of the key itself, so a block is gathered
    ONCE and the result is [B, T, H, value_dim]. ``softmax_scale``:
    what the scores are multiplied by, where it is not ``D ** -0.5``.

    Three forms, chosen by what the arguments show. A chunk of at
    least one query tile over a latent pool of bfloat16 entries, on a
    TPU outside any multi-device mesh, is ONE Pallas kernel
    (``latent_window.applies``; ops/latent_window_attention.py: the
    scores and the accumulator in VMEM, each row walked to its own last
    block). A decode step over a bfloat16 K/V pool without int8 scales
    (one query a row, or under the block mask as many as fit a visit's
    scores: a block of ``block_len`` positions) or over a bfloat16
    latent pool (one query a row), there too, is ANOTHER
    (``paged_decode.applies``; ops/paged_decode_attention.py: each
    rider's own pages read once, where they lie, a row without a rider
    not at all, and zeros read out for it). Both are the same
    mathematics and are called under the ``attn_scores`` scope.
    Everything else is the loop below: prefill chunks and verifies over
    K/V pools, a verify over a latent pool, int8 and float32 pools, the
    CPU, a mesh.

    Work follows the live contexts, not the table's width: a loop with
    a RUNTIME trip count walks blocks of ``block_pages`` logical pages
    up to the block holding the last position any live row can see,
    folding each block's float32 scores into a running max / sum /
    accumulator (the online softmax: the same mathematics as one
    softmax over the whole window, nothing approximated, no visible
    position left out). A live row is one whose page-table row is not
    the null row: non-riders and dummy prefill rows carry rows of 0,
    and their ``pos`` may be stale and large, so they must not widen
    the window. The count is a value, not a shape: one executable
    serves every context length, and inside the decode loop it is
    recomputed every step, so a context that crosses a block's edge in
    the middle of a dispatch is still attended whole.

    A KV head's query group (``H // KH`` heads x T tokens) never
    presents fewer than ``_MIN_GROUP_ROWS`` rows to the two
    contractions of a block, so that they stay matrix products that
    read the gathered blocks as the bfloat16 they are stored in: a
    one-row group cost a float32 copy of every gathered K and V block
    (1.07 -> 0.35 ms a layer-step of OLMoE's 32-row decode on a TPU
    v5e).

    The named scopes (kv_gather, attn_scores, attn_pv) are metadata
    only: a device trace splits a step's time by them (PERF.md
    section 3).
    """
    B, T, H, D = q.shape
    Pg, KH = pk.shape[1], (1 if pv is None else pk.shape[2])
    if (pv is None) != (value_dim is not None):
        raise PagedShapeError(
            "value_dim names the columns of a key that are its value "
            "where there is no V pool, and only there; got pv "
            f"{'None' if pv is None else pv.shape}, value_dim "
            f"{value_dim}")
    Dv = D if value_dim is None else value_dim
    max_pages = page_table.shape[1]
    block_pages = paged_window_block_pages(Pg, max_pages)
    Lb = block_pages * Pg
    max_blocks = -(-max_pages // block_pages)
    causal = block_len == 1
    if causal and pv is None and sk is None \
            and latent_window.applies(q, pk, Dv):
        # under one of the loop's scopes: a device trace's split of a
        # step by scope keeps counting it as attention
        with jax.named_scope("attn_scores"):
            return latent_window.latent_window_attention(
                q, pk, page_table, pos, value_dim=Dv,
                softmax_scale=(D ** -0.5 if softmax_scale is None
                               else softmax_scale),
                block_pages=block_pages)
    if paged_decode.applies(q, pk, pv, sk, page_table, value_dim,
                            block_len):
        with jax.named_scope("attn_scores"):
            return paged_decode.paged_decode_attention(
                q, pk, pv, page_table, pos,
                softmax_scale=float(D ** -0.5 if softmax_scale is None
                                    else softmax_scale),
                value_dim=value_dim, block_len=block_len)
    # Grouped-query attention WITHOUT materializing repeated K/V: q
    # reshapes to [B, T, KH, rep, D] and contracts against the grouped
    # cache directly (a repeat would move rep x the KV bytes a step).
    rep = H // KH
    qg = q.reshape(B, T, KH, rep, D)
    if rep * T < _MIN_GROUP_ROWS and sk is None:
        # a zero row beside the real one: it scores 0 against every
        # key, touches none of the real row's sums and is dropped at
        # the end. The operands stay as they are stored (bfloat16 on a
        # deployment: exact products, float32 accumulation).
        rows = _MIN_GROUP_ROWS
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, rows - rep), (0, 0)))
    else:
        # an int8 pool's dequantised blocks are float32 values that no
        # bfloat16 holds: they keep the float32 contraction
        rows = rep
        qg = qg.astype(jnp.float32)
    # causal over absolute positions: query t of row b sits at
    # pos[b] + t and sees keys 0..pos[b]+t
    q_pos = pos[:, None] + jnp.arange(T)[None]              # [B, T]
    if not causal:
        # block-causal: the LAST key a query sees is its block's last
        q_pos = (q_pos // block_len + 1) * block_len - 1
    with jax.named_scope("kv_gather"):
        # a whole number of blocks: columns past the table are null
        # pages, which the mask never lets a live query see
        table = jnp.pad(
            page_table,
            ((0, 0), (0, max_blocks * block_pages - max_pages)))
        live = page_table[:, 0] != 0
        # the last position any live row's last query sees
        last_seen = pos + (T - 1) if causal else q_pos[:, -1]
        last = jnp.max(jnp.where(live, last_seen, 0))
        n_blocks = jnp.minimum(last // Lb + 1, max_blocks)

    def block(j, carry):
        m, l, acc = carry
        with jax.named_scope("kv_gather"):
            cols = jax.lax.dynamic_slice_in_dim(
                table, j * block_pages, block_pages, axis=1)
            # [B, block_pages, Pg, KH, D] -> [B, Lb, KH, D]; gathered
            # index + j * Lb == logical position by construction
            kg = pk[cols]
            if pv is not None:
                vg = pv[cols]
            if sk is not None:
                # dequantize the gathered block in fp32 with the
                # gathered per-page scales (value = q * s / 127): only
                # one block ever exists in fp, never the pool itself
                kg = kg.astype(jnp.float32) * \
                    (sk[cols] * (1.0 / 127.0))[:, :, None, :, None]
                vg = vg.astype(jnp.float32) * \
                    (sv[cols] * (1.0 / 127.0))[:, :, None, :, None]
            kg = kg.reshape(B, Lb, KH, D)
            if pv is not None:
                vg = vg.reshape(B, Lb, KH, D)
            else:
                vg = kg[..., :Dv]
        with jax.named_scope("attn_scores"):
            s = jnp.einsum("btkrd,bskd->bkrts", qg,
                           kg.astype(qg.dtype),
                           preferred_element_type=jnp.float32)
            s = (s / np.sqrt(D) if softmax_scale is None
                 else s * softmax_scale)
            valid = (j * Lb + jnp.arange(Lb))[None, None] <= \
                q_pos[:, :, None]                            # [B, T, Lb]
            s = jnp.where(valid[:, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            scale = jnp.exp(m - m_new)
            l = l * scale + jnp.sum(p, axis=-1)
        with jax.named_scope("attn_pv"):
            acc = acc * scale[..., None] + jnp.einsum(
                "bkrts,bskd->bkrtd", p.astype(vg.dtype), vg,
                preferred_element_type=jnp.float32)
        return m_new, l, acc

    stat = (B, KH, rows, T)
    carry = (jnp.full(stat, -1e30, jnp.float32),
             jnp.zeros(stat, jnp.float32),
             jnp.zeros(stat + (Dv,), jnp.float32))
    if max_blocks == 1:
        # the table is one block wide: no loop, the one-shot softmax
        # over the whole window as straight-line code
        carry = block(0, carry)
    else:
        carry = jax.lax.fori_loop(0, n_blocks, block, carry)
    _, l, acc = carry
    with jax.named_scope("attn_pv"):
        # key 0 is visible to every query, so l > 0
        y = (acc / l[..., None]).astype(q.dtype)
    if rows > rep:
        y = y[:, :, :rep]
    # [B, KH, rep, T, Dv] -> [B, T, H, Dv]
    return y.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Dv)


# ------------------------------------------------ sliding-window rings

def ring_append(ring_k, ring_v, slots, pos, k, v, valid):
    """Write a [B, T] chunk of new K/V into the rows' rings: token t of
    row b, at absolute position ``pos[b] + t``, goes to index
    ``(pos[b] + t) mod L`` of slot ``slots[b]``'s ring (row b's own
    where ``slots`` is None: a decode call, whose row i is slot i).
    Positions that are not ``valid`` [B, T] (padding behind a row's
    last real token, rows that carry no request, whose ``pos`` may be
    stale) and rows whose slot is out of range are dropped: a ring is
    only ever written by its own request's real tokens.

    ring_k/ring_v: [n_slots, KH, L, D]; k/v: [B, T, KH, D]
    """
    _, KH, L, D = ring_k.shape
    B, T = k.shape[:2]
    if k.shape[2:] != (KH, D) or k.shape != v.shape:
        raise PagedShapeError(
            f"chunk {k.shape} / {v.shape} does not fit rings of "
            f"{ring_k.shape}")
    if T > L:
        raise PagedShapeError(
            f"a chunk of {T} tokens laps a ring of {L}")
    rows = (jnp.arange(B, dtype=jnp.int32) if slots is None else slots)
    idx = (pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]) % L
    idx = jnp.where(valid, idx, L)              # out of range: dropped
    # one scattered row a (token, KV head): [D] values at (slot, head,
    # index). A token's [KH, D] rows scattered as one window wanted
    # the ring position-major, and the contractions want it head-major:
    # the compiler then re-laid every ring out on every step.
    at = (rows[:, None, None], jnp.arange(KH)[None, None, :],
          idx[:, :, None])
    return (ring_k.at[at].set(k.astype(ring_k.dtype), mode="drop"),
            ring_v.at[at].set(v.astype(ring_v.dtype), mode="drop"))


def ring_attention(q, ring_k, ring_v, pos, valid, window: int):
    """Sliding-window grouped-query attention of ``q`` [B, T, H, D]
    (row b's queries at absolute positions ``pos[b] + t``) over the
    rows' rings ``ring_k``/``ring_v`` [B, KH, L, D] (row b's own: the
    caller has taken them by slot) AFTER ``ring_append`` of the same
    chunk: query at
    position i sees the keys at i - window < j <= i, its own among
    them, and nothing else; exact, one softmax over the ring.

    What a ring index holds follows from the row's last written
    position p (``pos`` + its count of ``valid`` positions - 1) alone:
    index r holds position ``p - ((p - r) mod L)``, the newest position
    congruent to r that the request has reached, or, where that is
    negative, nothing of this request (whatever a previous owner of the
    slot left there is never visible). A chunk's writes replace
    positions a whole ring behind them, which no query of the chunk can
    see as long as ``T <= L - window + 1``: checked here, and what
    models/kv_cache.py ``sliding_ring_len`` sizes L for.

    The scopes (ring_scores, ring_pv) are metadata only, as
    ``_paged_window_attention``'s.
    """
    B, T, H, D = q.shape
    KH, L = ring_k.shape[1:3]
    if T > L - window + 1:
        raise PagedShapeError(
            f"a chunk of {T} queries over a window of {window} needs a "
            f"ring of at least {window + T - 1} positions, got {L}")
    rep = H // KH
    qg = q.reshape(B, T, KH, rep, D)
    with jax.named_scope("ring_scores"):
        last = pos + jnp.sum(valid, axis=1, dtype=jnp.int32) - 1     # [B]
        age = (last[:, None] - jnp.arange(L, dtype=jnp.int32)[None]) % L
        k_pos = last[:, None] - age                              # [B, L]
        q_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        seen = ((k_pos[:, None] <= q_pos[:, :, None])
                & (k_pos[:, None] > q_pos[:, :, None] - window)
                & (k_pos[:, None] >= 0))                         # [B, T, L]
        s = jnp.einsum("btkrd,bksd->bkrts", qg, ring_k.astype(qg.dtype),
                       preferred_element_type=jnp.float32) / np.sqrt(D)
        s = jnp.where(seen[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
    with jax.named_scope("ring_pv"):
        y = jnp.einsum("bkrts,bksd->btkrd", p.astype(ring_v.dtype),
                       ring_v, preferred_element_type=jnp.float32)
    return y.astype(q.dtype).reshape(B, T, H, D)


def dequantize_pages(pages, scales):
    """Debug/test helper: materialize the fp view of a page-major int8
    pool ([n_pages, Pg, KH, D], scales [n_pages, KH]: ``q * s / 127``).
    NEVER used on the serving path — the whole point of the int8 mode
    is that this tensor never exists there."""
    return pages.astype(jnp.float32) * (
        scales.astype(jnp.float32) / _QMAX)[:, None, :, None]
