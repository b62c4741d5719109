"""Pallas paged-attention decode kernel (the vLLM kernel, TPU-style).

The continuous-batching engine's decode step attends each slot's
single query against its KV pages. The XLA fallback (models/llama.py
_paged_window_attention) GATHERS the pages into dense [B, L, KH, D]
blocks every step, up to the batch's longest live context (it once
gathered the whole page table's width, the dominant HBM traffic of
the decode loop). This kernel never materializes the
window: the page table rides scalar prefetch
(pltpu.PrefetchScalarGridSpec) and each grid step DMAs exactly one
physical page per (slot, kv-head), accumulating flash-style online
softmax in VMEM. Per-step traffic drops from O(B * L) gathered copies
to O(B * L) page READS only — no gathered intermediate, no scatter of
it back.

Two layout contracts live in this file. ``paged_append`` writes the
engine's pool, which is PAGE-MAJOR (models/kv_cache.py):
``[n_pages, page_size, n_kv_heads, head_dim]``, scales
``[n_pages, n_kv_heads]``. The KERNEL keeps its own head-major
contract, and its one caller (models/llama.py, under
RAY_TPU_PAGED_KERNEL=1) hands it a transposed view of the pool
(``kernel_pool_view``):
  pages_k/pages_v: [n_kv_heads, n_pages, page_size, head_dim] —
                   HEAD-MAJOR so each grid step's block is one
                   contiguous [page_size, head_dim] tile, which
                   Mosaic can tile (page-major would put a size-1
                   slice of n_kv_heads in the sublane dim)
  scales_k/scales_v: [n_kv_heads, n_pages, 1] fp32 (int8 pools)
  page_table:      [n_slots, max_pages] int32 (0 = null page)
  positions:       [n_slots]            int32 — current decode
                   position; the step attends keys 0..pos inclusive
  q:               [n_slots, n_heads, head_dim] (grouped-query: head
                   h uses kv head h // (n_heads // n_kv_heads))

Grid (B, n_pages_per_slot): the page dimension is innermost, so TPU
executes it sequentially per slot and the online-softmax scratch
carries across pages. Each grid step processes ONE physical page for
ALL kv heads at once — the block ``[KH, 1, Pg, D]`` is a strided but
Mosaic-expressible slice of the head-major pool, so one step moves
KH*(Pg*D) bytes per tensor (64KB at 1.1B shapes) instead of a 4KB
single-head page, and the [KH, rep, Pg] score tile fills the VPU
sublanes. (A first cut used grid (B, KH, pages) with one head-page
per step: 4096 serialized 4KB DMAs measured 31ms/step at 1.1B-16-slot
shapes vs 8.2ms for XLA's dense gather — DMA-issue latency-bound.)
Inactive slots point at the null page and mask everything — their
outputs are ignored host-side.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Int8 pages use a symmetric absmax code: value = q * scale / 127 with
# q in [-127, 127] (-128 unused so the code is symmetric). One fp32
# scale per (kv_head, physical page) — coarse enough to cost 4 bytes
# per page per head, fine enough that one outlier page cannot poison
# the whole pool's precision.
_QMAX = 127.0


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


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
    if not quantized and pages_k.dtype == jnp.int8:
        raise PagedShapeError(
            "int8 pool appended without its per-page scales — pass "
            "scales_k/scales_v (kv_dtype='int8' wiring bug)")
    n_pages, Pg, KH, D = pages_k.shape
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


def _attend_page(b, p, pos_ref, q_ref, k, v, o_ref,
                 m_sc, l_sc, acc_sc, *, page_size: int, scale: float):
    """Shared flash-style online-softmax body: one physical page of
    already-dequantized fp32 K/V for all kv heads."""
    n_p = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)             # [KH, rep, D]
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale   # [KH, rep, Pg]
    pos = pos_ref[b]
    kpos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 2)
    s = jnp.where(kpos <= pos, s, _NEG_INF)

    m_prev = m_sc[...]                            # [KH, rep, 1]
    m_cur = jnp.max(s, axis=2, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # Fully-masked pages keep exp() finite.
    m_safe = jnp.maximum(m_new, -1e29)
    alpha = jnp.exp(m_prev - m_safe)
    pexp = jnp.exp(s - m_safe)                    # [KH, rep, Pg]
    l_sc[...] = l_sc[...] * alpha + \
        jnp.sum(pexp, axis=2, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
        pexp, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)       # [KH, rep, D]
    m_sc[...] = m_new

    @pl.when(p == n_p - 1)
    def _fin():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def _kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_sc, l_sc, acc_sc, *, page_size: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)
    k = k_ref[:, 0].astype(jnp.float32)          # [KH, Pg, D]
    v = v_ref[:, 0].astype(jnp.float32)          # [KH, Pg, D]
    _attend_page(b, p, pos_ref, q_ref, k, v, o_ref,
                 m_sc, l_sc, acc_sc, page_size=page_size, scale=scale)


def _kernel_q(pt_ref, pos_ref, sk_ref, sv_ref, q_ref, k_ref, v_ref,
              o_ref, m_sc, l_sc, acc_sc, *, page_size: int,
              scale: float):
    """Int8 variant: the fp32 absmax scales of the pages this call
    attends ride SCALAR PREFETCH next to the page table (flat
    ``[KH * B * max_pages]``, gathered by the wrapper), and the
    dequantize happens IN REGISTER right after the page DMA — the fp
    window never exists in HBM or VMEM, so the kernel's memory
    footprint is the halved int8 one. (A ``(KH, 1, 1)`` VMEM block
    over the ``[KH, n_pages, 1]`` scale tensor is not tileable: Mosaic
    wants the last two block dims (8, 128)-aligned or whole.)"""
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_b = pl.num_programs(0)
    n_p = pl.num_programs(1)
    KH = k_ref.shape[0]
    inv = 1.0 / _QMAX
    # [KH, 1, 1] scale columns assembled from KH SMEM scalars: KH is
    # the untiled leading dim, so the selects touch one vreg each.
    h_iota = jax.lax.broadcasted_iota(jnp.int32, (KH, 1, 1), 0)
    sk = jnp.zeros((KH, 1, 1), jnp.float32)
    sv = jnp.zeros((KH, 1, 1), jnp.float32)
    for h in range(KH):
        i = (h * n_b + b) * n_p + p
        sk = jnp.where(h_iota == h, sk_ref[i] * inv, sk)
        sv = jnp.where(h_iota == h, sv_ref[i] * inv, sv)
    k = k_ref[:, 0].astype(jnp.float32) * sk      # [KH, Pg, D]
    v = v_ref[:, 0].astype(jnp.float32) * sv
    _attend_page(b, p, pos_ref, q_ref, k, v, o_ref,
                 m_sc, l_sc, acc_sc, page_size=page_size, scale=scale)


def kernel_pool_view(t):
    """The kernel's head-major view of one tensor of the engine's
    page-major pool: pages [n_pages, Pg, KH, D] -> [KH, n_pages, Pg, D],
    an int8 pool's scales [n_pages, KH] -> [KH, n_pages, 1], None as
    it is (an fp pool has no scales)."""
    if t is None:
        return None
    return t.transpose(2, 0, 1, 3) if t.ndim == 4 else t.T[..., None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, pages_k, pages_v, page_table, positions,
                           scales_k=None, scales_v=None,
                           interpret: bool | None = None):
    """One decode step of paged attention.

    q: [B, H, D]; returns [B, H, D] in q.dtype. See module docstring
    for the kernel's HEAD-MAJOR layout ([KH, n_pages, Pg, D]: a
    transposed view of the engine's page-major pool). Falls back
    transparently to interpreter mode off-TPU (tests). Int8 pools pass scales_k/scales_v
    ([KH, n_pages, 1] fp32) and get in-register dequantization.
    """
    B, H, D = q.shape
    KH, n_pages, Pg, Dk = pages_k.shape
    assert D == Dk, (D, Dk)
    rep = H // KH
    max_pages = page_table.shape[1]
    qg = q.reshape(B, KH, rep, D)
    scale = 1.0 / (D ** 0.5)
    quantized = scales_k is not None
    if quantized:
        _check_scale_shapes(pages_k, scales_k, scales_v,
                            (KH, n_pages, 1))

    grid = (B, max_pages)
    prefetch = [page_table, positions]
    kern = _kernel
    if quantized:
        # the scales of exactly the pages the table names, flat
        # [KH * B * max_pages] fp32: KH x the page table's own SMEM
        # footprint, whatever the pool size
        prefetch += [scales_k[:, page_table, 0].reshape(-1),
                     scales_v[:, page_table, 0].reshape(-1)]
        kern = _kernel_q

    def _page(b, p, pt, *_):
        # ONE physical page of K/V across ALL kv heads, chosen by
        # the scalar-prefetched page table: [KH, 1, Pg, D]
        return (0, pt[b, p], 0, 0)

    def _slot(b, p, *_):
        # q/out block for this slot, every head: [1, KH, rep, D]
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, KH, rep, D), _slot),
        pl.BlockSpec((KH, 1, Pg, D), _page),
        pl.BlockSpec((KH, 1, Pg, D), _page),
    ]
    kernel = functools.partial(kern, page_size=Pg, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KH, rep, D), _slot),
            scratch_shapes=[
                pltpu.VMEM((KH, rep, 1), jnp.float32),    # m
                pltpu.VMEM((KH, rep, 1), jnp.float32),    # l
                pltpu.VMEM((KH, rep, D), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, rep, D), q.dtype),
        interpret=_interpret() if interpret is None else interpret,
        name="paged_decode",
    )(*prefetch, qg, pages_k, pages_v)
    return out.reshape(B, H, D)


def dequantize_pages(pages, scales):
    """Debug/test helper: materialize the fp view of a page-major int8
    pool ([n_pages, Pg, KH, D], scales [n_pages, KH]: ``q * s / 127``).
    NEVER used on the serving path — the whole point of the int8 mode
    is that this tensor never exists there."""
    return pages.astype(jnp.float32) * (
        scales.astype(jnp.float32) / _QMAX)[:, None, :, None]
