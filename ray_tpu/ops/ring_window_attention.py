"""A sliding-window layer's append and attention over the rows' rings as
one Pallas kernel, each ring read and written where it lies.

ops/paged_attention.py ``ring_append`` + ``ring_attention`` are plain
``jax.numpy``, and what the chip's compiler made of them passed over
WHOLE rings (PERF.md section 6, PRs 42 and 52): every decode step
moved every slot's ring of every sliding layer through the fast memory
and back (``copy-done bf16[32,4,1344,128]``, 1.17 of the 1.50 ms the
six sliding layers cost a step) and scored all 32 slots' rings where
24 rode; a prefill call's scatter of 4 x 256 tokens became a pass over
the 172,032 rows of a whole ring (0.225 ms, twelve a call), its rows'
rings were gathered by slot, and its float32 scores ``[4, 32, 256,
1344]`` (176 MB a layer) went through HBM.

``ring_window_attention`` is the same mathematics (operands as stored,
float32 accumulation in both contractions, float32 scores x
``1 / sqrt(D)``, the softmax in float32, ``p`` cast to the ring's type
before the read-out; the softmax over blocks of keys differs from the
one-piece form in rounding only), with one call for both:

- the rings ``[n_slots, KH, L, D]`` go in and come out as the SAME
  buffers (``input_output_aliases``): a visit's slab is fetched by the
  index map, from HBM, by the row's slot, and what a call writes goes
  back by DMA, a block of ``write_rows`` ring indices at a time and
  only the blocks the row's tokens touch. A slot that no live row
  names is neither read nor written;
- the grid walks the call's LIVE rows first (the visits' order goes in
  by scalar prefetch); a visit left over names the block the last live
  one fetched, which the pipeline does not fetch again, and writes
  zeros for its row;
- a visit scores its queries against the row's NEW keys as they came
  (they are in fast memory already: nothing is read back behind its own
  write) and then against the slab as it WAS, block by block, under the
  mask ``ring_attention`` has: with ``w = pos mod L`` the index the
  row's first new token goes to, index r held position ``pos - w + r``
  (less L from ``w`` on) before the call, seen by the query at ``i``
  where it is not negative and ``> i - window``. The chunk's own
  writes land on positions a whole ring behind it, which no query of
  the call sees (``T <= L - window + 1``);
- the block a token lands in is merged in fast memory: a 0/1 placement
  matrix times the new rows (exact in any type) under the mask of the
  indices it hits, then stored whole, so no DMA has a length or a start
  that is not a whole tile.

A decode step (``T == 1``) takes a row's KV heads in ONE visit (all
``H`` query heads against each head's slab, the other groups' products
masked out of the softmax like PR 47's: the bytes are all a step costs,
so a visit is as fat as a slot); a chunk takes a (row, KV head) a visit,
its ``rep x T`` query rows (PR 43's 2,048 at Mellum 2's shape) one
tile.

Which calls the kernel serves is ``applies``'s rule: shapes, types, the
backend and the ambient mesh, never a flag. Everything else (the CPU, a
mesh, another type) runs ``ring_append`` + ``ring_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the backend is a TPU and no multi-device mesh is ambient: one rule
# for every Mosaic kernel that has an XLA form
from ray_tpu.ops.grouped_matmul import on_one_tpu as _on_one_tpu
from ray_tpu.ops.paged_attention import ring_append, ring_attention

_NEG_INF = -1e30
_NEVER = -(1 << 30)                   # a position no query sees
_LANES = 128
_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_NN = (((1,), (0,)), ((), ()))        # a @ b

# Ring indices one write-back block holds: the largest of these that
# divides L (1,344 = 21 x 64). A token's block is merged in fast memory
# and stored whole, so a decode step writes 64 x 256 B a (slot, KV
# head) for the 256 B that changed: 16 KB of 688 fetched.
_WRITE_ROWS = (64, 32, 16)
# Bytes of float32 scores one fold of the online softmax spans: the
# tile's query rows x a block of keys in whole lane tiles. A decode
# step's 32 rows take the whole ring in ONE fold, a chunk's 2,048 rows
# 256 keys a fold. Measured on v5e (PR 52, tools/ring_window_bench.py;
# ms a layer-call over six layers' rings [32, 4, 1344, 128] in turn,
# keys a fold rule | 256 | 512): a decode step at 24 riders of 32 rows
# **0.1018** | 0.1024 | 0.1017 (and 128: 0.1031), at 32 of 32 **0.1326**
# | 0.1325 | 0.1324: the folds do not show, a rider is its 2.75 MB at
# 666 GB/s (4.13 us) and a row without one 0.29 us; the pair it
# replaces 0.146 here (and 0.257 inside the decode program, where the
# compiler moved every ring through the fast memory). A [4, 256] chunk
# **0.2310** | 0.2309 | 0.2309 (128 keys a fold: the chip has no room
# for the unrolled folds); the pair 1.225.
_SCORE_BYTES = 2 << 20


def write_rows(L: int, T: int) -> Optional[int]:
    """Ring indices a write-back block holds for a call of ``T`` tokens
    a row: the largest that divides a ring of ``L`` and leaves the
    call's blocks apart (a row's tokens and a block more inside the
    ring), or None where there is none."""
    return next((w for w in _WRITE_ROWS
                 if L % w == 0 and T + w <= L), None)


def key_spans(rows: int, L: int, block: Optional[int] = None):
    """The ``(start, stop)`` blocks of ring indices a tile of ``rows``
    query rows folds one at a time: whole lane tiles inside
    ``_SCORE_BYTES`` of scores (``block`` where given), the last one
    what is left of L."""
    block = block or max(_LANES,
                         _SCORE_BYTES // (4 * rows) // _LANES * _LANES)
    return [(c, min(c + block, L)) for c in range(0, L, block)]


def applies(q, k, v, ring_k, ring_v, window: int) -> bool:
    """Whether the kernel serves ``q`` [B, T, H, D] and the new ``k``,
    ``v`` [B, T, KH, D] over rings [n_slots, KH, L, D]: everything
    bfloat16, whole query groups, a head of whole 128-lane tiles, a
    ring of whole write-back blocks and whole bfloat16 sublane tiles, a
    ``T`` that is one token (its ``H`` query rows a whole tile) or
    whole sublane tiles of tokens that a ring takes beside the window,
    and a TPU outside any multi-device mesh. Only shapes and types are
    read: ``ring_window_attention`` asks it of its arguments, and the
    engine of the same shapes for its ``sliding_kernel_keys``."""
    (_, T, H, D), (_, KH, L, _) = q.shape, ring_k.shape
    return (q.dtype == k.dtype == v.dtype == ring_k.dtype == ring_v.dtype
            == jnp.bfloat16
            and k.shape[2:] == (KH, D) and H % KH == 0
            and D % _LANES == 0 and write_rows(L, T) is not None
            and (H % 16 == 0 if T == 1 else T % 16 == 0)
            and T <= L - window + 1 and _on_one_tpu())


def kernel_keys(riders: int, L: int) -> int:
    """Ring positions ONE sliding layer's kernel fetches for a decode
    dispatch's ``riders`` (a host integer): each rider's whole ring,
    scored under the mask, and no ring of a slot without a rider. The
    engine's ``sliding_kernel_keys``, beside ``decode_sliding_keys``
    (the riders' windows, what must be read)."""
    return riders * L


def _split(x, n: int):
    """``x // n`` of non-negative ``x``."""
    if n & (n - 1) == 0:
        return x >> (n.bit_length() - 1)
    return jax.lax.div(x, n)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _ring_kernel(order_ref, slot_ref, pos_ref, w_ref, n_ref, live_ref,
                 q_ref, kn_ref, vn_ref, rk_ref, rv_ref,
                 y_ref, ko_ref, vo_ref, wk_scr, wv_scr, sem, *,
                 scale: float, window: int, rep: int, spans):
    i, g = pl.program_id(0), pl.program_id(1)
    row = order_ref[i]
    hv, L, D = rk_ref.shape[1:]
    T = kn_ref.shape[1]
    R = rep * T * hv                    # query rows a visit
    n_blocks, _, wb, _ = wk_scr.shape
    dtype = rk_ref.dtype
    f32, i32 = jnp.float32, jnp.int32

    @pl.when(i >= live_ref[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < live_ref[0])
    def _():
        slot, pos, w, n = (slot_ref[row], pos_ref[row], w_ref[row],
                           n_ref[row])

        # ---- the append: the blocks of ``wb`` ring indices the row's
        # n tokens touch, merged in fast memory and stored whole
        first = _split(w, wb)
        inside = w - first * wb
        token = jax.lax.broadcasted_iota(i32, (wb, T), 1)
        at = jax.lax.broadcasted_iota(i32, (wb, 1), 0)

        def block(j):
            """(whether the row writes into its j-th block, the block's
            first ring index)."""
            b = first + j
            b = jnp.where(b >= L // wb, b - L // wb, b)
            return j * wb - inside < n, pl.multiple_of(b * wb, wb)

        def copies(j, h, base):
            head = g * hv + h
            return [pltpu.make_async_copy(
                scr.at[j, h], out.at[slot, head, pl.ds(base, wb), :],
                sem.at[c, j, h])
                for c, (scr, out) in enumerate(((wk_scr, ko_ref),
                                                (wv_scr, vo_ref)))]

        blocks = [block(j) for j in range(n_blocks)]
        for j, (touched, base) in enumerate(blocks):
            @pl.when(touched)
            def _(j=j, base=base):
                # ring index base + r takes the row's token ``d``
                d = base + at - w
                d = jnp.where(d < 0, d + L, d)                  # [wb, 1]
                hit = d < n
                place = (d == token).astype(dtype)              # [wb, T]
                for h in range(hv):
                    cols = slice(h * D, (h + 1) * D)
                    for new, ring, scr in ((kn_ref, rk_ref, wk_scr),
                                           (vn_ref, rv_ref, wv_scr)):
                        if T == 1:
                            placed = jnp.broadcast_to(new[0, :, cols],
                                                      (wb, D))
                        else:
                            placed = _dot(place, new[0, :, cols],
                                          _NN).astype(dtype)
                        scr[j, h] = jnp.where(
                            hit, placed, ring[0, h, pl.ds(base, wb), :])
                    for copy in copies(j, h, base):
                        copy.start()

        # ---- the attention. A tile row's token and, where a visit
        # holds several KV heads (a decode step), its query group
        at_row = jax.lax.broadcasted_iota(i32, (R, 1), 0)
        if T == 1:
            q = q_ref[0, 0]                          # [H, D]: a row a head
            t_row = jnp.zeros_like(at_row)
            own = [_split(at_row, rep) == h for h in range(hv)]
        else:
            # a token's heads lie side by side: the group's rep x T rows,
            # head by head (whole lane tiles off, whole sublane tiles on)
            q = jnp.concatenate([q_ref[0, :, h * D:(h + 1) * D]
                                 for h in range(rep)], axis=0)
            t_row = at_row - _split(at_row, T) * T
            own = [None]
        floor = pos + t_row - window            # a query sees what is above

        def grouped(per_head):
            """Each tile row's own KV head's entry of ``per_head``."""
            out = per_head[0]
            for h in range(1, hv):
                out = jnp.where(own[h], per_head[h], out)
            return out

        # the new keys first: every query sees its own, so the running
        # maximum is a real score from the start
        if T == 1:
            k_new = grouped([kn_ref[0, :, h * D:(h + 1) * D].astype(f32)
                             for h in range(hv)])                # [R, D]
            v_new = grouped([vn_ref[0, :, h * D:(h + 1) * D].astype(f32)
                             for h in range(hv)])
            m = jnp.sum(q.astype(f32) * k_new, axis=-1,
                        keepdims=True) * scale                   # [R, 1]
            l = jnp.ones_like(m)
            acc = v_new
        else:
            t_key = jax.lax.broadcasted_iota(i32, (1, T), 1)
            k_at = jnp.where(t_key < n, pos + t_key, -_NEVER)
            seen = (k_at <= pos + t_row) & (k_at > floor)
            s = jnp.where(seen, _dot(q, kn_ref[0], _NT) * scale, _NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            acc = _dot(p.astype(dtype), vn_ref[0], _NN)

        # then the slab as it was before the call, a block at a time:
        # index r held position pos - w + r, a ring less from w on
        for c0, c1 in spans:
            r = c0 + jax.lax.broadcasted_iota(i32, (1, c1 - c0), 1)
            k_at = pos - w + r - jnp.where(r >= w, L, 0)
            k_at = jnp.where(k_at >= 0, k_at, _NEVER)
            s = grouped([_dot(q, rk_ref[0, h, c0:c1, :], _NT)
                         for h in range(hv)]) * scale
            s = jnp.where(k_at > floor, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            p = p.astype(dtype)
            acc = acc * alpha + sum(
                _dot(p if hv == 1 else jnp.where(own[h], p, 0),
                     rv_ref[0, h, c0:c1, :], _NN) for h in range(hv))
            m = m_new
        y = (acc / l).astype(y_ref.dtype)
        if T == 1:
            y_ref[0, 0] = y
        else:
            for h in range(rep):
                y_ref[0, :, h * D:(h + 1) * D] = y[h * T:(h + 1) * T]

        for j, (touched, base) in enumerate(blocks):
            @pl.when(touched)
            def _(j=j, base=base):
                for h in range(hv):
                    for copy in copies(j, h, base):
                        copy.wait()


def _order(live):
    """The order in which a call's B visits take its rows: the live
    rows first, in their order ([B] int32), and how many they are."""
    B = live.shape[0]
    i32 = jnp.int32
    rows = jnp.arange(B, dtype=i32)
    n = jnp.sum(live, dtype=i32)
    place = jnp.where(live, jnp.cumsum(live, dtype=i32) - 1,
                      n + jnp.cumsum(~live, dtype=i32) - 1)
    order = jnp.sum(jnp.where(place[None, :] == rows[:, None],
                              rows[None, :], 0), axis=1, dtype=i32)
    return order, n


@functools.partial(jax.jit, static_argnames=("window", "key_block",
                                             "interpret"))
def ring_window_kernel(q, k, v, ring_k, ring_v, slots, pos, valid, *,
                       window: int, key_block: Optional[int] = None,
                       interpret: bool = False):
    """``ring_window_attention`` as the Pallas kernel, whatever
    ``applies`` says (``interpret``: off the chip, for a test);
    ``key_block``: the keys a fold spans where not ``key_spans``'s
    (tools/ring_window_bench.py and the tests). The rings are aliased
    to the new rings: donate them (or carry them through a loop) and
    they are updated where they lie.

    One jitted function: the layers of a step program that call it
    with equal shapes share one trace and one lowering."""
    B, T, H, D = q.shape
    S, KH, L, _ = ring_k.shape
    rep = H // KH
    wb = write_rows(L, T)
    assert (k.shape == v.shape == (B, T, KH, D) and H % KH == 0 and wb
            and T <= L - window + 1), (
                q.shape, k.shape, v.shape, ring_k.shape, window)
    i32 = jnp.int32
    pos = pos.astype(i32)
    n = jnp.sum(valid, axis=1, dtype=i32)
    slot = jnp.arange(B, dtype=i32) if slots is None else slots.astype(i32)
    live = (n > 0) & (slot >= 0) & (slot < S)
    order, n_live = _order(live)
    # a decode step's visit is a row (every KV head's slab, H query
    # rows); a chunk's a (row, KV head): rep x T query rows, a token's
    # heads side by side
    G, hv = (1, KH) if T == 1 else (KH, 1)
    spans = key_spans(H * T // G, L, key_block)
    # the queries and the result as the model has them, a group's heads
    # a block: [B, 1, H, D] whole, [B, T, H x D] by lane tiles
    tiles = (B, 1, H, D) if T == 1 else (B, T, H * D)
    tile_block = (1, 1, H, D) if T == 1 else (1, T, rep * D)
    n_blocks = -(-(T - 1) // wb) + 1

    def at_live(i, g, order, live):
        """(the i-th live row or, past them, the last one; its block
        ``g`` or, past them, the last one fetched)."""
        last = jnp.maximum(live[0] - 1, 0)
        return (order[jnp.minimum(i, last)],
                jnp.where(i < live[0], g, G - 1))

    def new(i, g, order, slot, pos, w, n, live):
        row, g = at_live(i, g, order, live)
        return row, 0, g

    minor = (0,) if T == 1 else ()      # a decode step's tile is 4-D

    def slab(i, g, order, slot, pos, w, n, live):
        row, g = at_live(i, g, order, live)
        return slot[row], g, 0, 0

    itemsize, rows = ring_k.dtype.itemsize, H * T // G
    widest = max(c1 - c0 for c0, c1 in spans)
    y, ring_k, ring_v = pl.pallas_call(
        functools.partial(_ring_kernel, scale=1.0 / np.sqrt(D),
                          window=window, rep=rep, spans=tuple(spans)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, G),
            in_specs=[pl.BlockSpec(tile_block, lambda *a: new(*a) + minor),
                      pl.BlockSpec((1, T, hv * D), new),
                      pl.BlockSpec((1, T, hv * D), new),
                      pl.BlockSpec((1, hv, L, D), slab),
                      pl.BlockSpec((1, hv, L, D), slab)],
            out_specs=[
                pl.BlockSpec(tile_block, lambda i, g, order, *_:
                             (order[i], 0, g) + minor),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM)],
            scratch_shapes=[
                pltpu.VMEM((n_blocks, hv, wb, D), ring_k.dtype),
                pltpu.VMEM((n_blocks, hv, wb, D), ring_v.dtype),
                pltpu.SemaphoreType.DMA((2, n_blocks, hv))]),
        out_shape=[jax.ShapeDtypeStruct(tiles, q.dtype),
                   # said to be in HBM: left to choose, the compiler
                   # moves a ring that fits its fast memory there whole
                   # and back around the call (44 MB each way, read off
                   # the prefill program compiled for a v5e)
                   pltpu.HBM(ring_k.shape, ring_k.dtype),
                   pltpu.HBM(ring_v.shape, ring_v.dtype)],
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            # the live rows first, in order: a repeated block index is
            # a fetch skipped
            dimension_semantics=("arbitrary", "arbitrary"),
            # the slabs, the tiles and the new rows double-buffered, a
            # fold's scores (float32, their exponentials, those in the
            # ring's type), the accumulator, and room for what the
            # compiler spills
            vmem_limit_bytes=(4 * hv * L * D * itemsize
                              + 6 * rows * D * itemsize
                              + 16 * rows * widest + 16 * rows * D
                              + (8 << 20))),
        interpret=interpret, name="ring_window",
    )(order, jnp.clip(slot, 0, S - 1), pos, pos % L,
      jnp.where(live, n, 0), n_live[None], q.reshape(tiles),
      k.reshape(B, T, KH * D), v.reshape(B, T, KH * D), ring_k, ring_v)
    return y.reshape(B, T, H, D), ring_k, ring_v


def ring_pair(q, k, v, ring_k, ring_v, slots, pos, valid, window: int):
    """``ring_window_attention`` as the ``jax.numpy`` pair,
    ops/paged_attention.py ``ring_append`` + ``ring_attention`` over
    the rows' rings taken by slot (zeros for a row that names none):
    what runs off the chip, and the form the tests and
    tools/ring_window_bench.py hold the kernel to."""
    with jax.named_scope("ring_append"):
        ring_k, ring_v = ring_append(ring_k, ring_v, slots, pos, k, v,
                                     valid)
    own_k, own_v = ((ring if slots is None else
                     ring.at[slots].get(mode="fill", fill_value=0))
                    for ring in (ring_k, ring_v))
    return (ring_attention(q, own_k, own_v, pos, valid, window), ring_k,
            ring_v)


def ring_window_attention(q, k, v, ring_k, ring_v, slots, pos, valid,
                          window: int):
    """Append the rows' new ``k``, ``v`` [B, T, KH, D] to their rings
    ``ring_k``/``ring_v`` [n_slots, KH, L, D] (row b's is slot
    ``slots[b]``'s, its own where ``slots`` is None) and attend ``q``
    [B, T, H, D] over them: token t of row b, at position ``pos[b] +
    t``, goes to ring index ``(pos[b] + t) mod L`` and sees the keys at
    ``i - window < j <= i``. ``valid`` [B, T]: a row's real tokens, a
    prefix of it; a row without one, or whose slot is out of range,
    writes nothing and its ``y`` means nothing. Returns (y [B, T, H, D]
    in ``q``'s type, the new rings).

    On one TPU the Pallas kernel above; everywhere else
    ``ring_pair``."""
    form = (functools.partial(ring_window_kernel, window=window)
            if applies(q, k, v, ring_k, ring_v, window)
            else functools.partial(ring_pair, window=window))
    return form(q, k, v, ring_k, ring_v, slots, pos, valid)
