"""Grouped matmul: rows sorted by group, each group through its own
matrix. ``out[i] = rows[i] @ w[g(i)]`` where group ``g`` holds the
``group_sizes[g]`` rows after those of the groups before it; rows past
the last group belong to none and come back UNDEFINED (the caller masks
them). Shapes are static, the group sizes are values: one executable
serves every routing.

On a TPU, outside any multi-device mesh, it is a Pallas kernel
(``grouped_matmul_kernel``). Its grid is the one of the grouped matmul
JAX ships (``megablox.gmm``: the same metadata, the same masked store,
float32 accumulation): it walks (row tile, group) visits, a group with
no row is never visited and its matrix never leaves HBM, so a decode
step streams only the experts its rows chose (PERF.md section 6, PR 28
has the chip's readings against ``jax.lax.ragged_dot``, which the TPU
compiler expands over every group when the rows are few). Its tiles
follow the matrices they walk (``tile_plan``).

Which body runs follows from the plan. A plan that DIVIDES the matrix
in column tiles of whole lanes (every matrix a serving cell presents)
runs the repo's own body (``_group_keyed``, PR 57): the matrices stay
in HBM and the body copies a block into one of two buffers itself,
keyed by the BLOCK and not by the grid step. Where a visit is one block (the contraction whole), the
block is the group's: at a group's FIRST visit the body waits for that
block, already on its way, and starts the next visited group's; every
later visit of the group (a prefill call gives a held expert ~128 rows,
which lie over two row tiles) multiplies out of the buffer while that
copy runs. The shipped kernel fetches by grid step, so its second visit
had nothing to fetch and its first nothing to hide behind: fetch and
multiply alternated (PERF.md section 6, PR 57). A group with one visit
(every decode call) degenerates to the shipped schedule: fetch the next
while this one multiplies. Where a visit walks several contraction
blocks, every step has a block of its own and the look-ahead is the
shipped one, a block ahead in the shipped order, through the same body.
A plan that leaves a REMAINDER (``tile_plan``'s fallback, for a shape
no tile divides inside the budget) or whose column tile is not whole
lanes of 128 (a matrix narrower than that taken whole; the chip's
compiler refuses such a buffer's halves) stays on ``megablox.gmm``,
whose block specs mask and pad where ours would copy past the matrix.
``visits`` counts the grid's visits; less the groups that have a row,
it is how often the look-ahead by group engages.

Everywhere else (the CPU; under a mesh, where GSPMD cannot partition a
Mosaic kernel) the op is ``jax.lax.ragged_dot``. What decides is the
backend, the ambient mesh and the plan, never a flag.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# What a kernel's blocks may take of VMEM: the 16 MiB a Mosaic kernel
# gets by default (the shipped ``gmm`` gives no way to raise it, and
# both bodies run under one plan), less room for what the compiler
# keeps beside them.
_VMEM_BUDGET = 15 << 20
_TILE_M = 128
# The plan of a shape whose dimensions no tile divides inside the
# budget: the kernel masks a remainder itself.
_FALLBACK_K, _FALLBACK_N = 2048, 1024


def on_one_tpu() -> bool:
    """The backend is a TPU and no multi-device mesh is ambient: one
    rule for every Mosaic kernel that has an XLA form. Each such module
    asks it under a name of its own, which is what a test steers."""
    if jax.default_backend() != "tpu":
        return False
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.empty or mesh.size == 1


def _use_kernel() -> bool:
    return on_one_tpu()


def vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What a plan's blocks take of VMEM: the row, matrix and output
    blocks double-buffered, the float32 accumulator, and the product
    and the masked store beside it as the compiler keeps them."""
    blocks = 2 * itemsize * (tm * tk + tk * tn + tm * tn)
    return blocks + 3 * 4 * tm * tn


def dividing_tiles(x: int) -> list[int]:
    """The tiles that walk a dimension of ``x`` with no remainder: ``x``
    whole and every multiple of 128 lanes that divides it, widest
    first."""
    return [x] + [t for t in range(x // 128 * 128, 0, -128)
                  if t < x and x % t == 0]


def tile_plan(m: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """(tm, tk, tn) for ``[m, k] @ [G, k, n]``. ``tk`` divides ``k`` and
    ``tn`` divides ``n``: the kernel masks a contraction's remainder on
    the vector unit at every visit, and a narrow last column tile costs
    a whole one's pass. Of the plans whose blocks fit the budget, the
    one with the fewest grid steps a visit; among those the contraction
    whole (one step a visit: the row block and a group's matrix are
    reused across consecutive visits, no accumulator round), then the
    wider column tile. ``tm`` is 128 whatever ``m``: no other row tile
    was better in both a decode and a prefill call (PERF.md section 6,
    PR 44)."""
    del m
    tm = _TILE_M
    fits = [(tk, tn) for tk in dividing_tiles(k) for tn in dividing_tiles(n)
            if vmem_bytes(tm, tk, tn, itemsize) <= _VMEM_BUDGET]
    if not fits:
        return tm, min(k, _FALLBACK_K), min(n, _FALLBACK_N)
    tk, tn = min(fits, key=lambda p: ((k // p[0]) * (n // p[1]),
                                      k // p[0], n // p[1]))
    return tm, tk, tn


def grouped_matmul(rows, w, group_sizes):
    """rows [M, K], w [G, K, N], group_sizes [G] int32 -> [M, N] in
    rows' dtype (float32 accumulation)."""
    if not _use_kernel():
        return jax.lax.ragged_dot(rows, w, group_sizes)
    return grouped_matmul_kernel(rows, w, group_sizes)


def _tiles_spanned(start, end, tm: int):
    """The row tiles of ``tm`` that rows ``start`` .. ``end`` lie over
    (``end`` > ``start``)."""
    return (end + tm - 1) // tm - start // tm


def visits(group_sizes, m: int):
    """The (row tile, group) visits the kernel's grid makes over
    ``group_sizes`` [G] of a call of ``m`` rows, int32: for each group
    that has a row, the row tiles it spans (``tile_plan``'s row tile,
    128 whatever ``m``). Over the groups that have a row it is the
    count of matrix FETCHES plus the visits that multiply out of a
    matrix already there."""
    del m
    ends = jnp.cumsum(group_sizes)
    spans = _tiles_spanned(ends - group_sizes, ends, _TILE_M)
    return jnp.sum(jnp.where(group_sizes > 0, spans, 0), dtype=jnp.int32)


def grouped_matmul_kernel(rows, w, group_sizes, *, interpret=False):
    """The Pallas form under ``tile_plan``; ``interpret`` is for a test
    off the chip."""
    m, k = rows.shape
    n = w.shape[-1]
    tm, tk, tn = tiling = tile_plan(m, k, n, rows.dtype.itemsize)
    pad = -m % tm
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    if k % tk or n % tn or tn % 128:
        # a plan whose tiles leave a remainder, or a matrix narrower
        # than whole lanes: the shipped kernel's block specs mask and
        # pad, ours copies whole blocks of whole lanes out of HBM
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        out = gmm(rows, w, group_sizes, preferred_element_type=rows.dtype,
                  tiling=tiling, interpret=interpret)
    else:
        out = _group_keyed(rows, w, group_sizes, tiling, interpret)
    return out[:m] if pad else out


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def _group_keyed(rows, w, group_sizes, tiling, interpret):
    """``rows`` [m, k] (m in whole row tiles) through ``w`` [G, k, n]
    under a plan that divides k and n: the shipped ``gmm``'s grid,
    metadata, masked store and float32 accumulation; the matrix blocks
    reach VMEM by copies this body starts itself, one block ahead of
    the block it multiplies, and a block changes only where the walk
    leaves it (the module docstring has the schedule). Jitted, as the
    shipped one is: a step program calls it three times a layer, and
    the calls of one shape share one trace and one lowered function
    (24 traces of the body were 16 s of ``.mellum-sat``'s set-up)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    m, k = rows.shape
    G, _, n = w.shape
    tm, tk, tn = tiling
    tiles_k, tiles_n = k // tk, n // tn
    (offsets, group_ids, tile_ids), n_visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=G, visit_empty_groups=False)
    i32 = jnp.int32

    def kernel(offsets, group_ids, tile_ids, n_visits, rows_ref, w_hbm,
               out_ref, w_buf, sem, slot_ref, *acc):
        n_i, v, k_i = (pl.program_id(a) for a in range(3))
        g = group_ids[v]

        def copy(g, k_i, n_i, slot):
            return pltpu.make_async_copy(
                w_hbm.at[g, pl.ds(pl.multiple_of(k_i * tk, tk), tk),
                         pl.ds(pl.multiple_of(n_i * tn, tn), tn)],
                w_buf.at[slot], sem.at[slot])

        first = (n_i == 0) & (v == 0) & (k_i == 0)
        if tiles_k > 1:
            # the matrix is walked in contraction blocks: every step
            # has a block of its own, the next step's is the next
            fresh = True
            wrap_k = k_i == tiles_k - 1
            nk = jnp.where(wrap_k, 0, k_i + 1)
            nv = jnp.where(wrap_k, v + 1, v)
        else:
            # the visit's block is its group's (column tile n_i): it
            # changes where the group does, and the next is that of the
            # group after this one's last row tile
            fresh = (v == 0) | (g != group_ids[jnp.maximum(v - 1, 0)])
            nk = i32(0)
            nv = v + _tiles_spanned(offsets[g], offsets[g + 1], tm)
        wrap_v = nv >= n_visits[0]
        nn = jnp.where(wrap_v, n_i + 1, n_i)
        nv = jnp.where(wrap_v, 0, nv)

        @pl.when(fresh)
        def _():
            slot = jnp.where(first, 0, 1 - slot_ref[0])
            slot_ref[0] = slot

            @pl.when(first)
            def _():            # the call's first block: nothing hides it
                copy(g, k_i, n_i, slot).start()

            @pl.when(nn < tiles_n)
            def _():
                copy(group_ids[nv], nk, nn, 1 - slot).start()
            copy(g, k_i, n_i, slot).wait()

        prod = jax.lax.dot_general(
            rows_ref[...], w_buf[slot_ref[0]].astype(rows_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        def store(acc):
            # rows of the tile that are another group's keep what is
            # there
            at = tile_ids[v] * tm + jax.lax.broadcasted_iota(
                i32, (tm, tn), 0)
            mine = (at >= offsets[g]) & (at < offsets[g + 1])
            out_ref[...] = jnp.where(
                mine, acc, out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

        if tiles_k == 1:
            store(prod)
        else:
            acc_ref, = acc

            @pl.when(k_i == 0)
            def _():
                acc_ref[...] = prod

            @pl.when(k_i > 0)
            def _():
                acc_ref[...] += prod

            @pl.when(k_i == tiles_k - 1)
            def _():
                store(acc_ref[...])

    itemsize = rows.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles_n, n_visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, offsets,
                             group_ids, tile_ids, n_visits:
                             (tile_ids[v], k_i)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, v, k_i, offsets, group_ids,
                tile_ids, n_visits: (tile_ids[v], n_i)),
            scratch_shapes=[
                pltpu.VMEM((2, tk, tn), w.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), i32)] + (
                    [pltpu.VMEM((tm, tn), jnp.float32)]
                    if tiles_k > 1 else [])),
        compiler_params=pltpu.CompilerParams(
            # one walk, in order: a block's copy is started a block
            # ahead, across column tiles too
            dimension_semantics=("arbitrary",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=itemsize * (m * k * tiles_n + m * n
                                       + k * n * group_ids.size)),
        interpret=interpret, name="grouped_matmul",
    )(offsets, group_ids, tile_ids, n_visits[None], rows, w)
