"""Grouped matmul: rows sorted by group, each group through its own
matrix. ``out[i] = rows[i] @ w[g(i)]`` where group ``g`` holds the
``group_sizes[g]`` rows after those of the groups before it; rows past
the last group belong to none and come back UNDEFINED (the caller masks
them). Shapes are static, the group sizes are values: one executable
serves every routing.

On a TPU, outside any multi-device mesh, it is the Pallas grouped
matmul JAX ships (``megablox.gmm``): the grid walks (row tile, group)
visits, a group with no row is never visited and its matrix never
leaves HBM, so a decode step streams only the experts its rows chose
(PERF.md section 6, PR 28 has the chip's readings against
``jax.lax.ragged_dot``, which the TPU compiler expands over every
group when the rows are few). Its tiles follow the matrices they walk
(``tile_plan``). Everywhere else (the CPU; under a mesh, where GSPMD
cannot partition a Mosaic kernel) it is ``jax.lax.ragged_dot``. What
decides is the backend and the ambient mesh, never a flag.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# What a kernel's blocks may take of VMEM: the 16 MiB a Mosaic kernel
# gets by default (``gmm`` gives no way to raise it), less room for
# what the compiler keeps beside them.
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


def grouped_matmul_kernel(rows, w, group_sizes, *, interpret=False):
    """The Pallas form under ``tile_plan``; ``interpret`` is for a test
    off the chip."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = rows.shape
    tiling = tile_plan(m, k, w.shape[-1], rows.dtype.itemsize)
    pad = -m % tiling[0]
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, group_sizes, preferred_element_type=rows.dtype,
              tiling=tiling, interpret=interpret)
    return out[:m] if pad else out
