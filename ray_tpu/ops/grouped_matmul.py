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
group when the rows are few). Everywhere else (the CPU; under a mesh,
where GSPMD cannot partition a Mosaic kernel) it is
``jax.lax.ragged_dot``. What decides is the backend and the ambient
mesh, never a flag.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Row tile of the kernel: a visit multiplies 128 rows whatever the
# group holds of them. Column tiles keep one (k, n) block of a matrix
# at 4 MiB in bf16, double-buffered well inside the 16 MiB of VMEM a
# kernel may use by default.
_TILE_M, _TILE_K, _TILE_N = 128, 2048, 1024


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


def grouped_matmul(rows, w, group_sizes):
    """rows [M, K], w [G, K, N], group_sizes [G] int32 -> [M, N] in
    rows' dtype (float32 accumulation)."""
    if not _use_kernel():
        return jax.lax.ragged_dot(rows, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = rows.shape
    n = w.shape[-1]
    pad = -m % _TILE_M
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, group_sizes, preferred_element_type=rows.dtype,
              tiling=(_TILE_M, min(k, _TILE_K), min(n, _TILE_N)))
    return out[:m] if pad else out
