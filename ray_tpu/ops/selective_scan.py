"""A selective state-space layer's recurrence (Mamba-1, arXiv:2312.00752):
the second RULE behind ``RecurrentState``'s shape, beside the delta rule
of ops/linear_attention.py.

A slot keeps ``h`` [N, C] float32: N states on the sublanes, C channels
on the lanes, as the chip tiles a float32 array (N = 16 is two sublane
tiles, C = 5,120 forty lane tiles: nothing is padded). One token moves
it by

    h[n, c] <- exp(delta[c] A[n, c]) h[n, c] + delta[c] u[c] B[n]
    y[c]     = sum_n h[n, c] C[n] + D[c] u[c]

``A`` [N, C] (negative), ``D`` [C] the layer's own; ``delta`` [C] (after
its softplus), ``B`` and ``C`` [N] the token's. The transition is
DIAGONAL and differs for every channel, state and token, so a chunk of
positions is no matrix product (the delta rule's chunked form does not
apply): the work is elementwise, 16 x C multiply-adds a token on the
vector unit, and a decode step is its bytes: each rider's state read
once and written once.

Two forms of the same mathematics, float32 throughout:

- ``ssm_step``: one token a row (a decode step), the state stepped as it
  is stored; a row that starts a request (``fresh``) begins from zeros
  in the one pass over the state, a row that carries none (``valid``
  false) leaves it as it was;
- ``ssm_chunked``: T positions a row (a prefill chunk, the cache-less
  forward), a ``lax.scan`` over the positions with the rows' states as
  the carry; positions that are not real leave the state as it was.

Both are plain ``jax.numpy``: what the CPU and a mesh run. On one TPU a
chunk of whole sublane tiles of positions over whole blocks of channels
is walked by ``selective_scan``, a Pallas kernel that keeps the state in
registers for the whole chunk where the ``lax.scan`` sends it through
HBM and back every position. ``serves`` decides from shapes, types,
backend and mesh alone, and ``ssm_chunked`` asks it of its arguments.

The kernel: a program is one (row, block of ``_BLOCK`` channels). Each
state's slice of the block is ONE float32 register (eight sublanes of
128 channels) carried through the walk, so the sixteen states are
sixteen independent chains of multiply-adds and the read-out is their
running sum: no work crosses a sublane and the state touches no memory
between a program's first position and its last. ``u``, ``delta`` and
``y`` come and go as ``[T, _BLOCK]`` blocks of the arrays as the model
has them; eight positions at a time are turned to ``[8, 8, 128]`` (a
position's channels one register) in VMEM scratch, walked by a loop
whose body is ONE position (a short trace: nine layers of three widths
are built at every start), and turned back. A token's ``B`` and ``C``
are scalars in SMEM. The same products and sums as ``_advance`` in
float32; only the sum over the states associates in its own order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.grouped_matmul import on_one_tpu as _on_one_tpu

# Channels a program: eight sublanes of 128 lanes, so that a state's
# slice of the block is one float32 register.
_BLOCK = 1024
# Positions between two stores of ``y``: a float32 tile's sublanes.
_GROUP = 8
# The longest chunk whose blocks (u, delta, y of [T, _BLOCK], twice for
# the pipeline) stay well inside a kernel's 16 MiB of VMEM.
_MAX_POSITIONS = 512


def _advance(h, u, d, b, c, A, D):
    """One position: (the state after it, its read-out). h [B, N, C];
    u, d [B, C]; b, c [B, N]; A [N, C]; D [C]; all float32."""
    new = jnp.exp(d[:, None, :] * A[None]) * h \
        + (d * u)[:, None, :] * b[:, :, None]
    return new, jnp.sum(new * c[:, :, None], axis=1) + D * u


def ssm_step(u, delta, A, Bm, Cm, D, state, valid, fresh=None):
    """One token a row. u, delta [B, C]; A [N, C]; Bm, Cm [B, N]; D [C];
    state [B, N, C] float32; valid [B] bool; fresh [B] bool or None.
    Returns (y [B, C] float32, the new state)."""
    f32 = jnp.float32
    if fresh is not None:
        state = jnp.where(fresh[:, None, None], 0.0, state)
    new, y = _advance(state, u.astype(f32), delta.astype(f32),
                      Bm.astype(f32), Cm.astype(f32), A.astype(f32),
                      D.astype(f32))
    return y, jnp.where(valid[:, None, None], new, state)


def ssm_chunked(u, delta, A, Bm, Cm, D, state, valid):
    """T positions a row, in order. u, delta [B, T, C]; A [N, C]; Bm, Cm
    [B, T, N]; D [C]; state [B, N, C] float32 (what the rows begin
    from); valid [B, T] bool, a row's real positions its first ones.
    Returns (y [B, T, C] float32, the state after each row's last real
    position). The kernel where ``serves`` says so, else the
    ``lax.scan``."""
    if serves(u.shape[1], state):
        return selective_scan(u, delta, A, Bm, Cm, D, state, valid)
    f32 = jnp.float32
    A, D = A.astype(f32), D.astype(f32)

    def step(h, xs):
        u_t, d_t, b_t, c_t, ok = xs
        new, y = _advance(h, u_t, d_t, b_t, c_t, A, D)
        return jnp.where(ok[:, None, None], new, h), y

    def by_time(x):
        return jnp.moveaxis(x.astype(f32), 1, 0)

    state, y = jax.lax.scan(
        step, state.astype(f32),
        (by_time(u), by_time(delta), by_time(Bm), by_time(Cm),
         jnp.moveaxis(valid, 1, 0)))
    return jnp.moveaxis(y, 0, 1), state


def serves(T: int, state) -> bool:
    """Whether the kernel walks a chunk of ``T`` positions from
    ``state`` [rows, N, C] (only its shape and type are read: the rows'
    states as ``ssm_chunked`` is handed them, or the slots' as the pool
    keeps them): a float32 state of this rule's two axes a row, whole
    blocks of channels, whole sublane tiles of positions that fit the
    kernel's VMEM, and a TPU outside any multi-device mesh. One
    position is ``ssm_step``'s. ``ssm_chunked`` asks it of its
    arguments, and the engine of the same shapes for its
    ``prefill_scan_kernel_positions``."""
    return (len(state.shape) == 3 and state.dtype == jnp.float32
            and state.shape[2] % _BLOCK == 0
            and 1 < T <= _MAX_POSITIONS and T % _GROUP == 0
            and _on_one_tpu())


def _scan_kernel(n_real_ref, b_ref, c_ref, u_ref, d_ref, A_ref, D_ref,
                 h0_ref, y_ref, h_ref, u_turned, d_turned, y_turned):
    """One row's chunk over one block of channels. n_real_ref [B] int32
    (SMEM); b_ref, c_ref [1, 1, T x N] float32 (SMEM, the row's); u_ref,
    d_ref, y_ref [1, T, _BLOCK]; A_ref [N, 8, 128]; D_ref [8, 128];
    h0_ref, h_ref [1, N, 8, 128]; the scratch [8, 8, 128] float32: a
    group's positions, the channels of each one register."""
    f32 = jnp.float32
    T, N = u_ref.shape[1], A_ref.shape[0]
    n_real = n_real_ref[pl.program_id(0)]

    def group(g, hs):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        at = pl.ds(t0, _GROUP)
        u_turned[...] = u_ref[0, at, :].astype(f32).reshape(u_turned.shape)
        d_turned[...] = d_ref[0, at, :].astype(f32).reshape(d_turned.shape)

        def position(i, hs):
            u, d = u_turned[i], d_turned[i]
            ok, first = t0 + i < n_real, (t0 + i) * N
            du, y, new = d * u, D_ref[...] * u, []
            for n in range(N):
                h = jnp.exp(d * A_ref[n]) * hs[n] \
                    + du * b_ref[0, 0, first + n]
                y = y + h * c_ref[0, 0, first + n]
                new.append(jnp.where(ok, h, hs[n]))
            y_turned[i] = y
            return tuple(new)

        # traced once, emitted eight times: a fifth faster than the loop
        # (0.231 | 0.288 ms a layer-call, PERF.md section 6, PR 62)
        hs = jax.lax.fori_loop(0, _GROUP, position, hs, unroll=True)
        y_ref[0, at, :] = y_turned[...].reshape(_GROUP, -1)
        return hs

    hs = jax.lax.fori_loop(0, T // _GROUP, group,
                           tuple(h0_ref[0, n] for n in range(N)))
    for n in range(N):
        h_ref[0, n] = hs[n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(u, delta, A, Bm, Cm, D, state, valid, *,
                   interpret: bool = False):
    """``ssm_chunked``'s contract by the kernel, for the shapes
    ``serves`` says (``interpret``: off the chip, for a test). Jitted:
    a model's state-space layers share one trace of the kernel a
    shape."""
    f32 = jnp.float32
    (B, T, C), N = u.shape, A.shape[0]
    tiles = (C // 128, 128)
    block = (_BLOCK // 128, 128)

    chunk = pl.BlockSpec((1, T, _BLOCK), lambda b, c, *_: (b, 0, c))
    row_scalars = pl.BlockSpec((1, 1, T * N), lambda b, c, *_: (b, 0, 0),
                               memory_space=pltpu.SMEM)
    states = pl.BlockSpec((1, N) + block, lambda b, c, *_: (b, 0, c, 0))
    y, state = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, C // _BLOCK),
            in_specs=[row_scalars, row_scalars, chunk, chunk,
                      pl.BlockSpec((N,) + block,
                                   lambda b, c, *_: (0, c, 0)),
                      pl.BlockSpec(block, lambda b, c, *_: (c, 0)),
                      states],
            out_specs=[chunk, states],
            scratch_shapes=[pltpu.VMEM((_GROUP,) + block, f32)] * 3),
        out_shape=[jax.ShapeDtypeStruct((B, T, C), f32),
                   jax.ShapeDtypeStruct((B, N) + tiles, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="selective_scan",
    )(jnp.sum(valid, axis=1, dtype=jnp.int32),
      Bm.astype(f32).reshape(B, 1, -1), Cm.astype(f32).reshape(B, 1, -1),
      u, delta,
      A.astype(f32).reshape((N,) + tiles), D.astype(f32).reshape(tiles),
      state.astype(f32).reshape((B, N) + tiles))
    return y, state.reshape(B, N, C)
