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

Both are plain ``jax.numpy``: what the CPU, a mesh and, today, the chip
run (ROADMAP has the kernel that walks a chunk in fast memory).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


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
    from); valid [B, T] bool. Returns (y [B, T, C] float32, the state
    after each row's last real position)."""
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
