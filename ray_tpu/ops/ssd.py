"""A state-space layer's recurrence under ONE SCALAR DECAY A HEAD
(Mamba-2's SSD, arXiv:2405.21060): the third RULE behind
``RecurrentState``'s shape, beside the delta rule of
ops/linear_attention.py and Mamba-1's of ops/selective_scan.py.

A slot keeps ``S`` [H, P, N] float32: H heads, a head's P channels on
the sublanes, N states on the lanes (at the served 128 x 64 x 128 a head
is eight sublane tiles of one lane tile: nothing is padded; with P minor
a head's 64 channels would be half a lane tile and the chip would keep
twice the bytes). One token moves head h by

    S_h <- a_h S_h + dt_h x_h (outer) B,    a_h = exp(dt_h A_h)
    y_h  = S_h C + D_h x_h

``A`` [H] (negative) and ``D`` [H] the layer's own; ``dt`` [H] (after
its softplus), ``x`` [H, P], ``B`` and ``C`` [N] the token's (one group:
every head reads the same B and C). The transition is a SCALAR a head,
so, unlike Mamba-1's (a decay a channel and a state, which no matrix
product expresses), a chunk of L positions is solved by matrix products:
with ``cum_t = sum_{s<=t} log a_s`` a head,

    M[t, s] = (C_t . B_s) exp(cum_t - cum_s)   for s <= t, else 0
    Y       = M (dt x) + exp(cum_t) C S_in + D x
    S_out   = exp(cum_L) S_in + sum_s exp(cum_L - cum_s) dt_s x_s (outer) B_s

``C B^T`` is [L, L] ONCE a row; the mask ``exp(cum_t - cum_s)`` and the
other three products are a head's. Every exponent is a sum of log decays
over positions AFTER s, so nothing overflows whatever the chunk.

Two forms of the same mathematics, float32 throughout (the products at
``HIGHEST``, as the delta rule's chunks):

- ``ssd_step``: one token a row (a decode step), ``ssm_step``'s
  contract: a row that starts a request (``fresh``) begins from zeros in
  the one pass over the state, a row that carries none (``valid`` false)
  leaves it as it was;
- ``ssd_chunked``: T positions a row (a prefill chunk, the cache-less
  forward) by the matrix form in chunks of ``chunk`` positions, the
  state crossing chunks (a ``lax.scan`` over the CHUNKS where there are
  several; one chunk is no loop at all) and calls. A position that is
  not real gets ``dt = 0``: its decay is 1 and it writes nothing, so the
  state passes it unchanged (real positions are a row's first ones).

Both are plain ``jax.numpy`` on every backend: a decode step is one pass
over the state, a chunk four matrix products; what a kernel would buy
either is for a trace to say (PERF.md section 7).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def ssd_step(x, dt, A, Bm, Cm, D, state, valid, fresh=None):
    """One token a row. x [B, H, P]; dt [B, H]; A, D [H]; Bm, Cm [B, N];
    state [B, H, P, N] float32; valid [B] bool; fresh [B] bool or None.
    Returns (y [B, H, P] float32, the new state)."""
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    if fresh is not None:
        state = jnp.where(fresh[:, None, None, None], 0.0, state)
    a = jnp.exp(dt * A.astype(f32))
    new = a[:, :, None, None] * state \
        + (dt[:, :, None] * x)[..., None] * Bm.astype(f32)[:, None, None, :]
    y = jnp.sum(new * Cm.astype(f32)[:, None, None, :], axis=-1) \
        + D.astype(f32)[:, None] * x
    return y, jnp.where(valid[:, None, None, None], new, state)


def _chunk(S, x, dt, Bm, Cm, A):
    """One chunk of L positions from the rows' states S [B, H, P, N]:
    (the states after it, its read-outs [B, L, H, P] without ``D x``).
    x [B, L, H, P]; dt [B, L, H] (0 where the position is not real);
    Bm, Cm [B, L, N]; A [H]; all float32."""
    L = x.shape[1]
    with jax.named_scope("ssd_intra"):
        cum = jnp.cumsum(dt * A, axis=1)                   # [B, L, H]
        by_head = jnp.moveaxis(cum, 1, 2)                  # [B, H, L]
        seen = jnp.tril(jnp.ones((L, L), bool))
        # exp of a masked difference, never a masked exp: the upper
        # triangle's differences are positive and may overflow
        decay = jnp.exp(jnp.where(
            seen, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
        cb = jnp.einsum("btn,bsn->bts", Cm, Bm, precision=_HI)
        dx = dt[..., None] * x                             # [B, L, H, P]
        y = jnp.einsum("bhts,bshp->bthp", cb[:, None] * decay, dx,
                       precision=_HI)
    with jax.named_scope("ssd_carry"):
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "btn,bhpn->bthp", Cm, S, precision=_HI)
        to_end = jnp.exp(cum[:, -1:] - cum)                # [B, L, H]
        S = jnp.exp(cum[:, -1])[:, :, None, None] * S + jnp.einsum(
            "bshp,bsn->bhpn", to_end[..., None] * dx, Bm, precision=_HI)
    return S, y


def ssd_chunked(x, dt, A, Bm, Cm, D, state, valid, chunk: int = 256):
    """T positions a row, in order. x [B, T, H, P]; dt [B, T, H]; A, D
    [H]; Bm, Cm [B, T, N]; state [B, H, P, N] float32 (what the rows
    begin from); valid [B, T] bool, a row's real positions its first
    ones. Returns (y [B, T, H, P] float32, the state after each row's
    last real position)."""
    f32 = jnp.float32
    B, T, H, P = x.shape
    x, Bm, Cm = x.astype(f32), Bm.astype(f32), Cm.astype(f32)
    A, D = A.astype(f32), D.astype(f32)
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    L = min(chunk, T)
    n = -(-T // L)

    def chunks(a):
        """[B, T, ...] -> [n, B, L, ...], the tail padded with positions
        that are not real (``dt`` 0 there: they move nothing)."""
        a = jnp.pad(a, ((0, 0), (0, n * L - T)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, L) + a.shape[2:]), 1, 0)

    if n == 1:
        state, y = _chunk(state.astype(f32), x, dt, Bm, Cm, A)
    else:
        state, y = jax.lax.scan(
            lambda S, xs: _chunk(S, *xs, A),
            state.astype(f32), (chunks(x), chunks(dt), chunks(Bm),
                                chunks(Cm)))
        y = jnp.moveaxis(y, 0, 1).reshape(B, n * L, H, P)[:, :T]
    return y + D[:, None] * x, state
