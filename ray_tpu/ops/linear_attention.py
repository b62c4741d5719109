"""Gated delta-rule linear attention with a per-channel decay (Kimi
Delta Attention), in plain ``jax.numpy``: the recurrence a linear-
attention layer keeps in place of a K/V cache.

Per head, with keys of width ``dk`` and values of width ``dv``, the
state ``S`` [dk, dv] (float32) moves one token at a time:

  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t

``g_t`` [dk] <= 0 is the log of the channel's decay, ``beta_t`` the
write strength (in (0, 2) where negative eigenvalues are allowed).
Two entry points compute it, and tests/test_linear_attention.py holds
them equal to each other and to a token-by-token scan:

- ``kda_step``: one token (a decode step). Elementwise products and
  sums over the state, in float32 on the vector unit: two passes over
  the state and one write.
- ``kda_chunked``: a row of T tokens in chunks (a prefill chunk). Inside
  a chunk the T x T interactions are solved at once (the WY/UT form: a
  unit lower-triangular system in the writes ``u``); the state is
  handed from chunk to chunk by ``lax.scan``.

STABLE FOR ANY GATE: the only exponentials taken are of differences
``G_t - G_i`` of cumulative log-decays with t >= i, which are <= 0.
The factored form ``(k_t exp(G_t)) . (k_i exp(-G_i))`` that would make
the interaction a matmul overflows as soon as a channel decays hard
(exp(+20 x 64)), so the interaction is summed over channels directly.
That is a [C, C, dk] product per head and chunk on the vector unit:
the chunk length trades it against the number of scan steps and the
size of their matmuls. On a TPU v5e a [4, 256] row of 64 heads of 128
took 5.93 ms at C = 64, 3.22 at 32 and 2.90 at 16 (PERF.md section 6,
PR 32), hence the default.

``valid`` marks real positions: an invalid one (padding inside a
prefill row, a free slot riding a decode call) has beta 0 and g 0,
which leaves the state exactly as it was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _masked(g, beta, valid):
    if valid is None:
        return g, beta
    return (jnp.where(valid[..., None, None], g, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


def kda_step(q, k, v, g, beta, state, valid=None):
    """One token. q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H]; state
    [B, H, dk, dv] float32; valid [B] bool or None. Returns (o [B, H,
    dv] float32, the new state)."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    g, beta = _masked(g, beta, valid)
    decayed = state * jnp.exp(g)[..., None]
    # S'^T k and S'^T q in one pass over the state; then
    # o = S_t^T q = S'^T q + u (k . q)
    from_k = jnp.sum(decayed * k[..., None], axis=-2)
    from_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - from_k)
    o = from_q + u * jnp.sum(k * q, axis=-1, keepdims=True)
    return o, decayed + k[..., None] * u[..., None, :]


def kda_chunked(q, k, v, g, beta, state, valid=None, chunk: int = 16):
    """A row of T tokens. q, k, g [B, T, H, dk]; v [B, T, H, dv]; beta
    [B, T, H]; state [B, H, dk, dv] float32; valid [B, T] bool or
    None. Returns (o [B, T, H, dv] float32, the state after the row's
    last valid token). T need not divide by ``chunk``: the row is
    padded with invalid positions."""
    B, T, H, dk = q.shape
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    g, beta = _masked(g, beta, valid)
    C = min(chunk, T)
    n = -(-T // C)
    pad = n * C - T

    def chunks(a):
        """[B, T, H, ...] -> [n, B, H, C, ...], zero-padded: beta 0
        and g 0 are invalid positions."""
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, n, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)

    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    eye = jnp.eye(C, dtype=F32)

    def one_chunk(S, xs):
        q, k, v, g, beta = xs           # [B, H, C, d]; beta [B, H, C]
        G = jnp.cumsum(g, axis=2)
        # decay[t, i, c] = exp(G_t[c] - G_i[c]) for t >= i, else 0:
        # never an exponential of a positive number
        diff = G[:, :, :, None, :] - G[:, :, None, :, :]
        decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        k_decayed = k[:, :, None, :, :] * decay         # [B,H,C,C,dk]
        kk = jnp.sum(k[:, :, :, None, :] * k_decayed, axis=-1)
        qk = jnp.sum(q[:, :, :, None, :] * k_decayed, axis=-1)
        into = jnp.exp(G)                               # from S into t
        # (I + Diag(beta) kk_strict) U = Diag(beta) (V - (K.into) S)
        rhs = beta[..., None] * (v - jnp.einsum(
            "bhtc,bhcv->bhtv", k * into, S, precision=_HI))
        system = eye + beta[..., None] * jnp.where(strict, kk, 0.0)
        U = jax.lax.linalg.triangular_solve(
            system, rhs, left_side=True, lower=True, unit_diagonal=True)
        o = (jnp.einsum("bhtc,bhcv->bhtv", q * into, S, precision=_HI)
             + jnp.einsum("bhti,bhiv->bhtv", qk, U, precision=_HI))
        out_of = jnp.exp(G[:, :, -1:, :] - G)           # from i to C
        S = (S * into[:, :, -1, :, None]
             + jnp.einsum("bhic,bhiv->bhcv", k * out_of, U,
                          precision=_HI))
        return S, o

    with jax.default_matmul_precision("highest"):   # the solve's too
        state, o = jax.lax.scan(
            one_chunk, state, tuple(chunks(a) for a in (q, k, v, g, beta)))
    # [n, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)
    return o.reshape((B, n * C) + o.shape[3:])[:, :T], state
