"""Gated delta-rule linear attention with a per-channel decay (Kimi
Delta Attention): the recurrence a linear-attention layer keeps in
place of a K/V cache.

Per head, with keys of width ``dk`` and values of width ``dv``, the
state ``S`` [dk, dv] (float32) moves one token at a time:

  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t

``g_t`` [dk] <= 0 is the log of the channel's decay, ``beta_t`` the
write strength (in (0, 2) where negative eigenvalues are allowed).
Two entry points compute it, and tests/test_linear_attention.py holds
them equal to each other and to a token-by-token scan:

- ``kda_step``: one token (a decode step). Elementwise products and
  sums over the state, in float32 on the vector unit. On a TPU,
  outside any multi-device mesh and where heads tile (``dk`` and ``dv``
  multiples of 128), it is ONE Pallas kernel that updates the state
  where it lies: a block of a slot's heads comes into VMEM once, both
  read-outs and the rank-one write are computed there, and the block
  goes back over its input (``input_output_aliases``); the reset of a
  row that starts a request and the mask of a row that rides nothing
  are applied to the block in VMEM, and a row that rides nothing is
  neither fetched nor written. Everywhere else (the CPU; under a mesh,
  where GSPMD cannot partition a Mosaic kernel) it is the ``jax.numpy``
  form, which XLA compiles to two reads of the state and one write (a
  sum followed by a consumer of the sum cannot be one fusion; PERF.md
  section 6, PR 40 has the chip's readings of both). What decides is
  the backend, the ambient mesh and the shapes, never a flag.
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

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the backend is a TPU and no multi-device mesh is ambient: one rule
# for every Mosaic kernel that has an XLA form
from ray_tpu.ops.grouped_matmul import on_one_tpu as _on_one_tpu

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_LANES = 128


def _masked(g, beta, valid):
    if valid is None:
        return g, beta
    return (jnp.where(valid[..., None, None], g, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


def _kda_step_xla(q, k, v, g, beta, state, valid, fresh):
    if fresh is not None:
        state = jnp.where(fresh[:, None, None, None], 0.0, state)
    g, beta = _masked(g, beta, valid)
    decayed = state * jnp.exp(g)[..., None]
    # S'^T k and S'^T q; then o = S_t^T q = S'^T q + u (k . q)
    from_k = jnp.sum(decayed * k[..., None], axis=-2)
    from_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - from_k)
    o = from_q + u * jnp.sum(k * q, axis=-1, keepdims=True)
    return o, decayed + k[..., None] * u[..., None, :]


# ------------------------------------------------ the one-token kernel

class StepPlan(NamedTuple):
    """How the one-token kernel walks a state [B, H, dk, dv]: blocks of
    ``heads`` heads of one slot, ``unroll`` heads to a loop step."""
    heads: int
    unroll: int

    def vmem_bytes(self, dk: int, dv: int) -> int:
        """The state's block in and out, each double-buffered, and room
        for the small operands and what the compiler spills."""
        return 4 * self.heads * dk * dv * 4 + (8 << 20)


# A visit moves one block of heads in and one out, and has a fixed
# price, so blocks are sized by what a visit moves: 2 MiB each way (32
# heads of 128 x 128 float32).
_BLOCK_BYTES = 2 << 20


@functools.lru_cache(maxsize=None)
def step_plan(H: int, dk: int, dv: int) -> StepPlan:
    """The plan for ``H`` heads of ``[dk, dv]`` float32.

    Measured on v5e (PR 40; 8 chained steps, ms a step, PERF.md section
    6) at [128 slots, 32 heads] with 125 rows riding and at [32, 64]
    with 29, heads of 128 x 128: blocks of 32 heads with one head a
    loop step 1.10 and 0.55, with two 0.87 and 0.42, with four **0.84
    and 0.41**; blocks of 16 with two 0.91 and 0.45, of 8 1.01 and
    0.50, of 64 0.41 (the ``jax.numpy`` form 1.29 and 0.65; a plain
    copy of the state in place, the bound of any kernel on this grid,
    0.82 and 0.42 with every row riding). A head's columns (``k``,
    ``q exp(g)``, ``exp(g)`` turned from lane rows into sublane
    columns) are 48 pushes and 48 pops on the cross-lane units, one to
    an instruction bundle; several heads to a loop step let one head's
    hide behind another's arithmetic, and from there the block's two
    ways to and from HBM bound the step (~650 GB/s of the 819)."""
    cap = max(1, _BLOCK_BYTES // (dk * dv * 4))
    heads = H
    if H > cap:
        # whole sublane tiles of the small operands' [heads, d] blocks;
        # a divisor of H where there is one
        fits = range(8, cap + 1, 8)
        heads = next((h for h in reversed(fits) if H % h == 0),
                     fits[-1] if fits else H)
    return StepPlan(heads, next(u for u in (4, 2, 1) if heads % u == 0))


# What a visit is, as the kernel reads it from SMEM: nothing (the visits
# left over once every riding row has had its own); one token; one
# token from zeros, whatever the slot held; a copy of the state (the
# first visit of a call in which NO row rides anything).
_SKIP, _STEP, _FRESH, _COPY = 0, 1, 2, 3


def _step_kernel(mode_ref, row_ref, o_row_ref, q_ref, k_ref, v_ref,
                 g_ref, beta_ref, s_ref, o_ref, s_out_ref, *, plan):
    del row_ref, o_row_ref                      # the index maps' alone
    mode = mode_ref[pl.program_id(1)]
    hb, dk, dv = s_ref.shape[1:]

    def column(row):
        """[1, dk] -> [dk, dv] with entry [c, :] = row[c]: the
        transpose of the row repeated down the sublanes, the one way
        from lanes to sublanes."""
        return jnp.broadcast_to(row, (dv, dk)).T

    @pl.when(mode == _SKIP)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(mode == _COPY)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        s_out_ref[...] = s_ref[...]

    @pl.when((mode == _STEP) | (mode == _FRESH))
    def _():
        def head(h):
            row = pl.ds(h, 1)
            state = jnp.where(mode == _FRESH, 0.0, s_ref[0, h])
            decay = jnp.exp(g_ref[0, row, :])               # [1, dk]
            k, q = k_ref[0, row, :], q_ref[0, row, :]
            k_col = column(k)
            decayed = state * column(decay)
            from_k = jnp.sum(decayed * k_col, axis=0, keepdims=True)
            from_q = jnp.sum(state * column(q * decay), axis=0,
                             keepdims=True)
            u = beta_ref[0, row, :] * (v_ref[0, row, :] - from_k)
            o_ref[0, row, :] = from_q + u * jnp.sum(
                k * q, axis=1, keepdims=True)
            s_out_ref[0, h] = decayed + k_col * u

        def group(i, carry):
            for j in range(plan.unroll):
                head(i * plan.unroll + j)
            return carry

        jax.lax.fori_loop(0, hb // plan.unroll, group, 0)


def _visits(valid, fresh):
    """The order in which a call's B visits take its rows: the riding
    rows first, in their order, so that each one's block is fetched
    while the one before is computed; the visits left over all name
    the LAST riding row's block, and a block index that repeats is
    neither fetched again nor written back, so a row that rides
    nothing costs a grid step and moves nothing. Returns (mode, the
    state's row, the read-out's row), each [B] int32."""
    B = valid.shape[0]
    i32 = jnp.int32
    rows = jnp.arange(B, dtype=i32)
    n = jnp.sum(valid, dtype=i32)
    place = jnp.where(valid, jnp.cumsum(valid, dtype=i32) - 1,
                      n + jnp.cumsum(~valid, dtype=i32) - 1)
    at = place[None, :] == rows[:, None]        # [visit, row]: B x B

    def ordered(a):
        return jnp.sum(jnp.where(at, a[None, :], 0), axis=1, dtype=i32)
    order = ordered(rows)
    mode = ordered(jnp.where(valid, jnp.where(fresh, _FRESH, _STEP),
                             _SKIP).astype(i32))
    mode = mode.at[0].set(jnp.where(n > 0, mode[0], _COPY))
    last = jnp.sum(jnp.where(rows == jnp.maximum(n - 1, 0), order, 0))
    return mode, jnp.where(rows < n, order, last), order


def kda_step_kernel(q, k, v, g, beta, state, valid=None, fresh=None, *,
                    plan=None, interpret=False):
    """``kda_step`` as one Pallas TPU kernel over (blocks of heads,
    visits); float32 operands. The state is aliased to the new state:
    donate it (or carry it through a loop) and it is updated where it
    lies. A row that is not ``valid`` keeps its state bit for bit and
    reads out zeros."""
    B, H, dk, dv = state.shape
    plan = plan or step_plan(H, dk, dv)
    assert plan.heads % plan.unroll == 0, plan
    if valid is None:
        valid = jnp.ones((B,), bool)
    if fresh is None:
        fresh = jnp.zeros((B,), bool)
    mode, row, o_row = _visits(valid, fresh)

    def small(width):
        return pl.BlockSpec((1, plan.heads, width),
                            lambda j, i, mode, row, o_row: (row[i], j, 0))
    whole = pl.BlockSpec(
        (1, plan.heads, dk, dv),
        lambda j, i, mode, row, o_row: (row[i], j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(H, plan.heads), B),
            in_specs=[small(dk), small(dk), small(dv), small(dk),
                      small(1), whole],
            out_specs=[
                pl.BlockSpec((1, plan.heads, dv),
                             lambda j, i, mode, row, o_row:
                             (o_row[i], j, 0)),
                whole]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a repeated block index is a visit skipped
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.vmem_bytes(dk, dv)),
        interpret=interpret, name="kda_step",
    )(mode, row, o_row, q, k, v, g, beta[..., None], state)
    return o, state


def _use_kernel(state) -> bool:
    dk, dv = state.shape[-2:]
    return (state.dtype == F32 and dk % _LANES == 0 and dv % _LANES == 0
            and _on_one_tpu())


def kda_step(q, k, v, g, beta, state, valid=None, fresh=None):
    """One token. q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H]; state
    [B, H, dk, dv] float32; valid, fresh [B] bool or None. A ``fresh``
    row starts from zeros, whatever ``state`` holds for it; a row that
    is not ``valid`` leaves its state as it was. Returns (o [B, H, dv]
    float32, the new state); ``o`` of a row that is not valid means
    nothing."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    if _use_kernel(state):
        return kda_step_kernel(q, k, v, g, beta, state, valid, fresh)
    return _kda_step_xla(q, k, v, g, beta, state, valid, fresh)


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
