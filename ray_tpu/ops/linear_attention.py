"""Gated delta-rule linear attention: the recurrence a linear-attention
layer keeps in place of a K/V cache, under either of two gates.

Per head, with keys of width ``dk`` and values of width ``dv`` (they
need not be equal), the state ``S`` [dk, dv] (float32) moves one token
at a time:

  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t

``beta_t`` is the write strength (in (0, 2) where negative eigenvalues
are allowed) and ``g_t`` <= 0 the log of the decay. THE GATE'S SHAPE
SAYS WHICH RULE IT IS: one more axis than ``beta`` ([.., H, dk]) is a
decay a CHANNEL (Kimi Delta Attention: models/solar_open2.py); the
shape of ``beta`` ([.., H]) is ONE decay a head, ``Diag(exp(g_t))`` a
multiple of the identity (Gated DeltaNet: models/olmo_hybrid.py). Two
entry points take both, and tests/test_linear_attention.py holds them
equal to each other, to a token-by-token scan and, under a gate a head,
to the per-channel form fed that gate broadcast over the channels:

- ``kda_step``: one token (a decode step), either gate, any ``dk`` and
  ``dv``. Elementwise products and sums over the state, in float32 on
  the vector unit. On a TPU, outside any multi-device mesh, a float32
  state whose heads tile (``dk`` and ``dv`` multiples of 128: 128 x 128
  in both per-channel models) goes through ONE Pallas kernel that
  updates the state where it lies (``kda_step_kernel``): a block of a
  slot's heads comes into VMEM once, both read-outs and the rank-one
  write are computed there, and the block goes back over its input
  (``input_output_aliases``); the reset of a row that starts a request
  and the mask of a row that rides nothing are applied to the block in
  VMEM, and a row that rides nothing is neither fetched nor written.
  A state whose values are NO whole lane tile (96 x 192) is stored
  several heads side by side (``pack_heads``: [B, H / p, dk, p x dv],
  which ``kda_step`` reads off the state's shape), and under one decay
  a head such a state has the same kernel in its own layout
  (``kda_step_packed_kernel``: a slot's whole state a visit). Every
  other state (a head at a time at 96 x 192, a packed state under a
  decay a channel, the toy sizes, another type), the CPU, and any call
  under a mesh (GSPMD cannot partition a Mosaic kernel) gets the
  ``jax.numpy`` form of its layout, which XLA compiles to two reads of
  the state and one write (a sum followed by a consumer of the sum
  cannot be one fusion; PERF.md section 6, PR 40 has the chip's
  readings of both at 128 x 128, PR 49 of all three at 96 x 192). What
  decides is the backend, the ambient mesh, the gate's and the state's
  shape and type, never a flag.
- ``kda_chunked``: a row of T tokens in chunks (a prefill chunk). Inside
  a chunk the C x C interactions are solved at once (the WY/UT form: a
  unit lower-triangular system in the writes ``u``); the state is
  handed from chunk to chunk by ``lax.scan``.

HOW A CHUNK'S SYSTEM IS SOLVED (``unit_lower_inverse``, PR 50). The
system ``I + Diag(beta) strict(k k^T under the decays)`` of a chunk's
B x H heads is INVERTED by forward substitution, a row of the inverse a
step, every head's system at once with the batch on the lanes (a row's
step is then well under a microsecond), and the inverse multiplied into
the right-hand side by one matmul; exact, float32 at full precision.
Until PR 50 each chunk called XLA's ``triangular_solve``, which walks
the rows one little operation after another: 0.60 ms for 120 systems of
64 rows, 28.9 of a 103.3 ms prefill call of Olmo-Hybrid's (75.5 since),
and 0.23 ms a layer-call even for 16 chunks of [16, 16] (PERF.md
section 6, PR 50, on a TPU v5e: a [4, 256] row through ``kda_chunked``
3.47 -> 1.21 ms at 30 heads of 96 x 192; the systems of a layer-call
alone 2.62 -> 0.35 ms there, 0.54 -> 0.35 at 64 heads of [16, 16],
0.44 -> 0.28 at 32). Blocks of 8 to 32 rows merged upward by matmuls
were timed too (1.38 | 1.21 | 1.13 ms against 1.16 for the rows alone)
and left out: in the served cell the plain rows gave the shorter call.
The inverse is taken INSIDE the scan, a chunk at a time: a system reads
no state, so every chunk's could be inverted before the scan, and that
was built and timed too; it is no faster where the chunk is 64 and
SLOWER than XLA's solve at 64 heads of 128 x 128 (3.04-3.22 for 2.89:
what the scan then needs from outside it is a second pass over the
call's [16, 4, 64, 16, 128] arrays, 33.5 MB each, which no longer fit
the chip's fast memory). NOT BY A SERIES: ``(I + A)^-1 = (I - A)
(I + A^2)(I + A^4)..`` is six matmuls and wrong here. With unit keys
and beta up to 2 a prompt that repeats a token gives ``A`` = 2 x (the
strictly lower ones): the inverse has entries of +-2, ``A^32`` entries
of 1e18, and float32 cancels to garbage (2e18 of the largest true entry
at C = 64; tests/test_linear_attention.py holds the substitution on
those inputs).

STABLE FOR ANY GATE: the only exponentials taken are of differences
``G_t - G_i`` of cumulative log-decays with t >= i, which are <= 0.
A decay a channel: the factored form ``(k_t exp(G_t)) . (k_i exp(-G_i))``
that would make the interaction a matmul overflows as soon as a
channel decays hard (exp(+20 x 64)), so the interaction is summed over
channels directly: a [C, C, dk] product per head and chunk on the
vector unit, and the chunk length trades it against the number of scan
steps and the size of their matmuls. On a TPU v5e a [4, 256] row of 64
heads of 128 took 5.93 ms at C = 64, 3.22 at 32 and 2.90 at 16
(PERF.md section 6, PR 32), hence that form's chunk. ONE decay a head:
the decay between two positions of a chunk is a [C, C] matrix
``exp(G_t - G_i)`` that no channel enters, so ``q k^T`` and ``k k^T``
are matrix-unit products masked by it, nothing of [C, C, dk] exists,
and the chunk is as long as the matmuls like: a [4, 256] row of 30
heads of 96 x 192 took 1.51 ms at C = 16, 1.28 at 32, 1.21 at 64 and
1.55 at 128 (PERF.md section 6, PR 50; 3.78 | 3.53 | 3.47 | 3.61 under
XLA's solve), hence that form's chunk.

``valid`` marks real positions: an invalid one (padding inside a
prefill row, a free slot riding a decode call) has beta 0 and g 0,
which leaves the state exactly as it was.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

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
    """Whether ``kda_step`` on ``state`` [B, H, dk, dv] is the kernel:
    a float32 state (the kernel's arithmetic), ``dk`` and ``dv`` whole
    128-lane tiles (a head's [dk, dv] block and its [dv, dk] transposed
    columns are then whole tiles of VMEM: 128 x 128 in both per-channel
    models; 96 x 192 is one and a half lane tiles and gets the
    ``jax.numpy`` form), and one TPU outside any multi-device mesh
    (GSPMD cannot partition a Mosaic kernel)."""
    dk, dv = state.shape[-2:]
    return (state.dtype == F32 and dk % _LANES == 0 and dv % _LANES == 0
            and _on_one_tpu())


def kda_step(q, k, v, g, beta, state, valid=None, fresh=None):
    """One token. q, k [B, H, dk]; v [B, H, dv]; beta [B, H]; g
    [B, H, dk] (a decay a channel) or [B, H] (one a head); state
    [B, H, dk, dv] float32, or [B, H / p, dk, p x dv] as ``pack_heads``
    stores it (fewer rows of heads than ``q`` has say so); valid, fresh
    [B] bool or None. A ``fresh`` row starts from zeros, whatever
    ``state`` holds for it; a row that is not ``valid`` leaves its state
    as it was. Returns (o [B, H, dv] float32, the new state, stored as
    it came); ``o`` of a row that is not valid means nothing."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    per_head = g.ndim == beta.ndim
    if per_head:
        # one decay a head: the same products, the gate broadcast over
        # the channels where it meets them
        g = g[..., None]
    if state.shape[1] != q.shape[1]:
        if _use_packed_kernel(state, g):
            return kda_step_packed_kernel(q, k, v, g[..., 0], beta, state,
                                          valid, fresh)
        return _kda_step_packed(q, k, v, g, beta, state, valid, fresh)
    if _use_kernel(state):
        if per_head:
            g = jnp.broadcast_to(g, k.shape)
        return kda_step_kernel(q, k, v, g, beta, state, valid, fresh)
    return _kda_step_xla(q, k, v, g, beta, state, valid, fresh)


# a chunk's length by the gate (the module's docstring has the readings)
_CHUNK_PER_CHANNEL, _CHUNK_PER_HEAD = 16, 64


def unit_lower_inverse(below):
    """``(I + below)^-1`` for ``below`` [.., C, C] STRICTLY lower-
    triangular, exactly, by forward substitution (the module's docstring
    says why no series): row i of the inverse is ``e_i - below[i, :i] @
    inverse[:i]``, C dependent steps, every matrix at once with the
    batch on the lanes."""
    C = below.shape[-1]
    a = jnp.moveaxis(below.reshape(-1, C, C), 0, -1)        # [i, j, N]

    def row(i, inv):
        a_i = jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        e_i = (jnp.arange(C) == i).astype(F32)
        # the rows not yet written are zeros, as below[i, j >= i] is
        new = e_i[:, None] - jnp.sum(a_i[:, None, :] * inv, axis=0)
        return jax.lax.dynamic_update_index_in_dim(inv, new, i, 0)

    inv = jax.lax.fori_loop(0, C, row, jnp.zeros_like(a))
    return jnp.moveaxis(inv, -1, 0).reshape(below.shape)


def kda_chunked(q, k, v, g, beta, state, valid=None,
                chunk: Optional[int] = None):
    """A row of T tokens. q, k [B, T, H, dk]; v [B, T, H, dv]; beta
    [B, T, H]; g [B, T, H, dk] (a decay a channel) or [B, T, H] (one a
    head); state [B, H, dk, dv] float32; valid [B, T] bool or None.
    Returns (o [B, T, H, dv] float32, the state after the row's last
    valid token). T need not divide by ``chunk`` (the gate's own where
    None): the row is padded with invalid positions."""
    B, T, H, dk = q.shape
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    per_head = g.ndim == beta.ndim
    if per_head:
        g = g[..., None]        # [B, T, H, 1]: broadcasts over channels
    if chunk is None:
        chunk = _CHUNK_PER_HEAD if per_head else _CHUNK_PER_CHANNEL
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

    def one_chunk(S, xs):
        q, k, v, g, beta = xs           # [B, H, C, d]; beta [B, H, C]
        G = jnp.cumsum(g, axis=2)
        # decay[t, i, c] = exp(G_t[c] - G_i[c]) for t >= i, else 0:
        # never an exponential of a positive number
        diff = G[:, :, :, None, :] - G[:, :, None, :, :]
        decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        if per_head:
            # [B, H, C, C, 1]: no channel enters it, so the two
            # interactions are matmuls under it
            kk = jnp.einsum("bhtc,bhic->bhti", k, k,
                            precision=_HI) * decay[..., 0]
            qk = jnp.einsum("bhtc,bhic->bhti", q, k,
                            precision=_HI) * decay[..., 0]
        else:
            k_decayed = k[:, :, None, :, :] * decay     # [B,H,C,C,dk]
            kk = jnp.sum(k[:, :, :, None, :] * k_decayed, axis=-1)
            qk = jnp.sum(q[:, :, :, None, :] * k_decayed, axis=-1)
        into = jnp.exp(G)                               # from S into t
        # (I + Diag(beta) kk_strict) U = Diag(beta) (V - (K.into) S)
        rhs = beta[..., None] * (v - jnp.einsum(
            "bhtc,bhcv->bhtv", k * into, S, precision=_HI))
        U = jnp.einsum("bhti,bhiv->bhtv", unit_lower_inverse(
            beta[..., None] * jnp.where(strict, kk, 0.0)), rhs,
            precision=_HI)
        o = (jnp.einsum("bhtc,bhcv->bhtv", q * into, S, precision=_HI)
             + jnp.einsum("bhti,bhiv->bhtv", qk, U, precision=_HI))
        out_of = jnp.exp(G[:, :, -1:, :] - G)           # from i to C
        S = (S * into[:, :, -1, :, None]
             + jnp.einsum("bhic,bhiv->bhcv", k * out_of, U,
                          precision=_HI))
        return S, o

    state, o = jax.lax.scan(
        one_chunk, state, tuple(chunks(a) for a in (q, k, v, g, beta)))
    # [n, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)
    return o.reshape((B, n * C) + o.shape[3:])[:, :T], state


# ------------------------------------- heads side by side on the lanes
#
# A head whose values are no whole number of 128-lane tiles (192: one
# and a half) is PADDED by the chip to the next tile in memory (256: a
# third more bytes kept and moved, every step). ``p`` heads side by side
# fill whole tiles where ``p * dv`` does (two heads of 192 are three
# tiles): the state is then stored, and stepped, as [B, H / p, dk,
# p * dv], lane l of group j belonging to head j * p + l // dv.

def pack_heads(state, p: int):
    """[B, H, dk, dv] -> [B, H / p, dk, p * dv]."""
    B, H, dk, dv = state.shape
    return jnp.swapaxes(state.reshape(B, H // p, p, dk, dv), 2, 3).reshape(
        B, H // p, dk, p * dv)


def unpack_heads(packed, p: int):
    """[B, H / p, dk, p * dv] -> [B, H, dk, dv]: ``pack_heads``'s
    inverse."""
    B, G, dk, W = packed.shape
    return jnp.swapaxes(packed.reshape(B, G, dk, p, W // p), 2, 3).reshape(
        B, G * p, dk, W // p)


def _kda_step_packed(q, k, v, g, beta, state, valid, fresh):
    """``_kda_step_xla`` over a state stored ``pack_heads``-wise: the
    same products, a head's row or column laid across its own lanes of
    its group (small arrays: nothing of the state's size is made but
    the new state). g [B, H, dk] or [B, H, 1]."""
    B, G, dk, W = state.shape
    p = q.shape[1] // G
    dv = W // p
    if fresh is not None:
        state = jnp.where(fresh[:, None, None, None], 0.0, state)
    g, beta = _masked(g, beta, valid)
    head_of = jax.lax.broadcasted_iota(jnp.int32, (W,), 0) // dv

    def row(a):
        """[B, H] -> [B, G, W]: a head's number on each of its lanes."""
        return jnp.repeat(a.reshape(B, G, p), dv, axis=-1)

    def column(a):
        """[B, H, dk] -> [B, G, dk, W]: a head's column down the
        sublanes of each of its lanes (a select among the group's p
        columns, which fuses into what reads it)."""
        a = a.reshape(B, G, p, dk)
        out = a[:, :, 0, :, None]
        for j in range(1, p):
            out = jnp.where(head_of == j, a[:, :, j, :, None], out)
        return out
    decay = jnp.exp(g)
    decayed = state * (row(decay[..., 0])[:, :, None, :]
                       if g.shape[-1] == 1 else column(decay))
    k_col = column(k)
    from_k = jnp.sum(decayed * k_col, axis=-2)
    from_q = jnp.sum(decayed * column(q), axis=-2)
    u = row(beta) * (v.reshape(B, G, W) - from_k)
    o = from_q + u * row(jnp.sum(k * q, axis=-1))
    return o.reshape(B, G * p, dv), decayed + k_col * u[..., None, :]


# A packed state under ONE decay a head, on one TPU: the kernel above in
# the packed layout. A visit is one slot's whole state ([G, dk, W]), a
# loop step a group of p heads: each head's key (with the head's three
# numbers after it: decay, beta, k . q) and query come in as lane rows,
# are turned into sublane columns by one transpose each, and are laid
# across the group's lanes by selects; the row after the key's columns
# IS the head's decay on every lane, and so on.

# a slot's state in one block, each way, double-buffered
_PACKED_BLOCK_BYTES = 4 << 20


def _packed_step_kernel(mode_ref, row_ref, o_row_ref, q_ref, k_ref, v_ref,
                        s_ref, o_ref, s_out_ref, *, p, unroll):
    del row_ref, o_row_ref                      # the index maps' alone
    mode = mode_ref[pl.program_id(0)]
    G, dk, W = s_ref.shape[1:]
    dv = W // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def column(ref, h):
        """Row ``h`` of ``ref`` [1, H, L] -> [L, 128] with entry [c, :]
        = row[c]."""
        row = ref[0, pl.ds(h, 1), :]
        return jnp.broadcast_to(row, (_LANES, row.shape[1])).T

    def across(pieces, rows):
        """p arrays whose lanes are all alike (rows ``rows`` of each) ->
        [n, W]: head j's on its own lanes of the group."""
        tiles = []
        for t in range(W // _LANES):
            first = t * _LANES // dv
            cur = pieces[first][rows]
            for j in range(first + 1, min(p - 1, (t * _LANES + _LANES - 1)
                                          // dv) + 1):
                cur = jnp.where(lane + t * _LANES >= j * dv,
                                pieces[j][rows], cur)
            tiles.append(cur)
        return jnp.concatenate(tiles, axis=1)

    @pl.when(mode == _SKIP)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(mode == _COPY)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        s_out_ref[...] = s_ref[...]

    @pl.when((mode == _STEP) | (mode == _FRESH))
    def _():
        def group(g):
            state = jnp.where(mode == _FRESH, 0.0, s_ref[0, g])
            ks = [column(k_ref, g * p + j) for j in range(p)]
            qs = [column(q_ref, g * p + j) for j in range(p)]
            k_col = across(ks, slice(0, dk))
            decayed = state * across(ks, slice(dk, dk + 1))
            from_k = jnp.sum(decayed * k_col, axis=0, keepdims=True)
            from_q = jnp.sum(decayed * across(qs, slice(0, dk)), axis=0,
                             keepdims=True)
            u = across(ks, slice(dk + 1, dk + 2)) * (
                v_ref[0, pl.ds(g, 1), :] - from_k)
            o_ref[0, pl.ds(g, 1), :] = from_q + u * across(
                ks, slice(dk + 2, dk + 3))
            s_out_ref[0, g] = decayed + k_col * u

        def groups(i, carry):
            for j in range(unroll):
                group(i * unroll + j)
            return carry

        jax.lax.fori_loop(0, G // unroll, groups, 0)


def kda_step_packed_kernel(q, k, v, g, beta, state, valid=None, fresh=None,
                           *, unroll=None, interpret=False):
    """``kda_step`` on a state stored ``pack_heads``-wise under ONE
    decay a head (g [B, H]) as one Pallas TPU kernel over the visits of
    ``kda_step_kernel`` (riding rows first, the others skipped); float32
    operands. The state is aliased to the new state. A row that is not
    ``valid`` keeps its state bit for bit and reads out zeros."""
    B, G, dk, W = state.shape
    H = q.shape[1]
    p = H // G
    if unroll is None:
        unroll = next(u for u in (3, 2, 1) if G % u == 0)
    assert G % unroll == 0 and W % _LANES == 0, (G, unroll, W)
    if valid is None:
        valid = jnp.ones((B,), bool)
    if fresh is None:
        fresh = jnp.zeros((B,), bool)
    mode, row, o_row = _visits(valid, fresh)

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, 0), (0, -a.shape[-1] % _LANES)))
    # a head's key, then its decay, its beta and k . q
    k_aux = padded(jnp.concatenate(
        [k, jnp.exp(g)[..., None], beta[..., None],
         jnp.sum(k * q, axis=-1, keepdims=True)], axis=-1))
    q = padded(q)

    def block(*shape):
        return pl.BlockSpec((1,) + shape, lambda i, mode, row, o_row:
                            (row[i],) + (0,) * len(shape))
    o, state = pl.pallas_call(
        functools.partial(_packed_step_kernel, p=p, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[block(H, q.shape[-1]), block(H, k_aux.shape[-1]),
                      block(G, W), block(G, dk, W)],
            out_specs=[
                pl.BlockSpec((1, G, W), lambda i, mode, row, o_row:
                             (o_row[i], 0, 0)),
                block(G, dk, W)]),
        out_shape=[jax.ShapeDtypeStruct((B, G, W), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a repeated block index is a visit skipped
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * G * dk * W * 4 + (8 << 20)),
        interpret=interpret, name="kda_step_packed",
    )(mode, row, o_row, q, k_aux, v.reshape(B, G, W), state)
    return o.reshape(B, H, W // p), state


def _use_packed_kernel(state, g) -> bool:
    """Whether ``kda_step`` on a packed ``state`` [B, H / p, dk, p x dv]
    is the kernel: ONE decay a head (g [B, H, 1]: no model packs a state
    under a decay a channel), a float32 state whose packed values are
    whole 128-lane tiles and whose keys are whole sublane tiles, a
    slot's state within a block, and one TPU outside any multi-device
    mesh."""
    G, dk, W = state.shape[1:]
    return (g.shape[-1] == 1 and state.dtype == F32 and W % _LANES == 0
            and dk % 8 == 0 and G * dk * W * 4 <= _PACKED_BLOCK_BYTES
            and _on_one_tpu())
