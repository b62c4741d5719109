"""Flash attention: custom pallas TPU kernels (forward + backward).

The framework's own blockwise-attention kernel (SURVEY.md §7 hard part 5 —
"the only place we write kernels"), used for long sequences where XLA
attention materializes the [B,H,T,T] score tensor in HBM. Design notes:

- Online softmax: running (m, l, acc) in VMEM scratch, revisited across the
  kv grid dimension (innermost, "arbitrary" semantics); scores never touch
  HBM. fp32 accumulation, bf16 MXU matmuls everywhere
  (preferred_element_type=f32 — fp32 MXU operands run at a fraction of
  bf16 rate).
- Causal blocks kj > qi are predicated off with @pl.when (the grid still
  visits them; the MXU work is skipped).
- Backward is two kernels: dq (grid over q blocks, accumulate over kv) and
  dk/dv (grid over kv blocks, accumulate over q), using the saved
  logsumexp; delta = rowsum(do * o) is computed in-kernel from o — no
  separate delta pass, no broadcast materialization in HBM (measured: the
  precomputed-delta version spent ~22 ms/step of the GPT-2-124M b24 body
  in multiply_reduce + broadcast_in_dim + copies).
- Layout: kernels read q/k/v straight from the model's natural
  [B, T, H*D] activation layout, packing 128/D heads per grid program
  (TPU lane width 128 — for GPT-2's D=64 each program handles 2 heads,
  for Llama's D=128 exactly 1). No [B,T,H,D] <-> [B*H,T,D] transpose
  copies on either side of the op (measured ~16 ms/step of copies on the
  b24 GPT-2 body with the folded layout). Shapes that don't tile the
  lane blocks (odd H, D not a power of two) are zero-padded to the
  nearest packable (H', D') in flash_attention — see its docstring for
  why that is sound.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128


# Measured on v5e (GPT-2-124M fwd+bwd, B=24 T=1024): 1024 gives the
# best step time — bigger blocks amortize grid overhead and keep the
# MXU busy; the 1024x1024 fp32 score block (4 MiB) still fits VMEM
# comfortably.
_BLOCK_TARGET = 1024


def _pick_block(t: int) -> int:
    """The largest block of at most ``_BLOCK_TARGET`` positions that
    divides a sequence of ``t`` (never under a lane's width)."""
    blk = min(t, _BLOCK_TARGET)
    while t % blk:
        blk //= 2
    return max(blk, min(t, _LANES))


def _interpret() -> bool:
    """Pallas TPU kernels run natively on TPU; everywhere else (the CPU
    test mesh) they run in interpreter mode."""
    return jax.default_backend() != "tpu"


def _causal_mask(s, qi, kj, blk_q, blk_k):
    qpos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    kpos = kj * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    return jnp.where(kpos <= qpos, s, _NEG_INF)


def _pack_factor(H: int, D: int):
    """How many heads each grid program covers in the packed layout,
    or 0 if the packed layout doesn't apply."""
    C = H * D
    if C <= _LANES:
        return H                      # whole C fits one lane block
    if D <= _LANES and _LANES % D == 0 and H % (_LANES // D) == 0:
        return _LANES // D
    if D % _LANES == 0:
        return 1                      # wide heads: one per program,
    return 0                          # lane block = D (128-divisible)


# --------------------------------------------------------------------------
# Forward (packed layout: q/k/v/o are [B, T, C], one program handles
# `npack` heads living in one lane block)
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                blk_q: int, blk_k: int, num_kv: int, npack: int, d: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0]                   # [blk_q, npack*d]
        k = k_ref[0]                   # [blk_k, npack*d]
        v = v_ref[0]
        for p in range(npack):
            sl = slice(p * d, (p + 1) * d)
            s = jax.lax.dot_general(
                q[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                s = _causal_mask(s, qi, kj, blk_q, blk_k)
            m_prev = m_scr[p, :, :1]   # [blk_q, 1]
            m_blk = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_blk)
            alpha = jnp.exp(m_prev - m_new)
            pp = jnp.exp(s - m_new)    # [blk_q, blk_k] f32
            l_new = l_scr[p, :, :1] * alpha + \
                jnp.sum(pp, -1, keepdims=True)
            pv = jax.lax.dot_general(
                pp.astype(v.dtype), v[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_scr[p] = acc_scr[p] * alpha + pv
            m_scr[p] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[p] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    if causal:
        pl.when(kj <= qi * (blk_q // blk_k) + (blk_q // blk_k) - 1)(
            _compute)
    else:
        _compute()

    last_kj = (qi * (blk_q // blk_k) + (blk_q // blk_k) - 1) \
        if causal else num_kv - 1

    @pl.when(kj == last_kj)
    def _finalize():
        outs, lses = [], []
        for p in range(npack):
            l = jnp.maximum(l_scr[p, :, :1], 1e-30)
            outs.append((acc_scr[p] / l).astype(o_ref.dtype))
            lses.append(m_scr[p, :, :1] + jnp.log(l))
        o_ref[0] = jnp.concatenate(outs, axis=1)
        # Head p's lse lives in lane p of the 128-lane block
        # (npack <= 128 always; readers index [:, p:p+1]).
        lse = jnp.concatenate(lses, axis=1)       # [blk_q, npack]
        lse_ref[0, 0] = jnp.pad(
            lse, ((0, 0), (0, _LANES - npack)))


def _flash_fwd(q, k, v, causal: bool, H: int, D: int,
               scale: float) -> Tuple[jax.Array, jax.Array]:
    """q/k/v: [B, T, C] with C = H*D in packed-lane layout."""
    B, T, C = q.shape
    Tk = k.shape[1]
    npack = _pack_factor(H, D)
    lane_blk = npack * D
    G = H // npack
    blk_q = _pick_block(T)
    blk_k = _pick_block(Tk)
    if causal and blk_q % blk_k:
        blk_k = blk_q = min(blk_q, blk_k)
    num_kv = Tk // blk_k

    grid = (B, G, T // blk_q, num_kv)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, blk_q=blk_q,
        blk_k=blk_k, num_kv=num_kv, npack=npack, d=D)
    qo_spec = pl.BlockSpec((1, blk_q, lane_blk),
                           lambda b, g, i, j: (b, i, g))
    kv_spec = pl.BlockSpec((1, blk_k, lane_blk),
                           lambda b, g, i, j: (b, j, g))
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=[
            qo_spec,
            pl.BlockSpec((1, 1, blk_q, _LANES),
                         lambda b, g, i, j: (b, g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, C), q.dtype),
            jax.ShapeDtypeStruct((B, G, T, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((npack, blk_q, _LANES), jnp.float32),   # m
            pltpu.VMEM((npack, blk_q, _LANES), jnp.float32),   # l
            pltpu.VMEM((npack, blk_q, D), jnp.float32),        # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   dq_ref, acc_scr, *, scale: float, causal: bool,
                   blk_q: int, blk_k: int, num_kv: int, npack: int,
                   d: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]                  # bf16: MXU operand
        o = o_ref[0]
        for p in range(npack):
            sl = slice(p * d, (p + 1) * d)
            lse = lse_ref[0, 0][:, p:p + 1]
            # delta = rowsum(do * o), computed here instead of a
            # separate HBM pass.
            delta = jnp.sum(
                do[:, sl].astype(jnp.float32) *
                o[:, sl].astype(jnp.float32), axis=-1, keepdims=True)
            s = jax.lax.dot_general(
                q[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                s = _causal_mask(s, qi, kj, blk_q, blk_k)
            pp = jnp.exp(s - lse)
            dp = jax.lax.dot_general(
                do[:, sl], v[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = pp * (dp - delta)
            acc_scr[p] += jax.lax.dot_general(
                ds.astype(k.dtype), k[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when(kj <= qi * (blk_q // blk_k) + (blk_q // blk_k) - 1)(
            _compute)
    else:
        _compute()

    last_kj = (qi * (blk_q // blk_k) + (blk_q // blk_k) - 1) \
        if causal else num_kv - 1

    @pl.when(kj == last_kj)
    def _finalize():
        dq_ref[0] = jnp.concatenate(
            [acc_scr[p].astype(dq_ref.dtype) for p in range(npack)],
            axis=1)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, blk_q: int, blk_k: int, num_q: int,
                    npack: int, d: int):
    kj = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]                  # bf16: MXU operand
        o = o_ref[0]
        for p in range(npack):
            sl = slice(p * d, (p + 1) * d)
            lse = lse_ref[0, 0][:, p:p + 1]
            delta = jnp.sum(
                do[:, sl].astype(jnp.float32) *
                o[:, sl].astype(jnp.float32), axis=-1, keepdims=True)
            s = jax.lax.dot_general(
                q[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                s = _causal_mask(s, qi, kj, blk_q, blk_k)
            pp = jnp.exp(s - lse)                 # [blk_q, blk_k] f32
            # dv += p^T do — bf16 operands, fp32 accumulation.
            dv_scr[p] += jax.lax.dot_general(
                pp.astype(do.dtype), do[:, sl],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do[:, sl], v[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = pp * (dp - delta)                # [blk_q, blk_k]
            dk_scr[p] += jax.lax.dot_general(
                ds.astype(q.dtype), q[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    if causal:
        # Only q blocks at/after this kv block contribute.
        pl.when(qi * blk_q + blk_q - 1 >= kj * blk_k)(_compute)
    else:
        _compute()

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = jnp.concatenate(
            [dk_scr[p].astype(dk_ref.dtype) for p in range(npack)],
            axis=1)
        dv_ref[0] = jnp.concatenate(
            [dv_scr[p].astype(dv_ref.dtype) for p in range(npack)],
            axis=1)


def _flash_bwd_packed(causal, H, D, scale, res, g):
    q, k, v, o, lse = res
    do = g
    B, T, C = q.shape
    Tk = k.shape[1]
    npack = _pack_factor(H, D)
    lane_blk = npack * D
    G = H // npack
    blk_q = _pick_block(T)
    blk_k = _pick_block(Tk)
    if causal and blk_q % blk_k:
        blk_k = blk_q = min(blk_q, blk_k)
    num_kv = Tk // blk_k
    num_q = T // blk_q

    q_spec = pl.BlockSpec((1, blk_q, lane_blk),
                          lambda b, g, i, j: (b, i, g))
    k_spec = pl.BlockSpec((1, blk_k, lane_blk),
                          lambda b, g, i, j: (b, j, g))
    lse_spec = pl.BlockSpec((1, 1, blk_q, _LANES),
                            lambda b, g, i, j: (b, g, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, num_kv=num_kv,
                          npack=npack, d=D),
        grid=(B, G, num_q, num_kv),
        in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, C), q.dtype),
        scratch_shapes=[pltpu.VMEM((npack, blk_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, o, do, lse)

    # dkv grid: kv blocks in the third slot, q blocks innermost.
    kv_q_spec = pl.BlockSpec((1, blk_q, lane_blk),
                             lambda b, g, j, i: (b, i, g))
    kv_k_spec = pl.BlockSpec((1, blk_k, lane_blk),
                             lambda b, g, j, i: (b, j, g))
    kv_lse_spec = pl.BlockSpec((1, 1, blk_q, _LANES),
                               lambda b, g, j, i: (b, g, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, num_q=num_q,
                          npack=npack, d=D),
        grid=(B, G, num_kv, num_q),
        in_specs=[kv_q_spec, kv_k_spec, kv_k_spec, kv_q_spec,
                  kv_q_spec, kv_lse_spec],
        out_specs=[kv_k_spec, kv_k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tk, C), k.dtype),
            jax.ShapeDtypeStruct((B, Tk, C), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((npack, blk_k, D), jnp.float32),
            pltpu.VMEM((npack, blk_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, o, do, lse)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom_vjp wrapper over the packed [B, T, C] layout
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_packed(q, k, v, causal, H, D, scale):
    o, _ = _flash_fwd(q, k, v, causal, H, D, scale)
    return o


def _flash_packed_fwd(q, k, v, causal, H, D, scale):
    o, lse = _flash_fwd(q, k, v, causal, H, D, scale)
    return o, (q, k, v, o, lse)


_flash_packed.defvjp(_flash_packed_fwd, _flash_bwd_packed)


def _pad_to_packable(H: int, D: int):
    """Smallest (H', D') >= (H, D) that _pack_factor accepts: D' is the
    next divisor (or multiple) of 128, H' pads to a whole lane group."""
    if D <= _LANES:
        Dp = next(d for d in (1, 2, 4, 8, 16, 32, 64, _LANES) if d >= D)
    else:
        Dp = -(-D // _LANES) * _LANES
    if H * Dp <= _LANES:
        return H, Dp
    npack = max(1, _LANES // Dp)
    Hp = -(-H // npack) * npack
    return Hp, Dp


def flash_attention(q, k, v, causal: bool = True) -> jax.Array:
    """Pallas flash attention. q/k/v: [B, T, H, D]; returns [B, T, H, D].
    T must be a multiple of 128; causal requires equal q/kv lengths.
    Differentiable (custom pallas backward).

    The [B,T,H,D] -> [B,T,H*D] reshape below is layout-free (same memory
    order); the kernels block the packed layout directly. Shapes that
    don't tile the 128-lane blocks (odd H, D not a power of two) are
    zero-padded up to the nearest packable (H', D') — sound because the
    softmax scale is passed explicitly (1/sqrt of the REAL D), zero
    padding adds zero to every q.k dot, and the padded output
    heads/dims are sliced away (autodiff routes gradients through the
    pad/slice, outside the kernel's custom_vjp).
    """
    B, T, H, D = q.shape
    Tk = k.shape[1]
    if T % _LANES or Tk % _LANES:
        raise ValueError(
            f"flash_attention requires T % {_LANES} == 0, got {T}/{Tk}")
    if causal and T != Tk:
        # The kernel's causal mask aligns position 0 of q and kv; with
        # Tq != Tk its last-block finalize bookkeeping would also skip
        # writes. Cross-length causal (decode) goes through the xla path.
        raise ValueError(
            f"causal flash_attention requires equal q/kv lengths, "
            f"got {T} vs {Tk}")
    scale = 1.0 / (D ** 0.5)
    Hp, Dp = _pad_to_packable(H, D)
    if (Hp, Dp) != (H, D):
        pad = [(0, 0), (0, 0), (0, Hp - H), (0, Dp - D)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))

    def pack(x):
        return x.reshape(x.shape[0], x.shape[1], Hp * Dp)

    o = _flash_packed(pack(q), pack(k), pack(v), causal, Hp, Dp, scale)
    o = o.reshape(B, T, Hp, Dp)
    if (Hp, Dp) != (H, D):
        o = o[:, :, :H, :D]
    return o
