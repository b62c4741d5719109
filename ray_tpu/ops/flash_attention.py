"""Flash attention: custom pallas TPU kernels (forward + backward).

The framework's own blockwise-attention kernel (SURVEY.md §7 hard part 5 —
"the only place we write kernels"), used for long sequences where XLA
attention materializes the [B,H,T,T] score tensor in HBM. Design notes:

- Two levels, both read off ``tile_plan`` (a pure function of the
  shapes): the grid fetches BLOCKS of up to 1024 positions, and inside a
  block every TILE of q rows is scored against one SLAB of the block's
  kv tiles. A causal call computes the tiles of the triangle and no
  other: a block above the diagonal is neither computed nor fetched
  (its ``index_map`` stays on the last block needed, and Pallas does not
  fetch a block again), a slab on the diagonal ends with its row's
  diagonal tile, and only that tile is masked (one lower triangle, the
  same for each). Non-causal is the same loop over the full plan.
  Grid blocks and not one program a sequence, because T reaches 65,536;
  at T <= 1024 a block IS the sequence and the grid is (B, G, 1, 1).
- Softmax: where the kv side is one block (T <= 1024) a row is scored in
  one go and nothing is carried; else running (m, l, acc) rest in VMEM
  scratch between blocks of the kv grid dimension (innermost,
  "arbitrary" semantics). Scores never touch HBM. fp32 scores, exp,
  max/sum and accumulators, bf16 MXU matmuls everywhere
  (preferred_element_type=f32 — fp32 MXU operands run at a fraction of
  bf16 rate).
- Backward: ONE kernel body (``_bwd_kernel``) that scores a tile once
  (s, p, dp, ds) and accumulates from it whatever it is asked for. Where
  a lane group's whole sequence and the three float32 accumulators fit
  ``_VMEM_BUDGET`` (``TilePlan.one_pass``; T = 1024 does), one call
  asks for dq, dk and dv: 5 matmuls a tile (measured on v5e, PR 37, the
  train cell's shape: 1.50 ms a layer against 0.90 + 1.22 in two).
  Longer sequences run it twice: for dq (grid over q blocks, accumulate
  over kv) and for dk/dv (grid over kv blocks, accumulate over q), 7
  matmuls a tile. Both use
  the saved logsumexp; delta = rowsum(do * o) is computed in-kernel from
  o, once a q tile — no separate delta pass, no broadcast
  materialization in HBM (measured: the precomputed-delta version spent
  ~22 ms/step of the GPT-2-124M b24 body in multiply_reduce +
  broadcast_in_dim + copies).
- 1/sqrt(D) is folded into the q tile where it is a power of two
  (D = 64: exact in bf16), so that the scores and dk need no multiply;
  otherwise it stays on the float32 scores.
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
import math
from typing import Iterator, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128

# What one kernel's blocks, accumulators and score tiles may take of the
# 16 MiB of VMEM that Mosaic scopes to a kernel by default.
_VMEM_BUDGET = 12 * 2 ** 20

# dot_general dimension numbers of the three matmul forms
_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_NN = (((1,), (0,)), ((), ()))        # a @ b
_TN = (((0,), (0,)), ((), ()))        # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# The tile plan: which score tiles a call computes
# --------------------------------------------------------------------------

def _divisor(t: int, cap: int) -> int:
    """The largest of cap, cap/2, cap/4, ... (from ``t`` itself if it is
    smaller) that divides a sequence of ``t``, never under a lane's
    width."""
    d = min(t, cap)
    while t % d:
        d //= 2
    return max(d, min(t, _LANES))


class TilePlan(NamedTuple):
    """What ``tile_plan`` decided, and the counts it comes to. The
    kernels' grids and loops are built from ``blocks`` and ``slab``."""
    tile_q: int       # a score tile is [tile_q, tile_k]: one pair of
    tile_k: int       # matmuls, one exp (square when causal)
    blk_q: int        # a grid step fetches [blk_q] of q and [blk_k] of
    blk_k: int        # k/v: whole tiles
    causal: bool
    one_pass: bool    # the backward scores a tile once (see _bwd_kernel)
    visited: int      # tiles computed,
    masked: int       # of them on the diagonal (the only ones masked),
    total: int        # tiles in the [T, Tk] square

    def slab(self, i: int, diag: bool) -> Tuple[int, int]:
        """The kv positions of a block that q tile ``i`` of a block is
        scored against, as one slab of whole tiles from the block's
        first: (positions, of them unmasked; what is left is the one
        tile on the diagonal). ``diag``: the block lies on the diagonal
        of a causal call. A row's tiles are computed side by side, so
        that what a row pays once a visit (the softmax's reductions
        across lanes, the accumulators' read and write) is paid once a
        slab and not once a tile."""
        if diag:
            return (i + 1) * self.tile_k, i * self.tile_k
        return self.blk_k, self.blk_k

    def blocks(self, T: int, Tk: int) -> Iterator[Tuple[int, int, bool]]:
        """(q block, kv block, on the diagonal) of every block pair a
        call of these lengths computes: the grid steps that are not
        skipped."""
        for bi in range(T // self.blk_q):
            for bj in range(Tk // self.blk_k):
                if not self.causal or bj <= bi:
                    yield bi, bj, self.causal and bj == bi

    def tiles(self, T: int, Tk: int) -> Iterator[Tuple[int, int, bool]]:
        """(q tile, kv tile, masked) of every tile computed, by the
        sequence's own tile numbers: ``blocks`` and ``slab`` composed as
        the kernels compose them."""
        n_q, n_k = self.blk_q // self.tile_q, self.blk_k // self.tile_k
        for bi, bj, diag in self.blocks(T, Tk):
            for i in range(n_q):
                width, clear = self.slab(i, diag)
                for j in range(width // self.tile_k):
                    yield (bi * n_q + i, bj * n_k + j,
                           j * self.tile_k >= clear)


@functools.lru_cache(maxsize=None)
def tile_plan(T: int, Tk: int, D: int, causal: bool) -> TilePlan:
    """The plan for q of ``T`` positions against k/v of ``Tk``, heads of
    ``D`` (after padding).

    Measured on v5e (PR 37; causal forward + backward of one layer at
    the train cell's B=24 T=1024 H=12 D=64, ms; PERF.md section 6):
    q tiles of 256 rows 0.83 + 1.50, of 512 0.74 + 1.73, of 128
    0.97 + 1.58; at D=128 (B=8, H=8) 0.19 + 0.34, 0.19 + 0.38 and
    0.22 + 0.37; the whole square in one tile, as before PR 37,
    1.33 + 3.05 and 0.25 + 0.71. A finer triangle computes less (0.5625,
    0.625, 0.75 of the square), a coarser one pays the row statistics
    and the accumulators' traffic less often: 256 wins by the backward.
    Scored tile by tile in place of slab by slab, 256 took 2.09 + 1.91:
    the time follows the visits a row gets, not the area alone. Blocks
    of 1024 are what a grid step fetches where a sequence is longer
    (T=8192, B=4, H=8: 17.1 ms against 19.0 before)."""
    tile_q, tile_k = _divisor(T, 256), _divisor(Tk, 256)
    blk_q, blk_k = _divisor(T, 1024), _divisor(Tk, 1024)
    if causal:
        # square tiles and square blocks: the diagonal crosses corners
        tile_q = tile_k = min(tile_q, tile_k)
        blk_q = blk_k = min(blk_q, blk_k)
    # One pass holds, for one lane group, q, o, do, dq and k, v, dk, dv
    # whole and double-buffered (counted at float32, the widest they
    # come), the saved logsumexp, and dq, dk, dv as float32 accumulators.
    lane = max(D, _LANES)
    held = (2 * 4 * 4 * (T + Tk) * lane + 2 * 4 * T * _LANES
            + 4 * (T + 2 * Tk) * lane)
    one_pass = held <= _VMEM_BUDGET
    if one_pass:
        blk_q, blk_k = T, Tk
    plan = TilePlan(tile_q, tile_k, blk_q, blk_k, causal, one_pass,
                    0, 0, (T // tile_q) * (Tk // tile_k))
    tiles = list(plan.tiles(T, Tk))
    return plan._replace(visited=len(tiles),
                         masked=sum(m for _, _, m in tiles))


def _interpret() -> bool:
    """Pallas TPU kernels run natively on TPU; everywhere else (the CPU
    test mesh) they run in interpreter mode."""
    return jax.default_backend() != "tpu"


def _diagonal_mask(s):
    """The lower triangle of a square tile on the diagonal: the same for
    every such tile, so no position is added."""
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(col <= row, s, _NEG_INF)


def _folds(scale: float) -> bool:
    """Whether ``scale`` is a power of two: multiplying q by it is then
    exact in any float type, and commutes with every rounding after."""
    return math.frexp(scale)[0] == 0.5


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


def _block_steps(plan: TilePlan, bi, bj, several: bool, compute):
    """Run ``compute(diag)`` for the block pair (bi, bj) of a grid step
    if the plan visits it: (bi, bj) are program ids, so this is
    ``TilePlan.blocks`` as predicates. ``several``: the sequence is more
    than one block, so there are blocks below the diagonal."""
    if not plan.causal:
        compute(False)
        return
    pl.when(bj == bi)(functools.partial(compute, True))
    if several:
        pl.when(bj < bi)(functools.partial(compute, False))


# --------------------------------------------------------------------------
# Forward (packed layout: q/k/v/o are [B, T, C], one program handles
# `npack` heads living in one lane block)
# --------------------------------------------------------------------------

def _scores(q, k, scale: float, clear: int):
    """float32 scores of a q tile against a slab of k; the columns from
    ``clear`` on are a tile on the diagonal."""
    s = _dot(q, k, _NT)
    if not _folds(scale):
        s = s * scale
    if clear == 0:
        return _diagonal_mask(s)
    if clear < s.shape[1]:
        s = jnp.concatenate(
            [s[:, :clear], _diagonal_mask(s[:, clear:])], axis=1)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scr,
                plan: TilePlan, scale: float, num_kv: int, npack: int,
                d: int):
    bi = pl.program_id(2)
    bj = pl.program_id(3)
    tq = plan.tile_q
    heads = [slice(p * d, (p + 1) * d) for p in range(npack)]
    # one kv block: a row is scored in one go, nothing is carried and
    # there is no scratch; else running (m, l, acc) rest in it
    single = num_kv == 1
    if not single:
        m_scr, l_scr, acc_scr = scr

    def _write(rows, ms, ls, accs):
        ls = [jnp.maximum(l, 1e-30) for l in ls]
        o_ref[0, rows, :] = jnp.concatenate(
            [(acc / l).astype(o_ref.dtype) for acc, l in zip(accs, ls)],
            axis=1)
        # Head p's lse lives in lane p of the 128-lane block
        # (npack <= 128 always; readers index [:, p:p+1]).
        lse = jnp.concatenate(
            [m + jnp.log(l) for m, l in zip(ms, ls)], axis=1)
        lse_ref[0, 0, rows, :] = jnp.pad(
            lse, ((0, 0), (0, _LANES - npack)))

    if not single:
        @pl.when(bj == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(diag: bool):
        for i in range(plan.blk_q // tq):
            rows = slice(i * tq, (i + 1) * tq)
            width, clear = plan.slab(i, diag)
            q = q_ref[0, rows, :]              # [tq, npack*d]
            if _folds(scale):
                q = q * scale
            k = k_ref[0, :width, :]            # [width, npack*d]
            v = v_ref[0, :width, :]
            ms, ls, accs = [], [], []
            for p, sl in enumerate(heads):
                s = _scores(q[:, sl], k[:, sl], scale, clear)
                m = jnp.max(s, axis=-1, keepdims=True)
                if not single:
                    m_prev = m_scr[p, rows, :1]
                    m = jnp.maximum(m_prev, m)
                    alpha = jnp.exp(m_prev - m)
                pp = jnp.exp(s - m)            # [tq, width] f32
                l = jnp.sum(pp, -1, keepdims=True)
                acc = _dot(pp.astype(v.dtype), v[:, sl], _NN)
                if not single:
                    l = l_scr[p, rows, :1] * alpha + l
                    acc = acc_scr[p, rows, :] * alpha + acc
                    m_scr[p, rows, :] = jnp.broadcast_to(m, (tq, _LANES))
                    l_scr[p, rows, :] = jnp.broadcast_to(l, (tq, _LANES))
                    acc_scr[p, rows, :] = acc
                ms.append(m), ls.append(l), accs.append(acc)
            if single:
                _write(rows, ms, ls, accs)

    _block_steps(plan, bi, bj, not single, _compute)

    if not single:
        @pl.when(bj == (bi if plan.causal else num_kv - 1))
        def _finalize():
            everything = slice(0, plan.blk_q)
            _write(everything,
                   [m_scr[p, :, :1] for p in range(npack)],
                   [l_scr[p, :, :1] for p in range(npack)],
                   [acc_scr[p] for p in range(npack)])


def _kv_clamped(plan: TilePlan):
    """index_map of a k/v block on a (b, g, q block, kv block) grid: a
    causal call's steps above the diagonal name the diagonal's block
    again, which is already there, so nothing is fetched for them."""
    if plan.causal:
        return lambda b, g, i, j: (b, jnp.minimum(j, i), g)
    return lambda b, g, i, j: (b, j, g)


def _flash_fwd(q, k, v, causal: bool, H: int, D: int,
               scale: float) -> Tuple[jax.Array, jax.Array]:
    """q/k/v: [B, T, C] with C = H*D in packed-lane layout."""
    B, T, C = q.shape
    Tk = k.shape[1]
    npack = _pack_factor(H, D)
    lane_blk = npack * D
    G = H // npack
    plan = tile_plan(T, Tk, D, causal)
    blk_q, blk_k = plan.blk_q, plan.blk_k
    num_kv = Tk // blk_k

    kernel = functools.partial(
        _fwd_kernel, plan=plan, scale=scale, num_kv=num_kv,
        npack=npack, d=D)
    qo_spec = pl.BlockSpec((1, blk_q, lane_blk),
                           lambda b, g, i, j: (b, i, g))
    kv_spec = pl.BlockSpec((1, blk_k, lane_blk), _kv_clamped(plan))
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, G, T // blk_q, num_kv),
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
        scratch_shapes=[] if num_kv == 1 else [
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

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                plan: TilePlan, scale: float, want_dq: bool,
                want_dkv: bool, q_axis: int, num_q: int, num_kv: int,
                npack: int, d: int):
    """Scores each tile of a block pair once and accumulates from it dq
    (``want_dq``: over the kv blocks of a q block, the grid's innermost
    dimension then) and/or dk and dv (``want_dkv``: over the q blocks
    of a kv block). Both at once only where one block is the whole
    sequence. ``rest``: the outputs asked for, then their float32
    accumulators, dq's before dk's and dv's."""
    outs, scrs = iter(rest[:len(rest) // 2]), iter(rest[len(rest) // 2:])
    if want_dq:
        dq_ref, dq_scr = next(outs), next(scrs)
    if want_dkv:
        (dk_ref, dv_ref), (dk_scr, dv_scr) = outs, scrs
    bi = pl.program_id(q_axis)
    bj = pl.program_id(5 - q_axis)
    tq = plan.tile_q
    fold = _folds(scale)
    heads = [slice(p * d, (p + 1) * d) for p in range(npack)]

    if want_dq:
        @pl.when(bj == 0)
        def _init_dq():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    if want_dkv:
        @pl.when(bi == 0)
        def _init_dkv():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(diag: bool):
        for i in range(plan.blk_q // tq):
            rows = slice(i * tq, (i + 1) * tq)
            width, clear = plan.slab(i, diag)
            cols = slice(0, width)
            q = q_ref[0, rows, :]
            if fold:
                q = q * scale
            do = do_ref[0, rows, :]             # bf16: MXU operand
            o = o_ref[0, rows, :]
            lse = lse_ref[0, 0, rows, :]
            k = k_ref[0, cols, :]
            v = v_ref[0, cols, :]
            for p, sl in enumerate(heads):
                # delta = rowsum(do * o), computed here, once a q tile,
                # instead of a separate HBM pass.
                delta = jnp.sum(do[:, sl].astype(jnp.float32) *
                                o[:, sl].astype(jnp.float32),
                                axis=-1, keepdims=True)
                s = _scores(q[:, sl], k[:, sl], scale, clear)
                pp = jnp.exp(s - lse[:, p:p + 1])   # [tq, width] f32
                dp = _dot(do[:, sl], v[:, sl], _NT)
                ds = (pp * (dp - delta)).astype(q.dtype)
                if want_dkv:
                    # dv += p^T do, dk += ds^T q — bf16 operands, fp32
                    # accumulation.
                    dv_scr[p, cols, :] += _dot(
                        pp.astype(do.dtype), do[:, sl], _TN)
                    dk_scr[p, cols, :] += _dot(ds, q[:, sl], _TN)
                if want_dq:
                    dq_scr[p, rows, :] += _dot(ds, k[:, sl], _NN)

    _block_steps(plan, bi, bj, num_kv > 1, _compute)

    # q was scaled, or the scores were: dq lacks the factor either way,
    # dk only in the second case.
    if want_dq:
        @pl.when(bj == (bi if plan.causal else num_kv - 1))
        def _finalize_dq():
            dq_ref[0] = jnp.concatenate(
                [(dq_scr[p] * scale).astype(dq_ref.dtype)
                 for p in range(npack)], axis=1)

    if want_dkv:
        @pl.when(bi == num_q - 1)
        def _finalize_dkv():
            dk_ref[0] = jnp.concatenate(
                [(dk_scr[p] * (1.0 if fold else scale)).astype(
                    dk_ref.dtype) for p in range(npack)], axis=1)
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
    plan = tile_plan(T, Tk, D, causal)
    blk_q, blk_k = plan.blk_q, plan.blk_k
    num_q, num_kv = T // blk_q, Tk // blk_k

    def call(name, want_dq, want_dkv, q_axis, q_map, k_map):
        """One run of _bwd_kernel on a (B, G, ., .) grid whose
        dimension ``q_axis`` counts q blocks and whose other counts kv
        blocks, the second of the two being the one accumulated over."""
        q_spec = pl.BlockSpec((1, blk_q, lane_blk), q_map)
        k_spec = pl.BlockSpec((1, blk_k, lane_blk), k_map)
        lse_spec = pl.BlockSpec(
            (1, 1, blk_q, _LANES),
            lambda *ids: (ids[0], ids[1], q_map(*ids)[1], 0))
        dq_out = [(q_spec, jax.ShapeDtypeStruct((B, T, C), q.dtype),
                   pltpu.VMEM((npack, blk_q, D), jnp.float32))]
        dkv_out = [(k_spec, jax.ShapeDtypeStruct((B, Tk, C), x.dtype),
                    pltpu.VMEM((npack, blk_k, D), jnp.float32))
                   for x in (k, v)]
        outs = dq_out * want_dq + dkv_out * want_dkv
        grid = (B, G, num_q, num_kv) if q_axis == 2 else \
            (B, G, num_kv, num_q)
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel, plan=plan, scale=scale, want_dq=want_dq,
                want_dkv=want_dkv, q_axis=q_axis, num_q=num_q,
                num_kv=num_kv, npack=npack, d=D),
            grid=grid,
            in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, lse_spec],
            out_specs=[spec for spec, _, _ in outs],
            out_shape=[shape for _, shape, _ in outs],
            scratch_shapes=[scr for _, _, scr in outs],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=_interpret(),
            name=name,
        )(q, k, v, o, do, lse)

    def q_major(b, g, i, j):
        return b, i, g

    if plan.one_pass:
        # one block is the whole sequence: a (B, G, 1, 1) grid
        return tuple(call("flash_bwd", True, True, 2, q_major,
                          lambda b, g, i, j: (b, j, g)))
    dq, = call("flash_bwd_dq", True, False, 2, q_major,
               _kv_clamped(plan))
    # dk/dv: kv blocks in the third slot, q blocks innermost; a causal
    # call's steps above the diagonal (q block < kv block) stay on the
    # diagonal's q block.
    dk, dv = call(
        "flash_bwd_dkv", False, True, 3,
        (lambda b, g, j, i: (b, jnp.maximum(i, j), g)) if causal else
        (lambda b, g, j, i: (b, i, g)),
        lambda b, g, j, i: (b, j, g))
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
