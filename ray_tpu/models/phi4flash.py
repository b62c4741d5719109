"""Phi-4-mini-flash (microsoft, ``model_type: phi4flash``): the SambaY
decoder-hybrid-decoder (arXiv:2507.06607) with differential attention
(arXiv:2410.05258). Of N layers (32 published), by index ``l``:

- ``l`` even, ``l <= N/2``: a SELECTIVE STATE-SPACE layer
  (``SelectiveSSM``: Mamba-1's, ops/selective_scan.py), whose state is a
  fixed-size float32 ``[d_state, d_inner]`` a SLOT of the serving engine
  beside the last ``d_conv - 1`` inputs of its convolution (models/
  kv_cache.py ``RecurrentState``: the second rule behind that shape).
  Layer ``N/2`` PUBLISHES its scan's output ``m`` (before the gate,
  ``D u`` included) for the gated memory units of the same call;
- ``l`` odd, ``l < N/2``: differential attention under a SLIDING WINDOW,
  a ring a slot (``SlidingRing``);
- ``l = N/2 + 1``: differential attention over the WHOLE context, the
  one layer that keeps K/V pages, and publishes them after its append;
- ``l`` odd, ``l > N/2 + 1``: CROSS attention, a query only, over that
  layer's pages (``KIND_BORROWED``: no entry of its own);
- ``l`` even, ``l > N/2``: a GATED MEMORY UNIT, ``(m * SiLU(x W_1))
  W_2`` with ``m`` layer ``N/2``'s at the same position
  (``KIND_STATELESS``: nothing kept, nothing read).

Every block is pre-norm with LayerNorm (scale and bias), every
feed-forward ``LlamaMLP``, no position encoding anywhere, the head tied.
The residual stream is float32 between the blocks; every matmul reads
and writes ``cfg.dtype``.

DIFFERENTIAL ATTENTION, AS STORED. The published 40 query / 20 K/V
heads of 64 are taken in pairs. K and V are kept as ``n_kv_heads`` = 10
heads of ``head_dim`` = 128, a pair side by side (``[k1 | k2]``, ``[v1 |
v2]``: a whole lane tile where a head of 64 is half of one), and query
pair i becomes TWO query rows of 128, ``[q1_i | 0]`` and ``[0 | q2_i]``:
against ``[k1 | k2]`` the first scores ``q1 . k1`` and the second ``q2 .
k2`` exactly, and each reads the whole 128-wide value under its own
softmax. That is plain grouped-query attention of ``n_heads`` = 40 rows
on 10 K/V heads (a group is pairs 2g, 2g+1: rows ``A_2g, A_2g+1, B_2g,
B_2g+1``), so the kernels the other families attend through serve it
unchanged (ops/ring_window_attention.py, ops/paged_decode_attention.py,
ops/paged_attention.py); ``diff_merge`` then takes ``A_i - lambda
B_i``, the RMSNorm of 128 and ``1 - lambda0``. The scores' scale is the
published head's, 1/8: handed to the page window as such, and to the
ring (which knows ``1 / sqrt(head_dim)`` only) as ``q x sqrt(2)``.
What the chip's tiles ask beside that is padding, zeros that score and
read zeros: a page stores 16 head rows a token (``kv_page_heads``: ten
are no whole sublane tile, and the compiler's way around that is a pool
no page can be read from as it lies), whole groups of zero query rows
riding them, and a sliding layer's decode step hands its kernel groups
of eight rows (80: whole sublane tiles).

benchmarks/reference/phi4flash.py has the equations, and says which of
them ``config.json`` leaves open (assumed).

The model runs through ``transformer_forward`` as the other families do
(the full forward pass without a cache; the serving engine's paged
path), with ``publishes``: what a block hands later blocks of the same
call rides a dict, never a cache. The static-cache ``generate`` of
models/llama.py does not serve it.

THE CROSS-DECODER'S LINEAR-TIME PREFILL IS TAKEN. Layers ``N/2 + 2``
onward (memory units and cross layers alternating: 14 of the published
32) keep nothing, so nothing they compute at a prompt position is ever
read but the logits of the position a row samples from. A paged prefill
call (``logits_at``) therefore runs them at that ONE position a row:
``transformer_forward`` narrows the call before the first of them
(models/kv_cache.py ``sampled_only_from``, read from ``layer_kinds``,
not from this model's name), ``m`` with it (``PUBLISHES``), and a cross
layer is then handed one query a row over the full layer's pages, all
of the call's positions appended: a decode step's shape, and on one
TPU a decode step's kernel. The self-decoder (layers 0 to ``N/2 + 1``)
sees every position, as it must: its states, rings and pages are read
by later calls.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kv_cache import (KIND_BORROWED, KIND_KV, KIND_RECURRENT,
                                     KIND_SLIDING, KIND_STATELESS,
                                     PagedKVLayer, RecurrentStateView,
                                     SlidingRingView)
from ray_tpu.models.llama import LlamaMLP, block_forward, transformer_forward
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)
from ray_tpu.ops.ring_window_attention import ring_window_attention
from ray_tpu.ops.selective_scan import ssm_chunked, ssm_step

SSM, SLIDING, FULL, CROSS, GMU = "ssm", "sliding", "full", "cross", "gmu"
_KINDS = {SSM: KIND_RECURRENT, SLIDING: KIND_SLIDING, FULL: KIND_KV,
          CROSS: KIND_BORROWED, GMU: KIND_STATELESS}
# what ``transformer_forward``'s dict carries between blocks of a call,
# and of each key whether its value is laid out BY POSITION: ``m`` is
# ``[B, T, d_inner]``, a memory unit reads its own position's, and a
# call narrowed to its sampled positions narrows it too; the full
# layer's pages (or, without a cache, its keys of the whole sequence)
# stand for the whole call and are handed on as they are
MEMORY, SHARED = "memory", "shared"
PUBLISHES = {MEMORY: True, SHARED: False}


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The published sizes (Phi-4-mini-flash-reasoning). ``attn_heads``
    / ``attn_kv_heads`` are the published query and K/V heads (of ``dim
    / attn_heads``); ``n_heads`` / ``n_kv_heads`` / ``head_dim`` are
    what the shared modules and the pool read: the PAIRED layout (the
    module's docstring)."""
    vocab_size: int = 200064
    max_seq_len: int = 262144
    dim: int = 2560
    n_layers: int = 32
    attn_heads: int = 40
    attn_kv_heads: int = 20
    hidden_dim: int = 10240
    mb_per_layer: int = 2
    sliding_window: int = 512
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 160
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if self.mb_per_layer != 2 or self.n_layers % 4 or self.n_layers < 8:
            raise ValueError(
                f"the layout is written for mb_per_layer 2 and a depth "
                f"of whole fours, at least 8; got {self.mb_per_layer} "
                f"and {self.n_layers}")
        if self.attn_heads % (2 * self.attn_kv_heads) \
                or self.attn_kv_heads % 2 or self.dim % self.attn_heads:
            raise ValueError(
                f"differential attention pairs the heads: {self.attn_heads} "
                f"query heads on {self.attn_kv_heads} K/V heads of a "
                f"model of {self.dim} leave no whole pairs and groups")

    # ---- what the shared modules and the pool read (the paired layout)
    @property
    def n_heads(self) -> int:
        """Query ROWS a layer's attention is handed: a pair's two."""
        return self.attn_heads

    @property
    def n_kv_heads(self) -> int:
        return self.attn_kv_heads // 2

    @property
    def head_dim(self) -> int:
        return 2 * (self.dim // self.attn_heads)

    @property
    def kv_page_heads(self) -> int:
        """Head rows a K/V page stores a token (models/kv_cache.py
        ``kv_page_heads`` says why): the pairs rounded up to whole
        16-row tiles of a bfloat16 array, 10 as 16, the rest zeros."""
        return -(-self.n_kv_heads // 16) * 16

    @property
    def query_heads_by_kind(self) -> Dict[str, int]:
        """The query rows a layer of each kind hands its DECODE kernel
        (models/kv_cache.py ``kv_query_heads``): a sliding layer pads a
        group's four rows to eight, whole sublane tiles over the ring's
        own (unpadded) heads; a paged layer's groups of four ride the
        page's padded head rows."""
        return {KIND_SLIDING: 2 * self.n_heads, KIND_KV: self.n_heads}

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.dim

    @property
    def mixers(self) -> Tuple[str, ...]:
        """Each layer's mixer (the module's docstring)."""
        half = self.n_layers // 2

        def of(l):
            if l % 2 == 0:
                return SSM if l <= half else GMU
            return SLIDING if l < half else FULL if l == half + 1 else CROSS
        return tuple(of(l) for l in range(self.n_layers))

    @property
    def memory_layer(self) -> int:
        """The state-space layer whose output the memory units read."""
        return self.n_layers // 2

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind of per-request state (models/kv_cache.py)."""
        return tuple(_KINDS[m] for m in self.mixers)

    @property
    def recurrent_state_shape(self) -> Tuple[int, int]:
        """One slot's state, a state-space layer: the states on the
        sublanes, the channels on the lanes."""
        return (self.ssm_state, self.d_inner)

    @property
    def recurrent_conv_shape(self) -> Tuple[int, int]:
        return (self.ssm_conv - 1, self.d_inner)

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return Phi4Flash


def phi4_mini_flash(**overrides) -> Phi4FlashConfig:
    return Phi4FlashConfig(**overrides)


def phi4flash_tiny(**overrides) -> Phi4FlashConfig:
    """Test size: 8 layers that keep every kind and the boundaries
    (state-space 0, 2, 4; sliding 1, 3; full 5; memory unit 6; cross 7),
    8 query / 4 K/V heads of 8 (two pairs of 16 stored), a window of 8,
    8 states on 96 channels."""
    d = dict(vocab_size=256, max_seq_len=512, dim=64, n_layers=8,
             attn_heads=8, attn_kv_heads=4, hidden_dim=96,
             sliding_window=8, ssm_state=8, ssm_expand=2, ssm_dt_rank=6)
    d.update(overrides)
    return Phi4FlashConfig(**d)


def lambda_init(layer: int) -> float:
    """``lambda0`` of layer ``layer`` (0-based): 0.8 - 0.6 exp(-0.3 l)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class LayerNorm(nn.Module):
    """LayerNorm with a scale and a bias, in float32 (``RMSNorm``'s
    signature: ``block_forward`` and ``transformer_forward`` build it).
    The two moments are taken in ONE pass (``E[x^2] - E[x]^2``, float32,
    clamped at 0): two sums over the same input fuse, where the variance
    of ``x - mean`` waits for the mean (a decode step's 65 norms were
    260 small fusions, a tenth of the step on the chip: PERF.md section
    6, PR 60)."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                          jnp.float32)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.maximum(
            jnp.mean(xf * xf, axis=-1, keepdims=True) - mean * mean, 0.0)
        return ((xf - mean) * jax.lax.rsqrt(var + self.eps) * scale
                + bias).astype(x.dtype)


class SelectiveSSM(nn.Module):
    """One state-space layer's token mixing on x [B, T, D]. ``kv_cache``
    is None (a whole sequence from an empty state) or the layer's
    ``RecurrentStateView``, read as models/olmo_hybrid.py's
    ``GatedDeltaNet`` reads it: a row whose ``cache_len`` is 0 and whose
    first position is real STARTS A REQUEST and begins from zeros,
    whatever its slot held; positions that are not real move neither
    the state nor the tail. Returns (out, the new cache, ``m``: the
    scan's output [B, T, d_inner] before the gate)."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, kv_cache=None, cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        C, N, K, R = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        f32 = jnp.float32
        u, z = jnp.split(dense(2 * C, name="w_in")(x), 2, axis=-1)

        rc = kv_cache
        if rc is None:
            valid, fresh = jnp.ones((B, T), bool), None
            state = jnp.zeros((B, N, C), f32)
            tail = jnp.zeros((B, K - 1, C), cfg.dtype)
        else:
            if not isinstance(rc, RecurrentStateView):
                raise TypeError(
                    f"a state-space layer keeps a recurrent state, not "
                    f"{type(rc).__name__}: only the paged engine and the "
                    f"cache-less forward pass serve this model")
            valid = rc.valid
            fresh = (cache_len == 0) & valid[:, 0]
            with jax.named_scope("ssm_conv"):
                tail = jnp.where(fresh[:, None, None], 0, rc.take(rc.conv))
            with jax.named_scope("ssm_scan"):
                state = rc.take(rc.state)
                if T > 1:
                    # one token's step resets a fresh row itself, in
                    # the one pass it makes over the state
                    state = jnp.where(fresh[:, None, None], 0.0, state)

        with jax.named_scope("ssm_conv"):
            conv = self.param("conv", nn.initializers.normal(K ** -0.5),
                              (K, C), cfg.param_dtype)
            conv_bias = self.param("conv_bias", nn.initializers.zeros,
                                   (C,), cfg.param_dtype)
            before = jnp.concatenate([tail.astype(cfg.dtype), u], axis=1)
            wide = before.astype(f32)
            u = jax.nn.silu(sum(wide[:, j:j + T] * conv[j].astype(f32)
                                for j in range(K))
                            + conv_bias.astype(f32)).astype(cfg.dtype)
            # the last K-1 inputs up to each row's last real position
            # (real positions are a row's first ones)
            if T == 1:
                tail = jnp.where(valid[:, :, None], before[:, 1:],
                                 before[:, :-1])
            else:
                n_real = jnp.sum(valid, axis=1, dtype=jnp.int32)
                tail = jax.vmap(
                    lambda row, n: jax.lax.dynamic_slice_in_dim(
                        row, n, K - 1, axis=0))(before, n_real)
        with jax.named_scope("ssm_gates"):
            dbc = dense(R + 2 * N, name="w_x")(u)
            d, Bm, Cm = jnp.split(dbc, (R, R + N), axis=-1)
            dt_bias = self.param("dt_bias", nn.initializers.constant(-4.6),
                                 (C,), f32)
            delta = jax.nn.softplus(
                dense(C, name="w_dt")(d).astype(f32) + dt_bias)
            # [N, C]: the states on the sublanes, as the state is kept
            A_log = self.param(
                "A_log", lambda key, shape, dtype: jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype)
                            )[:, None], shape), (N, C), f32)
            A = -jnp.exp(A_log)
            D = self.param("D", nn.initializers.ones, (C,), f32)
        with jax.named_scope("ssm_scan"):
            if T == 1:
                y, state = ssm_step(u[:, 0], delta[:, 0], A, Bm[:, 0],
                                    Cm[:, 0], D, state, valid[:, 0], fresh)
                y = y[:, None]
            else:
                y, state = ssm_chunked(u, delta, A, Bm, Cm, D, state, valid)
        with jax.named_scope("ssm_out"):
            m = y.astype(cfg.dtype)
            gated = (y * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
            out = dense(cfg.dim, name="wo")(gated)
        if rc is None:
            return out, None, m
        with jax.named_scope("ssm_scan"):
            new_state = rc.put(rc.state, state)
        with jax.named_scope("ssm_conv"):
            new_conv = rc.put(rc.conv, tail)
        return out, rc._replace(state=new_state, conv=new_conv), m


class DiffAttention(nn.Module):
    """One layer's differential attention on x [B, T, D] (already
    normed), of ``mixer`` ``SLIDING`` (its ``SlidingRingView``), ``FULL``
    (its ``PagedKVLayer``: appended, attended, returned for the cross
    layers) or ``CROSS`` (no key or value of its own: ``shared`` is the
    full layer's ``PagedKVLayer`` after its append, or its (k, v) of the
    whole sequence where nothing is cached). Returns (out, the new
    cache, what the cross layers read)."""
    config: Phi4FlashConfig
    mixer: str = FULL
    layer: int = 0

    @nn.compact
    def __call__(self, x, kv_cache=None, cache_len=None, shared=None):
        cfg = self.config
        B, T, _ = x.shape
        H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        half, rep = hd // 2, cfg.n_heads // cfg.n_kv_heads
        dense = functools.partial(nn.Dense, use_bias=True, dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        f32 = jnp.float32
        q = dense(H * half, name="wq")(x)
        if self.mixer != CROSS:
            # a pair side by side is two neighbouring published heads
            k = dense(KH * hd, name="wk")(x).reshape(B, T, KH, hd)
            v = dense(KH * hd, name="wv")(x).reshape(B, T, KH, hd)

        def rows(scale=1.0, group=rep):
            """The query rows [B, T, KH x group, hd]: a K/V pair's two
            query pairs as ``[q1 | 0]`` twice, then ``[0 | q2]`` twice,
            then zeros up to ``group`` rows."""
            qp = q.reshape(B, T, KH, rep // 2, 2, half)
            if scale != 1.0:
                qp = (qp.astype(f32) * scale).astype(q.dtype)
            zero = jnp.zeros_like(qp[..., 0, :])
            a = jnp.concatenate([qp[..., 0, :], zero], axis=-1)
            b = jnp.concatenate([zero, qp[..., 1, :]], axis=-1)
            r = jnp.concatenate([a, b], axis=3)       # [B, T, KH, rep, hd]
            if group > rep:
                r = jnp.pad(r, ((0, 0),) * 3 + ((0, group - rep), (0, 0)))
            return r.reshape(B, T, KH * group, hd)

        new_cache = None
        cached = kv_cache is not None if self.mixer != CROSS else \
            isinstance(shared, PagedKVLayer)
        if not cached:
            # the whole sequence at once: one softmax a row under the
            # layer's mask
            if self.mixer == CROSS:
                k, v = shared
            else:
                shared = (k, v)
            qg = rows().reshape(B, T, KH, rep, hd)
            i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
            seen = j <= i
            if self.mixer == SLIDING:
                seen &= j > i - cfg.sliding_window
            with jax.named_scope("attn_scores"):
                s = jnp.einsum("btkrd,bskd->bkrts", qg, k,
                               preferred_element_type=f32) * half ** -0.5
                p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            with jax.named_scope("attn_pv"):
                y = jnp.einsum("bkrts,bskd->btkrd", p.astype(v.dtype), v)
        elif self.mixer == SLIDING:
            if not isinstance(kv_cache, SlidingRingView):
                raise TypeError(
                    f"a sliding-window layer keeps a ring a slot, not "
                    f"{type(kv_cache).__name__}: only the paged engine "
                    f"and the cache-less forward pass serve this model")
            rc = kv_cache
            # the ring scores by 1 / sqrt(hd); a decode step's groups
            # are whole sublane tiles of rows
            group = rep if T > 1 else cfg.query_heads_by_kind[
                KIND_SLIDING] // KH
            with jax.named_scope("attn_sliding"):
                y, rk, rv = ring_window_attention(
                    rows(2.0 ** 0.5, group), k, v, rc.k, rc.v, rc.slots,
                    cache_len, rc.valid, cfg.sliding_window)
            y = y.reshape(B, T, KH, group, hd)[:, :, :, :rep]
            new_cache = rc._replace(k=rk, v=rv)
        else:
            pc = kv_cache if self.mixer == FULL else shared
            if not (isinstance(pc, PagedKVLayer) and pc.pages_v is not None
                    and not pc.quantized):
                raise TypeError(
                    f"the full-attention layer keeps K/V pages in the "
                    f"model's type, not {type(pc).__name__}")
            # the pages' head rows past KH are zeros, with zero query
            # groups of their own
            pad = pc.pages_k.shape[-2] - KH

            def padded(a, rows=pad):
                return jnp.pad(a, ((0, 0), (0, 0), (0, rows), (0, 0)))
            with jax.named_scope("attn_shared"):
                if self.mixer == FULL:
                    with jax.named_scope("kv_append"):
                        pk, pv = paged_append(
                            pc.pages_k, pc.pages_v, pc.page_table,
                            cache_len, padded(k), padded(v))
                    pc = new_cache = shared = pc._replace(pages_k=pk,
                                                          pages_v=pv)
                y = _paged_window_attention(
                    padded(rows(), pad * rep), pc.pages_k, pc.pages_v,
                    None, None, pc.page_table, cache_len,
                    softmax_scale=half ** -0.5)[:, :, :H]
        with jax.named_scope("diff_merge"):
            y = y.reshape(B, T, KH, rep, hd).astype(f32)
            l0 = lambda_init(self.layer)
            lq1, lk1, lq2, lk2 = (
                self.param(n, nn.initializers.normal(0.1), (half,), f32)
                for n in ("lambda_q1", "lambda_k1", "lambda_q2",
                          "lambda_k2"))
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(
                jnp.sum(lq2 * lk2)) + l0
            o = y[..., :rep // 2, :] - lam * y[..., rep // 2:, :]
            scale = self.param("subln", nn.initializers.ones, (hd,), f32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.norm_eps) * scale * (1.0 - l0)
            o = o.reshape(B, T, H * half).astype(cfg.dtype)
        return dense(cfg.dim, name="wo")(o), new_cache, shared


class GatedMemoryUnit(nn.Module):
    """``(m * SiLU(x W_1)) W_2`` on x [B, T, D] (already normed), ``m``
    [B, T, d_inner] the memory layer's at the same positions."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, m):
        cfg = self.config
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(dense(cfg.d_inner, name="w1")(x).astype(
                jnp.float32))
            return dense(cfg.dim, name="w2")(
                (m.astype(jnp.float32) * gate).astype(cfg.dtype))


class Phi4FlashBlock(nn.Module):
    """Layer ``index``'s block: its mixer by ``cfg.mixers``, then the
    SwiGLU, pre-norm with LayerNorm. Takes and returns what the blocks
    of one call publish (``transformer_forward``'s ``publishes``)."""
    config: Phi4FlashConfig
    index: int = 0

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None, cache_len=None,
                 published=None):
        cfg = self.config
        mixer = cfg.mixers[self.index]
        out = dict(published or {})

        def attention(h, _freqs, _positions, cache, start):
            if mixer == SSM:
                y, new, m = SelectiveSSM(cfg, name="attention")(
                    h, cache, start)
                if self.index == cfg.memory_layer:
                    out[MEMORY] = m
                return y, new
            if mixer == GMU:
                return GatedMemoryUnit(cfg, name="attention")(
                    h, out[MEMORY]), ()
            y, new, shared = DiffAttention(
                cfg, mixer, self.index, name="attention")(
                    h, cache or None, start, out.get(SHARED))
            if mixer == FULL:
                out[SHARED] = shared
            return y, () if mixer == CROSS else new
        # THE RESIDUAL STREAM IS FLOAT32 (the matmuls read and write
        # cfg.dtype; models/ouro.py has the precedent): 32 layers add 64
        # unit-size branches to it, and with every add rounded to
        # bfloat16 the served tokens lay up to 0.94 of the harness's
        # tolerance under the float32 reference's best on the chip
        # (0.20 at the same seed with the stream in float32: PERF.md
        # section 6, PR 60); it is [rows, dim] floats, nothing beside a
        # layer's weights
        x, new_cache = block_forward(
            cfg, attention, LlamaMLP(cfg, name="feed_forward"),
            x.astype(jnp.float32), freqs, positions, kv_cache, cache_len,
            norm=LayerNorm)
        return x, new_cache, out


class Phi4Flash(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``RecurrentStateView`` for a state-space layer, a ``SlidingRingView``
    for a sliding one, a ``PagedKVLayer`` for the full one and the empty
    tuple for the layers that keep nothing (models/kv_cache.py
    ``kv_layer_view``)."""
    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        return transformer_forward(
            self, self.config,
            lambda i: functools.partial(Phi4FlashBlock, index=i),
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at, norm=LayerNorm, publishes=PUBLISHES)


def ssm_param_count(cfg: Phi4FlashConfig) -> int:
    """One state-space layer's mixer: the input and output projections,
    the convolution and its bias, the x and dt projections and dt's
    bias, A and D."""
    D, C, N, R = cfg.dim, cfg.d_inner, cfg.ssm_state, cfg.ssm_dt_rank
    return (D * 2 * C + C * D + (cfg.ssm_conv + 1) * C + C * (R + 2 * N)
            + R * C + C + N * C + C)


def attention_param_count(cfg: Phi4FlashConfig, mixer: str) -> int:
    """A differential-attention layer's mixer: the projections it has
    (a cross layer has no K or V) with their biases, the four lambda
    vectors and the sub-norm's scale."""
    D, hd = cfg.dim, cfg.head_dim
    wq, wkv = cfg.n_heads * hd // 2, cfg.n_kv_heads * hd
    n = D * wq + wq + wq * D + D + 4 * (hd // 2) + hd
    return n if mixer == CROSS else n + 2 * (D * wkv + wkv)


def phi4flash_param_count(cfg: Phi4FlashConfig) -> int:
    D = cfg.dim
    of = {SSM: ssm_param_count(cfg), GMU: 2 * D * cfg.d_inner}
    of.update({m: attention_param_count(cfg, m)
               for m in (SLIDING, FULL, CROSS)})
    # every block: the SwiGLU and two LayerNorms; the embedding (tied)
    # and the final LayerNorm
    return (sum(of[m] for m in cfg.mixers)
            + cfg.n_layers * (3 * D * cfg.hidden_dim + 4 * D)
            + cfg.vocab_size * D + 2 * D)
