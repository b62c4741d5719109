"""Granite-4.0-H (ibm-granite, ``model_type: granitemoehybrid``): a
hybrid decoder of Mamba-2 layers with one grouped-query attention layer
in ten, and a sparse mixture beside a shared SwiGLU behind EVERY layer.

- ``layer_types[l] == "mamba"`` (nine of ten): ``Mamba2Mixer`` below, a
  state-space layer under ONE SCALAR DECAY A HEAD (ops/ssd.py: the third
  rule behind ``RecurrentState``'s shape). It keeps no K/V: its state is
  a fixed-size float32 ``[heads, head_dim, d_state]`` a SLOT of the
  serving engine beside the last ``d_conv - 1`` inputs of its
  convolution (models/kv_cache.py ``RecurrentState``), whatever the
  context's length.
- ``"attention"``: causal grouped-query softmax attention with NO
  position encoding and a published softmax scale that is NOT ``1 /
  sqrt(head_dim)`` (``attention_multiplier``), over K/V pages
  (``NoPEAttention`` below: ops/paged_attention.py's append and page
  window, handed ``softmax_scale``).
- The feed-forward of every layer is ``models/mixtral.py``'s
  ``MoEFeedForward``: a softmax router whose chosen gates are
  renormalised (a softmax over the chosen logits), the shared SwiGLU as
  ``n_shared_experts`` experts' width, and (where the config says so)
  only a share ``experts_held`` of the router's experts, one chip's of
  an expert-parallel group: the first mixture behind a layer that keeps
  a recurrent state and no pages.
- Four published scalars: the embedding's rows times
  ``embedding_multiplier``, each branch times ``residual_multiplier``
  before it joins the stream, the attention's scores times
  ``attention_multiplier``, the logits over ``logits_scaling``.

benchmarks/reference/granite_hybrid.py has the equations, token by
token, and says which of them ``config.json`` leaves open (assumed).

The model runs through ``transformer_forward`` as the other families do
(the full forward pass without a cache; the serving engine's paged
path). The static-cache ``generate`` of models/llama.py knows only K/V
caches and does not serve it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kv_cache import (KIND_KV, KIND_RECURRENT, PagedKVLayer,
                                     RecurrentStateView, decay_log_init,
                                     live_rows)
from ray_tpu.models.llama import RMSNorm, block_forward, transformer_forward
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)
from ray_tpu.ops.ssd import ssd_chunked, ssd_step

MAMBA, ATTENTION = "mamba", "attention"
_KINDS = {MAMBA: KIND_RECURRENT, ATTENTION: KIND_KV}
# the published period: the attention layer is the sixth of ten
_PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published sizes (Granite-4.0-H-Small) under the names the
    shared modules read: ``hidden_dim`` is ONE routed expert's width,
    ``num_experts`` the router's width, ``n_shared_experts`` the shared
    SwiGLU's width in experts (1,536 = 2 x 768)."""
    vocab_size: int = 100352
    max_seq_len: int = 131072
    dim: int = 4096
    n_layers: int = 40
    # each layer's type as published; entries past ``n_layers`` name
    # layers a cut lacks
    layer_types: Tuple[str, ...] = _PERIOD * 4
    n_heads: int = 32              # the attention layers' (GQA)
    n_kv_heads: int = 8
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1
    mamba_conv: int = 4
    # positions a chunk of the recurrence solves at once by matrix
    # products (ops/ssd.py); the published value is also the engine's
    # prefill chunk
    mamba_chunk: int = 256
    hidden_dim: int = 768
    num_experts: int = 72
    num_experts_per_tok: int = 10
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    router: str = "softmax"
    experts_held: Optional[Tuple[int, int]] = None
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if self.mamba_groups != 1:
            raise ValueError(
                f"the mixer is written for ONE group of B and C (every "
                f"head reads the same); got mamba_groups "
                f"{self.mamba_groups}")
        types = self.layer_types[:self.n_layers]
        if len(types) < self.n_layers or set(types) - set(_KINDS):
            raise ValueError(
                f"layer_types names {len(types)} of {self.n_layers} "
                f"layers, each to be one of {sorted(_KINDS)}")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: x, then B and C."""
        return self.d_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind of per-request state (models/kv_cache.py)."""
        return tuple(_KINDS[t] for t in self.layer_types[:self.n_layers])

    @property
    def recurrent_state_shape(self) -> Tuple[int, int, int]:
        """One slot's state, a Mamba-2 layer: [H, P, N], the STATES
        minor. N = 128 is one whole lane tile and a head's P = 64
        channels eight sublane tiles, so the chip keeps the 4 MiB as
        they are; with the channels minor a head's 64 would be padded to
        128 lanes, twice the bytes kept and moved a step."""
        return (self.mamba_heads, self.mamba_head_dim, self.mamba_state)

    @property
    def recurrent_conv_shape(self) -> Tuple[int, int]:
        """One slot's convolution tail, a Mamba-2 layer: the last
        ``mamba_conv - 1`` inputs of x, B and C."""
        return (self.mamba_conv - 1, self.conv_width)

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return GraniteHybrid


def granite_hybrid_tiny(**overrides) -> GraniteHybridConfig:
    """Test size: one period of ten layers (the attention layer the
    sixth); 4 heads of 8 x 16 states over a chunk of 8; 4 query heads on
    2 K/V heads of 16; 8 experts of 32 of which 3 a token, the shared
    SwiGLU two experts wide."""
    d = dict(vocab_size=256, max_seq_len=512, dim=64, n_layers=10,
             layer_types=_PERIOD, n_heads=4, n_kv_heads=2, mamba_heads=4,
             mamba_head_dim=8, mamba_state=16, mamba_chunk=8,
             hidden_dim=32, num_experts=8, num_experts_per_tok=3)
    d.update(overrides)
    return GraniteHybridConfig(**d)


class Mamba2Mixer(nn.Module):
    """One Mamba-2 layer's token mixing on x [B, T, D] (already normed).
    ``kv_cache`` is None (a whole sequence from an empty state) or the
    layer's ``RecurrentStateView``, read as models/phi4flash.py's
    ``SelectiveSSM`` reads it: a row whose ``cache_len`` is 0 and whose
    first position is real STARTS A REQUEST and begins from zeros,
    whatever its slot held; positions that are not real move neither the
    state nor the tail. The scopes are that module's too (the trace
    readers find the recurrence by them); the chunked form names
    ``ssd_intra`` and ``ssd_carry`` inside ``ssm_scan``."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        H, P, N = cfg.recurrent_state_shape
        C, W, K = cfg.d_inner, cfg.conv_width, cfg.mamba_conv
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        f32 = jnp.float32
        z, xbc, dt = jnp.split(dense(C + W + H, name="w_in")(x),
                               (C, C + W), axis=-1)

        rc = kv_cache
        if rc is None:
            valid, fresh = jnp.ones((B, T), bool), None
            state = jnp.zeros((B, H, P, N), f32)
            tail = jnp.zeros((B, K - 1, W), cfg.dtype)
        else:
            if not isinstance(rc, RecurrentStateView):
                raise TypeError(
                    f"a Mamba-2 layer keeps a recurrent state, not "
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
                    state = jnp.where(fresh[:, None, None, None], 0.0,
                                      state)

        with jax.named_scope("ssm_conv"):
            conv = self.param("conv", nn.initializers.normal(K ** -0.5),
                              (K, W), cfg.param_dtype)
            conv_bias = self.param("conv_bias", nn.initializers.zeros,
                                   (W,), cfg.param_dtype)
            before = jnp.concatenate([tail.astype(cfg.dtype), xbc], axis=1)
            wide = before.astype(f32)
            xbc = jax.nn.silu(sum(wide[:, j:j + T] * conv[j].astype(f32)
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
            u, Bm, Cm = jnp.split(xbc, (C, C + N), axis=-1)
            u = u.reshape(B, T, H, P)
        with jax.named_scope("ssm_gates"):
            dt_bias = self.param("dt_bias", nn.initializers.constant(-4.6),
                                 (H,), f32)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
            A = -jnp.exp(self.param("A_log", decay_log_init, (H,), f32))
            D = self.param("D", nn.initializers.ones, (H,), f32)
        with jax.named_scope("ssm_scan"):
            if T == 1:
                y, state = ssd_step(u[:, 0], dt[:, 0], A, Bm[:, 0],
                                    Cm[:, 0], D, state, valid[:, 0], fresh)
                y = y[:, None]
            else:
                y, state = ssd_chunked(u, dt, A, Bm, Cm, D, state, valid,
                                       cfg.mamba_chunk)
        with jax.named_scope("ssm_out"):
            # the gate BEFORE the norm, and the norm over all channels
            gated = y.reshape(B, T, C) * jax.nn.silu(z.astype(f32))
            out = dense(cfg.dim, name="wo")(
                RMSNorm(cfg.norm_eps, name="o_norm")(gated).astype(
                    cfg.dtype))
        if rc is None:
            return out, None
        with jax.named_scope("ssm_scan"):
            new_state = rc.put(rc.state, state)
        with jax.named_scope("ssm_conv"):
            new_conv = rc.put(rc.conv, tail)
        return out, rc._replace(state=new_state, conv=new_conv)


class NoPEAttention(nn.Module):
    """The attention layer's token mixing on x [B, T, D]: causal
    grouped-query softmax attention, no bias, no position encoding, the
    scores times ``attention_multiplier``. ``kv_cache`` is None (a whole
    sequence from position 0) or the layer's ``PagedKVLayer``, appended
    and attended as any paged layer's (``LlamaAttention``'s scopes)."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        q = dense(H * hd, name="wq")(x).reshape(B, T, H, hd)
        k = dense(KH * hd, name="wk")(x).reshape(B, T, KH, hd)
        v = dense(KH * hd, name="wv")(x).reshape(B, T, KH, hd)
        if kv_cache is None:
            new_cache = None
            qg = q.reshape(B, T, KH, H // KH, hd)
            with jax.named_scope("attn_scores"):
                s = jnp.einsum("btkrd,bskd->bkrts", qg, k,
                               preferred_element_type=jnp.float32
                               ) * cfg.attention_multiplier
                seen = jnp.tril(jnp.ones((T, T), bool))
                p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            with jax.named_scope("attn_pv"):
                y = jnp.einsum("bkrts,bskd->btkrd", p.astype(v.dtype), v)
        else:
            if not isinstance(kv_cache, PagedKVLayer) \
                    or kv_cache.pages_v is None:
                raise TypeError(
                    f"the attention layer of this model keeps K/V pages, "
                    f"not {type(kv_cache).__name__}: only the paged "
                    f"engine and the cache-less forward pass serve it "
                    f"(its other layers keep a recurrent state)")
            pc = kv_cache
            with jax.named_scope("kv_append"):
                appended = paged_append(
                    pc.pages_k, pc.pages_v, pc.page_table, cache_len, k, v,
                    pc.scales_k, pc.scales_v)
            if pc.quantized:
                pk, pv, sk, sv = appended
                new_cache = pc._replace(pages_k=pk, pages_v=pv,
                                        scales_k=sk, scales_v=sv)
            else:
                (pk, pv), sk, sv = appended, None, None
                new_cache = pc._replace(pages_k=pk, pages_v=pv)
            y = _paged_window_attention(
                q, pk, pv, sk, sv, pc.page_table, cache_len,
                softmax_scale=cfg.attention_multiplier)
        out = dense(cfg.dim, name="wo")(
            y.reshape(B, T, H * hd).astype(cfg.dtype))
        return out, new_cache


class GraniteHybridBlock(nn.Module):
    """Layer ``index``'s block: its token mixing by the layer's type,
    then the mixture and the shared SwiGLU off ONE norm, each branch
    times ``residual_multiplier`` before it joins the stream."""
    config: GraniteHybridConfig
    index: int = 0

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        scale = jnp.asarray(cfg.residual_multiplier, x.dtype)
        mixer = (NoPEAttention if cfg.layer_types[self.index] == ATTENTION
                 else Mamba2Mixer)(cfg, name="attention")
        moe = MoEFeedForward(cfg, name="moe")
        live = live_rows(kv_cache)

        def mixed(h, *args):
            out, new_cache = mixer(h, *args)
            return out * scale, new_cache
        return block_forward(cfg, mixed, lambda h: moe(h, live) * scale,
                             x, freqs, positions, kv_cache, cache_len)


class GraniteHybrid(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``RecurrentStateView`` for a Mamba-2 layer and a ``PagedKVLayer``
    for an attention layer (models/kv_cache.py ``kv_layer_view``)."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        logits, new_caches = transformer_forward(
            self, self.config,
            lambda i: functools.partial(GraniteHybridBlock, index=i),
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at,
            embed_scale=self.config.embedding_multiplier)
        return logits / self.config.logits_scaling, new_caches


def mixer_param_count(cfg: GraniteHybridConfig) -> int:
    """One Mamba-2 layer's token mixing: the in and out projections, the
    convolution and its bias, dt's bias, A and D a head, the gated
    norm's scale."""
    D, C, W, H = cfg.dim, cfg.d_inner, cfg.conv_width, cfg.mamba_heads
    return (D * (C + W + H) + C * D + (cfg.mamba_conv + 1) * W + 3 * H + C)


def attention_param_count(cfg: GraniteHybridConfig) -> int:
    wq, wkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return 2 * cfg.dim * wq + 2 * cfg.dim * wkv


def granite_hybrid_param_count(cfg: GraniteHybridConfig,
                               experts: Optional[int] = None) -> int:
    """Parameters, with ``experts`` routed experts a layer (the router's
    whole width where None); the embedding is the head."""
    E = cfg.num_experts if experts is None else experts
    D, F = cfg.dim, cfg.hidden_dim
    ffn = (E + cfg.n_shared_experts) * 3 * D * F + D * cfg.num_experts
    n_attn = cfg.layer_kinds.count(KIND_KV)
    return (cfg.vocab_size * D + D
            + n_attn * attention_param_count(cfg)
            + (cfg.n_layers - n_attn) * mixer_param_count(cfg)
            + cfg.n_layers * (ffn + 2 * D))
