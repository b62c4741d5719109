"""Laguna (poolside, ``model_type: laguna``; Laguna-XS.2 is 33.4B-A3B):
a decoder whose layers keep caches of TWO SIZES, as models/mellum.py's
do, and whose QUERY differs by layer type over one K/V pool. One layer
in four attends the whole context (48 query heads at the published
sizes), three a sliding window of ``sliding_window`` keys (64 query
heads); every layer has the same ``n_kv_heads`` K/V heads of an explicit
``head_dim``, so a full layer's group is 6 query heads a K/V head and a
sliding layer's 8, and the pool (models/kv_cache.py) has ONE page shape
and one ring shape whatever the layer's query.

What differs from Mellum 2, each a field of the config:

- ``n_heads_per_layer``: the query heads of every layer as published
  (``num_attention_heads_per_layer``); all layers of one type must
  agree, because a kind of request state asks ONE query shape of its
  decode kernel (``query_heads_by_kind``, read by models/kv_cache.py
  ``kv_query_heads``; ``n_heads`` is the published
  ``num_attention_heads``, the full layers');
- rope by layer type in base AND in width: a full layer rotates the
  FIRST ``partial_rotary_factor`` of a head's columns (64 of 128) under
  YaRN whose frequencies are computed for that rotated width (models/
  axk1.py ``yarn_inv_freq``; cos and sin both times
  ``yarn_attention_factor``) and passes the rest as they are; a sliding
  layer rotates the whole head by plain rope at its own base
  (``sliding_rope_theta``);
- ``gating``: a gate a head on the attention's output, ``g =
  sigmoid(h W_g)`` [.., H] from the layer's normed input, ``y_i = g_i *
  a_i`` before ``W_o`` (scope ``attn_gate``, inside the layer type's);
- ``mlp_layer_types``: layer 0's feed-forward is a dense SwiGLU of
  ``dense_hidden_dim`` (models/llama.py ``LlamaMLP``), the others'
  models/mixtral.py's ``MoEFeedForward`` with a sigmoid router without
  a bias, the k largest renormalised and times
  ``routed_scaling_factor``, and ONE shared expert every token passes.

benchmarks/reference/laguna.py has the equations, and says which of
them ``config.json`` leaves open (assumed).

The model runs through ``transformer_forward`` as the other families do
(the full forward pass without a cache; the serving engine's paged
path); the static-cache ``generate`` of models/llama.py does not serve
it. The attention's cached forms are Mellum 2's own: ``paged_append``
and ``_paged_window_attention`` over K/V pages in a full layer (on one
TPU a decode step is ops/paged_decode_attention.py's kernel at a group
of 6), ``ring_window_attention`` over a slot's ring in a sliding one
(ops/ring_window_attention.py's kernel at a group of 8).

The named scopes are metadata only (PERF.md section 3): ``attn_sliding``
and ``attn_full`` as Mellum 2's, ``attn_gate`` inside either, the dense
layer's module ``feed_forward`` and the mixture's ``moe_*`` scopes
(``moe_shared`` the shared expert).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.axk1 import _rope, yarn_inv_freq
from ray_tpu.models.kv_cache import (KIND_KV, KIND_SLIDING, PagedKVLayer,
                                     SlidingRingView, live_rows)
from ray_tpu.models.llama import (LlamaMLP, block_forward,
                                  transformer_forward)
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)
from ray_tpu.ops.ring_window_attention import ring_window_attention

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
_PERIOD = (FULL, SLIDING, SLIDING, SLIDING)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published sizes (Laguna-XS.2) under the names the shared
    modules read: ``hidden_dim`` is ONE expert's width, ``n_heads`` the
    published ``num_attention_heads`` (the full layers' query heads)."""
    vocab_size: int = 100352
    max_seq_len: int = 262144
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    # each layer's type, query heads and feed-forward as published; the
    # first ``n_layers`` are read (a cut in depth keeps the lists)
    layer_types: Tuple[str, ...] = _PERIOD * 10
    n_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    sliding_window: int = 512
    gating: bool = True
    # the full layers' rope (``rope_parameters.full_attention``): YaRN
    # over the first ``partial_rotary_factor`` of a head's columns
    rope_theta: float = 500000.0
    partial_rotary_factor: float = 0.5
    yarn_factor: float = 64.0
    yarn_original_max_seq_len: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    # the sliding layers' (``rope_parameters.sliding_attention``)
    sliding_rope_theta: float = 10000.0
    sliding_partial_rotary_factor: float = 1.0
    dense_hidden_dim: int = 8192
    hidden_dim: int = 512
    num_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    router: str = "sigmoid"
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    tie_word_embeddings: bool = False

    def __post_init__(self):
        n = self.n_layers
        types = self.layer_types[:n]
        if len(types) < n or set(types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {n} layers as {SLIDING!r} or "
                f"{FULL!r}; got {self.layer_types}")
        ffn = self.mlp_layer_types[:n]
        if len(ffn) < n or set(ffn) - {DENSE, SPARSE}:
            raise ValueError(
                f"mlp_layer_types must name {n} layers as {DENSE!r} or "
                f"{SPARSE!r}; got {self.mlp_layer_types}")
        heads = self.n_heads_per_layer[:n]
        if len(heads) < n or any(h % self.n_kv_heads for h in heads):
            raise ValueError(
                f"n_heads_per_layer must give {n} layers whole groups "
                f"of query heads over {self.n_kv_heads} K/V heads; got "
                f"{self.n_heads_per_layer}")
        for t in set(types):
            counts = {h for h, u in zip(heads, types) if u == t}
            if len(counts) > 1:
                raise ValueError(
                    f"the {t} layers have {sorted(counts)} query heads: "
                    f"a kind of request state asks ONE query shape of "
                    f"its decode kernel")
        for factor in (self.partial_rotary_factor,
                       self.sliding_partial_rotary_factor):
            if not 0 < factor <= 1 or (self.head_dim * factor) % 2:
                raise ValueError(
                    f"a rotated fraction {factor} of a head of "
                    f"{self.head_dim} is no whole number of pairs")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind of per-request state (models/kv_cache.py):
        a ring a slot in a sliding layer, K/V pages in a full one."""
        return tuple(KIND_SLIDING if t == SLIDING else KIND_KV
                     for t in self.layer_types[:self.n_layers])

    @property
    def query_heads_by_kind(self) -> Dict[str, int]:
        """The query heads a layer of each kind hands its attention
        (models/kv_cache.py ``kv_query_heads``): the kinds' layers
        agree, ``__post_init__`` has seen to it."""
        return {kind: heads for kind, heads in zip(
            self.layer_kinds, self.n_heads_per_layer)}

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return Laguna

    def dense_config(self) -> "LagunaConfig":
        """What ``LlamaMLP`` reads for a dense layer."""
        return dataclasses.replace(self, hidden_dim=self.dense_hidden_dim)


def laguna_xs2(**overrides) -> LagunaConfig:
    return LagunaConfig(**overrides)


def laguna_tiny(**overrides) -> LagunaConfig:
    """Test size: the dense layer and one whole period after it (full,
    sliding, sliding, sliding, full) with 4 full and 6 sliding query
    heads over 2 K/V heads of 16 (groups of 2 and 3), a window of 12,
    half a full head rotated under YaRN over 32 original positions (use
    a context past both); 16 experts of which 4 a token and one
    shared."""
    d = dict(vocab_size=256, max_seq_len=1024, dim=48, n_layers=5,
             n_heads=4, n_kv_heads=2, head_dim=16,
             n_heads_per_layer=(4, 6, 6, 6) * 10, sliding_window=12,
             rope_theta=10000.0, yarn_factor=4.0,
             yarn_original_max_seq_len=32, yarn_beta_fast=8.0,
             yarn_attention_factor=1.1386294361119891,
             sliding_rope_theta=1000.0, dense_hidden_dim=96,
             hidden_dim=24, num_experts=16, num_experts_per_tok=4)
    d.update(overrides)
    return LagunaConfig(**d)


def rope_by_type(cfg: LagunaConfig, layer_type: str):
    """(columns of a head that rotate, their inverse frequencies
    [columns / 2], what cos and sin are multiplied by) of a layer of
    ``layer_type``. YaRN's correction range is computed for the
    ROTATED width, as its frequencies are."""
    if layer_type == FULL:
        r = int(cfg.head_dim * cfg.partial_rotary_factor)
        return (r, yarn_inv_freq(r, cfg.rope_theta, cfg.yarn_factor,
                                 cfg.yarn_original_max_seq_len,
                                 cfg.yarn_beta_fast, cfg.yarn_beta_slow),
                cfg.yarn_attention_factor)
    r = int(cfg.head_dim * cfg.sliding_partial_rotary_factor)
    return r, 1.0 / cfg.sliding_rope_theta ** (
        jnp.arange(0, r, 2, dtype=jnp.float32) / r), 1.0


def _rotate(x, width: int, inv_freq, positions, factor: float):
    """``x`` [B, T, H, d] with its first ``width`` columns rotated
    (rotate-half pairing inside them) and the others as they are."""
    if width == x.shape[-1]:
        return _rope(x, inv_freq, positions, factor)
    return jnp.concatenate(
        [_rope(x[..., :width], inv_freq, positions, factor),
         x[..., width:]], axis=-1)


class LagunaAttention(nn.Module):
    """One layer's attention on x [B, T, D] (already normed), of
    ``layer_type`` with ``heads`` query heads. ``kv_cache`` is None (a
    whole sequence from position 0), the layer's ``PagedKVLayer`` (a
    full layer) or its ``SlidingRingView`` (a sliding layer): the chunk
    is appended at the rows' offsets and attended over what the layer
    keeps."""
    config: LagunaConfig
    layer_type: str = FULL
    heads: int = 48

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        H, KH, hd = self.heads, cfg.n_kv_heads, cfg.head_dim
        sliding = self.layer_type == SLIDING
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        q = dense(H * hd, name="wq")(x).reshape(B, T, H, hd)
        k = dense(KH * hd, name="wk")(x).reshape(B, T, KH, hd)
        v = dense(KH * hd, name="wv")(x).reshape(B, T, KH, hd)
        width, inv_freq, factor = rope_by_type(cfg, self.layer_type)
        q = _rotate(q, width, inv_freq, positions, factor)
        k = _rotate(k, width, inv_freq, positions, factor)

        def gated(y):
            """[B, T, H, hd] times the layer's gate a head."""
            if not cfg.gating:
                return y
            with jax.named_scope("attn_gate"):
                g = jax.nn.sigmoid(
                    dense(H, name="wg")(x).astype(jnp.float32))
                return (y.reshape(B, T, H, hd).astype(jnp.float32)
                        * g[..., None]).astype(cfg.dtype)

        new_cache = None
        if kv_cache is None:
            # the whole sequence at once: one softmax under the
            # layer's mask
            qg = q.reshape(B, T, KH, H // KH, hd)
            i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
            seen = j <= i
            if sliding:
                seen &= j > i - cfg.sliding_window
            with jax.named_scope("attn_scores"):
                s = jnp.einsum("btkrd,bskd->bkrts", qg, k,
                               preferred_element_type=jnp.float32
                               ) / np.sqrt(hd)
                p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            with jax.named_scope("attn_pv"):
                y = jnp.einsum("bkrts,bskd->btkrd", p.astype(v.dtype), v)
            y = gated(y)
        elif sliding:
            if not isinstance(kv_cache, SlidingRingView):
                raise TypeError(
                    f"a sliding-window layer keeps a ring a slot, not "
                    f"{type(kv_cache).__name__}: only the paged engine "
                    f"and the cache-less forward pass serve this model")
            rc = kv_cache
            with jax.named_scope("attn_sliding"):
                y, rk, rv = ring_window_attention(
                    q, k, v, rc.k, rc.v, rc.slots, cache_len, rc.valid,
                    cfg.sliding_window)
                y = gated(y)
            new_cache = rc._replace(k=rk, v=rv)
        else:
            if not (isinstance(kv_cache, PagedKVLayer)
                    and kv_cache.pages_v is not None
                    and not kv_cache.quantized):
                raise TypeError(
                    f"a full-attention layer keeps K/V pages in the "
                    f"model's type, not {type(kv_cache).__name__}")
            pc = kv_cache
            with jax.named_scope("attn_full"):
                with jax.named_scope("kv_append"):
                    pk, pv = paged_append(pc.pages_k, pc.pages_v,
                                          pc.page_table, cache_len, k, v)
                y = _paged_window_attention(q, pk, pv, None, None,
                                            pc.page_table, cache_len)
                y = gated(y)
            new_cache = pc._replace(pages_k=pk, pages_v=pv)
        out = dense(cfg.dim, name="wo")(
            y.reshape(B, T, H * hd).astype(cfg.dtype))
        return out, new_cache


class LagunaBlock(nn.Module):
    """Layer ``index``'s block: attention of the layer's type and query
    heads, then the dense SwiGLU or the mixture by
    ``mlp_layer_types``."""
    config: LagunaConfig
    index: int = 0

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        attention = LagunaAttention(
            cfg, cfg.layer_types[self.index],
            cfg.n_heads_per_layer[self.index], name="attention")
        if cfg.mlp_layer_types[self.index] == DENSE:
            ffn = LlamaMLP(cfg.dense_config(), name="feed_forward")
        else:
            moe = MoEFeedForward(cfg, name="moe")
            live = live_rows(kv_cache)
            ffn = lambda h: moe(h, live)                # noqa: E731
        return block_forward(cfg, attention, ffn, x, freqs, positions,
                             kv_cache, cache_len)


class Laguna(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``SlidingRingView`` for a sliding layer and a ``PagedKVLayer`` for
    a full one (models/kv_cache.py ``kv_layer_view``)."""
    config: LagunaConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        # each layer rotates by its own type's rule: no shared table
        return transformer_forward(
            self, self.config,
            lambda i: functools.partial(LagunaBlock, index=i),
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at)


def laguna_param_count(cfg: LagunaConfig,
                       experts: Optional[int] = None) -> int:
    """Parameters, with ``experts`` routed experts a mixture layer (all
    of them where None; ``num_experts_per_tok`` gives the active
    count): the embedding, the head and the final norm; a layer's two
    norms, its four projections by ITS query heads and its gate; the
    dense SwiGLU or the router, the routed experts and the shared
    one."""
    E = cfg.num_experts if experts is None else experts
    D, hd, F = cfg.dim, cfg.head_dim, cfg.hidden_dim
    kv = 2 * D * cfg.n_kv_heads * hd
    moe = ((E + cfg.n_shared_experts) * 3 * D * F + D * cfg.num_experts)
    total = 2 * cfg.vocab_size * D + D
    for heads, ffn in zip(cfg.n_heads_per_layer[:cfg.n_layers],
                          cfg.mlp_layer_types):
        total += (2 * D * heads * hd + kv
                  + (D * heads if cfg.gating else 0) + 2 * D
                  + (3 * D * cfg.dense_hidden_dim if ffn == DENSE
                     else moe))
    return total
