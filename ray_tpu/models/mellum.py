"""Mellum 2 (JetBrains, ``model_type: mellum``): a decoder whose layers
keep caches of TWO SIZES. Three layers in four attend a SLIDING WINDOW
(query i sees the ``sliding_window`` keys i - window < j <= i, its own
among them) under plain rope; the fourth attends the whole context
under YaRN (models/axk1.py ``yarn_inv_freq``; cos and sin both times
``yarn_attention_factor``). Grouped-query heads with an EXPLICIT
``head_dim`` (32 query heads of 128 over a hidden size of 2,304: the
query is 4,096 wide, not the hidden size), no bias, no query/key norm;
every layer's feed-forward is ``models/mixtral.py``'s ``MoEFeedForward``
(softmax router over all experts in float32, the k largest
renormalised, no shared expert); an untied head.

So the serving engine's pool (models/kv_cache.py) holds, for this
model, K/V pages in the full layers, handed out and walked exactly as
Mistral's and OLMoE's are (``paged_append``,
``_paged_window_attention``), and a ``SlidingRing`` a slot in the
sliding layers: the last ``sliding_ring_len`` positions' keys and
values, whatever the context, appended to and attended in ONE call
(ops/ring_window_attention.py ``ring_window_attention``: on one TPU a
Pallas kernel that reads and writes the rows' rings where they lie,
elsewhere ops/paged_attention.py ``ring_append`` + ``ring_attention``).
``layer_kinds`` is ``KIND_SLIDING`` and ``KIND_KV``, by the published
``layer_types``.

benchmarks/reference/mellum2.py has the equations, and says which of
them ``config.json`` leaves open (assumed).

The model runs through ``transformer_forward`` as the other families do
(the full forward pass without a cache; the serving engine's paged
path). The static-cache ``generate`` of models/llama.py knows only K/V
caches and does not serve it.

The named scopes are metadata only (PERF.md section 3): ``attn_sliding``
and ``attn_full`` around a layer's append and attention by its type,
with the parts inside named (the kernel ``ring_window``, or off the
chip ``ring_append``, ``ring_scores``, ``ring_pv``; ``kv_append``,
``kv_gather``, ``attn_scores``, ``attn_pv``), so that a device trace
splits both step programs by layer type.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.axk1 import _rope, yarn_inv_freq
from ray_tpu.models.kv_cache import (KIND_KV, KIND_SLIDING, PagedKVLayer,
                                     SlidingRingView, live_rows)
from ray_tpu.models.llama import block_forward, transformer_forward
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)
from ray_tpu.ops.ring_window_attention import ring_window_attention

SLIDING, FULL = "sliding_attention", "full_attention"
_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The published sizes (Mellum2-12B-A2.5B) under the names the
    shared modules read: ``hidden_dim`` is ONE expert's width."""
    vocab_size: int = 98304
    max_seq_len: int = 131072
    dim: int = 2304
    n_layers: int = 28
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    # each layer's type as published; the first ``n_layers`` are read
    # (a cut in depth keeps the published list)
    layer_types: Tuple[str, ...] = _PERIOD * 7
    sliding_window: int = 1024
    rope_theta: float = 500000.0
    # the full layers' YaRN (``rope_parameters.full_attention``)
    yarn_factor: float = 16.0
    yarn_original_max_seq_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    hidden_dim: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    router: str = "softmax"
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    tie_word_embeddings: bool = False

    def __post_init__(self):
        types = self.layer_types[:self.n_layers]
        if len(types) < self.n_layers or set(types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {self.n_layers} layers as "
                f"{SLIDING!r} or {FULL!r}; got {self.layer_types}")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind of per-request state (models/kv_cache.py):
        a ring a slot in a sliding layer, K/V pages in a full one."""
        return tuple(KIND_SLIDING if t == SLIDING else KIND_KV
                     for t in self.layer_types[:self.n_layers])

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return Mellum


def mellum2_12b(**overrides) -> MellumConfig:
    return MellumConfig(**overrides)


def mellum_tiny(**overrides) -> MellumConfig:
    """Test size: two periods of (sliding, sliding, sliding, full), a
    window of 12, YaRN over 32 original positions (use a context past
    32); 8 experts of which 3 a token."""
    d = dict(vocab_size=256, max_seq_len=1024, dim=48, n_layers=8,
             n_heads=4, n_kv_heads=2, head_dim=16, sliding_window=12,
             rope_theta=10000.0, yarn_factor=4.0,
             yarn_original_max_seq_len=32, yarn_beta_fast=8.0,
             hidden_dim=32, num_experts=8, num_experts_per_tok=3)
    d.update(overrides)
    return MellumConfig(**d)


def rope_by_type(cfg: MellumConfig, layer_type: str):
    """(inverse frequencies [head_dim / 2], what cos and sin are
    multiplied by) of a layer of ``layer_type``."""
    if layer_type == FULL:
        return (yarn_inv_freq(cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
                              cfg.yarn_original_max_seq_len,
                              cfg.yarn_beta_fast, cfg.yarn_beta_slow),
                cfg.yarn_attention_factor)
    hd = cfg.head_dim
    return 1.0 / cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd), 1.0


class MellumAttention(nn.Module):
    """One layer's attention on x [B, T, D] (already normed), of
    ``layer_type``. ``kv_cache`` is None (a whole sequence from
    position 0), the layer's ``PagedKVLayer`` (a full layer) or its
    ``SlidingRingView`` (a sliding layer): the chunk is appended at the
    rows' offsets and attended over what the layer keeps."""
    config: MellumConfig
    layer_type: str = FULL

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        sliding = self.layer_type == SLIDING
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        q = dense(H * hd, name="wq")(x).reshape(B, T, H, hd)
        k = dense(KH * hd, name="wk")(x).reshape(B, T, KH, hd)
        v = dense(KH * hd, name="wv")(x).reshape(B, T, KH, hd)
        inv_freq, factor = rope_by_type(cfg, self.layer_type)
        q = _rope(q, inv_freq, positions, factor)
        k = _rope(k, inv_freq, positions, factor)

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
            new_cache = pc._replace(pages_k=pk, pages_v=pv)
        out = dense(cfg.dim, name="wo")(
            y.reshape(B, T, H * hd).astype(cfg.dtype))
        return out, new_cache


class MellumBlock(nn.Module):
    """Layer ``index``'s block: attention of the layer's type, then the
    mixture."""
    config: MellumConfig
    index: int = 0

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        moe = MoEFeedForward(cfg, name="moe")
        live = live_rows(kv_cache)
        return block_forward(
            cfg, MellumAttention(cfg, cfg.layer_types[self.index],
                                 name="attention"),
            lambda h: moe(h, live), x, freqs, positions, kv_cache,
            cache_len)


class Mellum(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``SlidingRingView`` for a sliding layer and a ``PagedKVLayer`` for
    a full one (models/kv_cache.py ``kv_layer_view``)."""
    config: MellumConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        # each layer rotates by its own type's rule: no shared table
        return transformer_forward(
            self, self.config,
            lambda i: functools.partial(MellumBlock, index=i),
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at)


def mellum_param_count(cfg: MellumConfig,
                       experts: Optional[int] = None) -> int:
    """Parameters, with ``experts`` experts a layer (all of them where
    None; ``num_experts_per_tok`` gives the active count)."""
    E = cfg.num_experts if experts is None else experts
    D, hd = cfg.dim, cfg.head_dim
    attention = 2 * D * cfg.n_heads * hd + 2 * D * cfg.n_kv_heads * hd
    moe = E * 3 * D * cfg.hidden_dim + D * cfg.num_experts
    return (2 * cfg.vocab_size * D + D
            + cfg.n_layers * (attention + moe + 2 * D))
