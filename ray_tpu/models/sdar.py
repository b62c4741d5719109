"""SDAR (JetLM, ``model_type: sdar_moe``): a Qwen3-MoE-shaped decoder
trained to fill in masked BLOCKS, so it DECODES BY BLOCKS
(models/kv_cache.py ``BlockDecode``): a decode step is a forward of a
whole block of ``block_length`` positions under a block-causal mask, the
logits AT a masked position are for that position's own token (no
shift), a block's tokens are revealed by confidence over several
forwards and one more forward of the finished block writes its K/V.

The layer: grouped-query heads with an EXPLICIT ``head_dim`` (32 query
heads of 128 over a hidden size of 2,048: ``wq`` is 2,048 x 4,096), an
RMSNorm over EACH HEAD's columns of the query and of the key with ONE
learned scale ``[head_dim]`` shared by the heads (not OLMoE's
whole-width norm), before rope (rotate-half over the whole head); the
feed-forward is ``models/mixtral.py``'s ``MoEFeedForward`` as OLMoE and
Laguna use it (softmax router over all experts in float32, the k
largest renormalised, no shared expert, every expert held), with its
``moe_*`` scopes and its routing counters; an untied head. The mask is
``block_length``'s: query i sees key j iff j // L <= i // L
(``ops/paged_attention.py`` ``_paged_window_attention`` and
``ops/attention.py`` take it as ``block_len``).

What generation is (the schedule, the strategies, the constants the
engine asks ``SdarConfig.block_decode`` for) is
serve/step_programs.py ``_jit_decode_blocks``'s and
benchmarks/reference/sdar.py's, which has the equations and says which
of them ``config.json`` leaves open. A masked position's input is
``mask_token_id``'s embedding: the caller puts that id there, the model
embeds what it is given.

The model runs through ``transformer_forward`` as the other families do
(the full forward pass without a cache; the serving engine's paged
path). The static-cache ``generate`` of models/llama.py yields a token a
step under a causal mask and does not serve it: ``SdarAttention``
refuses a static cache with a ``TypeError``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kv_cache import (REMASKING, BlockDecode, PagedKVLayer,
                                     live_rows)
from ray_tpu.models.llama import (RMSNorm, apply_rope, block_forward,
                                  transformer_forward)
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.ops.attention import multi_head_attention
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published sizes (SDAR-30B-A3B-Chat) under the names the
    shared modules read (``hidden_dim`` is ONE expert's width), and the
    generation constants of the source's ``generate.py`` (no key of
    ``config.json``: benchmarks/configs/sdar-30b-a3b-chat-d6.json
    ``assumed``)."""
    vocab_size: int = 151936
    max_seq_len: int = 32768
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    hidden_dim: int = 768
    num_experts: int = 128
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
    # generation by diffusion over blocks
    block_length: int = 4
    mask_token_id: int = 151669
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        if self.block_length < 1 or self.denoising_steps < 1:
            raise ValueError(
                f"block_length and denoising_steps must be >= 1; got "
                f"{self.block_length}, {self.denoising_steps}")
        if self.remasking not in REMASKING:
            raise ValueError(
                f"remasking={self.remasking!r} is not one of {REMASKING}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} is no token of a "
                f"vocabulary of {self.vocab_size}")

    @property
    def block_decode(self) -> BlockDecode:
        """How the model decodes: what serving asks the config instead
        of its type (None, or absent, for every other family)."""
        return BlockDecode(self.block_length, self.mask_token_id,
                           self.denoising_steps, self.remasking,
                           self.confidence_threshold)

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return Sdar


def sdar_30b_a3b(**overrides) -> SdarConfig:
    return SdarConfig(**overrides)


def sdar_tiny(**overrides) -> SdarConfig:
    """Test size: two query heads a K/V head with a head_dim that is
    not ``dim // n_heads``, 8 experts of which 3 a token, blocks of 4;
    the vocabulary's last token is the mask."""
    d = dict(vocab_size=256, max_seq_len=256, dim=48, n_layers=2,
             n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=10000.0,
             hidden_dim=32, num_experts=8, num_experts_per_tok=3)
    d.update(overrides)
    d.setdefault("mask_token_id", d["vocab_size"] - 1)
    return SdarConfig(**d)


class SdarAttention(nn.Module):
    """One layer's attention on x [B, T, D] (already normed) under the
    block-causal mask. ``kv_cache`` is None (a whole sequence from
    position 0) or the layer's ``PagedKVLayer``: the chunk is appended
    at the rows' offsets and attended over the rows' pages. A static
    cache is refused (the module docstring says why)."""
    config: SdarConfig

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
        # each head's columns normed on their own, one scale [hd] for
        # all heads
        q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
        k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        q = apply_rope(q, freqs, positions)
        k = apply_rope(k, freqs, positions)

        new_cache = None
        if kv_cache is None:
            rep = H // KH
            y = multi_head_attention(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                causal=True, block_len=cfg.block_length)
        else:
            if not (isinstance(kv_cache, PagedKVLayer)
                    and kv_cache.pages_v is not None
                    and not kv_cache.quantized):
                raise TypeError(
                    f"a model that decodes by blocks keeps K/V pages in "
                    f"the model's type, not {type(kv_cache).__name__}: "
                    f"only the paged engine and the cache-less forward "
                    f"pass serve it")
            pc = kv_cache
            # A denoising forward writes its block's K/V as it stands
            # (masks and all); the next forward of the same block
            # overwrites the same positions and the commit writes the
            # final ones: no ``store_kv`` switch, and no query reads a
            # block's pages before its own forward has written them
            # (the mask ends at the query's own block).
            with jax.named_scope("kv_append"):
                pk, pv = paged_append(pc.pages_k, pc.pages_v,
                                      pc.page_table, cache_len, k, v)
            y = _paged_window_attention(
                q, pk, pv, None, None, pc.page_table, cache_len,
                block_len=cfg.block_length)
            new_cache = pc._replace(pages_k=pk, pages_v=pv)
        out = dense(cfg.dim, name="wo")(
            y.reshape(B, T, H * hd).astype(cfg.dtype))
        return out, new_cache


class SdarBlock(nn.Module):
    config: SdarConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        moe = MoEFeedForward(cfg, name="moe")
        live = live_rows(kv_cache)
        return block_forward(
            cfg, SdarAttention(cfg, name="attention"),
            lambda h: moe(h, live), x, freqs, positions, kv_cache,
            cache_len)


class Sdar(nn.Module):
    """Call signature as models/llama.py Llama's."""
    config: SdarConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        return transformer_forward(self, self.config,
                                   lambda i: SdarBlock,
                                   input_ids, kv_caches, cache_len,
                                   logits_at=logits_at)


def sdar_param_count(cfg: SdarConfig,
                     experts: Optional[int] = None) -> int:
    """Parameters, with ``experts`` experts a layer (all of them where
    None; ``num_experts_per_tok`` gives the active count)."""
    E = cfg.num_experts if experts is None else experts
    D, hd = cfg.dim, cfg.head_dim
    attention = (2 * D * cfg.n_heads * hd + 2 * D * cfg.n_kv_heads * hd
                 + 2 * hd)
    moe = E * 3 * D * cfg.hidden_dim + D * cfg.num_experts
    return (2 * cfg.vocab_size * D + D
            + cfg.n_layers * (attention + moe + 2 * D))
