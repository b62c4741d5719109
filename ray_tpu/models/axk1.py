"""A.X-K1 (skt, ``model_type: axk1``): a decoder whose every layer is
multi-head LATENT attention (MLA, the DeepSeek-V2/V3 form), whose first
``first_k_dense`` layers have a dense SwiGLU feed-forward and whose
other layers a sparse mixture with a sigmoid router (no stored choice
bias) and a shared expert.

What MLA caches is not K and V a head but, a token a layer, ONE latent
entry ``[c | k_r]``: the normed compressed key-value vector
(``kv_lora_rank``) and a rope key (``qk_rope_head_dim``) that all heads
share. The serving engine keeps it in latent pages (models/kv_cache.py
``KIND_LATENT``), and ``MLAttention`` reads them in the ABSORBED form:
the key up-projection is folded into the query and the value
up-projection into the read-out, so the block loop of
ops/paged_attention.py attends every head over the one gathered entry
(one "KV head" of ``latent_dim``, values its first ``kv_lora_rank``
columns) and no per-head K or V of a cached token ever exists. Without
a cache (a whole sequence: the parameters' shapes, the tests) the same
module computes the EXPANDED form, per-head K and V from the latent;
the two are algebraically equal (tests/test_axk1.py).

Positions: rotary on the decoupled ``qk_rope_head_dim`` columns only,
with YaRN frequencies (``yarn_inv_freq``) and the score scale
``qk_head_dim ** -0.5 * m ** 2`` that the YaRN convention of
``rope_scaling`` gives (``yarn_mscale``).

benchmarks/reference/axk1.py has the equations, token by token, and
says which of them ``config.json`` leaves open (assumed). The model
runs through ``transformer_forward`` as the other families do (the full
forward pass without a cache; the serving engine's paged path); the
static-cache ``generate`` of models/llama.py knows only K/V caches and
does not serve it.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kv_cache import (KIND_LATENT, PagedKVLayer,
                                     live_rows)
from ray_tpu.models.llama import (LlamaMLP, RMSNorm, block_forward,
                                  transformer_forward)
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)
from ray_tpu.ops.sparse_latent_attention import (SELECTION_STATS,
                                                 SelectionStats,
                                                 sparse_attention,
                                                 topk_mask)


@dataclasses.dataclass(frozen=True)
class AXK1Config:
    """The published sizes (A.X-K1) under the names the shared modules
    read: ``hidden_dim`` is ONE expert's width (and the shared
    expert's), ``dense_hidden_dim`` the leading dense layers',
    ``num_experts`` the router's width."""
    vocab_size: int = 163840
    max_seq_len: int = 131072
    dim: int = 7168
    n_layers: int = 61
    n_heads: int = 64
    # None: no low-rank query, one direct W_q (models/kimi_linear.py)
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # False: NO position encoding (NoPE, models/kimi_linear.py): the
    # decoupled columns stay, unrotated, as a key every head shares
    mla_rope: bool = True
    rope_theta: float = 10000.0
    # YaRN (``rope_scaling``)
    rope_factor: float = 32.0
    rope_original_max_seq_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    first_k_dense: int = 1
    dense_hidden_dim: int = 18432
    hidden_dim: int = 2048
    num_experts: int = 192
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    router: str = "sigmoid"
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    tie_word_embeddings: bool = False

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind of per-request state (models/kv_cache.py)."""
        return (KIND_LATENT,) * self.n_layers

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return AXK1

    @property
    def stats_sections(self) -> tuple:
        """What the layers count on the device beside the mixture
        (models/mixtral.py ``stats_sections``): with an indexer
        (``index_topk``) ``MLAttention`` sows ``SELECTION_STATS``."""
        return (SelectionStats(),) if getattr(self, "index_topk", None) \
            else ()

    @property
    def latent_dim(self) -> int:
        """A cached token's entry a layer: ``[c | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    def dense_config(self) -> "AXK1Config":
        """What ``LlamaMLP`` reads for a leading dense layer."""
        return dataclasses.replace(self, hidden_dim=self.dense_hidden_dim)


def axk1(**overrides) -> AXK1Config:
    return AXK1Config(**overrides)


def axk1_tiny(**overrides) -> AXK1Config:
    """Test size: a dense layer and two mixture layers, 16 experts of
    which 4 a token, 1 shared; YaRN over 64 original positions with
    two of the eight frequencies inside the ramp, so a context past 64
    exercises the blend; positions enough for two 512-token blocks of
    the page window's loop."""
    d = dict(vocab_size=256, max_seq_len=1024, dim=64, n_layers=3,
             n_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=16, v_head_dim=8,
             rope_factor=8.0, rope_original_max_seq_len=64,
             rope_beta_fast=8.0, first_k_dense=1, dense_hidden_dim=96,
             hidden_dim=32, num_experts=16, num_experts_per_tok=4,
             n_shared_experts=1)
    d.update(overrides)
    return AXK1Config(**d)


# --------------------------------------------------------------------------
# YaRN
# --------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN's ``dim / 2`` inverse frequencies: the original ones where
    a dimension turns more than ``beta_fast`` times over the
    ``original`` positions, those divided by ``factor`` where fewer
    than ``beta_slow``, and a linear ramp between the two over the
    dimensions in between."""
    def correction_dim(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def _rope(x, inv_freq, positions, mscale: float):
    """x [B, T, H, d] at ``positions`` [T] or [B, T]; rotate-half
    pairing (column i with i + d/2)."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if ang.ndim == 2:
        ang = ang[None]
    cos = (jnp.cos(ang) * mscale)[..., None, :]        # [B|1, T, 1, d/2]
    sin = (jnp.sin(ang) * mscale)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


class MLAttention(nn.Module):
    """One layer's latent attention on x [B, T, D] (already normed).
    ``kv_cache`` is None (a whole sequence, the expanded form) or the
    layer's ``PagedKVLayer`` over a pool of latent pages (``pages_v``
    None): the chunk's entries are appended at the rows' offsets and
    every head attends over its row's pages in the absorbed form.

    The named scopes are metadata only (PERF.md section 3): ``mla_q``
    (the low-rank query: W_qa, its norm, W_qb, rope), ``mla_kv`` (the
    latent entry: W_kva, its norm, rope), ``mla_absorb`` (the key
    up-projection folded into the query and the value up-projection of
    the read-out), beside ``kv_append`` and the block loop's own.

    ``config`` is an ``AXK1Config`` or any config with its attention
    fields (models/kimi_linear.py's: ``q_lora_rank`` None gives the
    query one direct matrix ``wq``, ``mla_rope`` false leaves the
    decoupled columns of query and key unrotated).

    ``indexer`` (models/deepseek_v32.py ``LightningIndexer``; None:
    every query attends every entry at or before it) is a module that
    scores the entries against a query's low-rank latent ``c_q`` and
    the layer's input, keeping its own keys in the cache's
    ``pages_index``; a query then attends its ``config.index_topk``
    best entries alone (ops/sparse_latent_attention.py). Up to that
    many positions the choice is every entry and the layer is the one
    without an indexer."""
    config: Any
    indexer: Optional[nn.Module] = None

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        R = cfg.kv_lora_rank
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        if cfg.mla_rope:
            inv_freq = yarn_inv_freq(dr, cfg.rope_theta, cfg.rope_factor,
                                     cfg.rope_original_max_seq_len,
                                     cfg.rope_beta_fast, cfg.rope_beta_slow)
            mscale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                      / yarn_mscale(cfg.rope_factor,
                                    cfg.rope_mscale_all_dim))
            rope = functools.partial(_rope, inv_freq=inv_freq,
                                     positions=positions, mscale=mscale)
        else:
            rope = lambda x: x                          # noqa: E731
        with jax.named_scope("mla_q"):
            if cfg.q_lora_rank is None:
                q = dense(H * (dn + dr), name="wq")(x)
            else:
                c_q = RMSNorm(cfg.norm_eps, name="q_norm")(
                    dense(cfg.q_lora_rank, name="wq_a")(x))
                q = dense(H * (dn + dr), name="wq_b")(c_q)
            q = q.reshape(B, T, H, dn + dr)
            q_nope, q_rope = q[..., :dn], rope(q[..., dn:])
        with jax.named_scope("mla_kv"):
            kv = dense(R + dr, name="wkv_a")(x)
            c = RMSNorm(cfg.norm_eps, name="kv_norm")(kv[..., :R])
            k_rope = rope(kv[..., None, R:])
        scores = None
        if self.indexer is not None:
            if cfg.q_lora_rank is None:
                raise ValueError("an indexer reads the query's low-rank "
                                 "latent, and q_lora_rank is None")
            # [B, T, S] float32, -inf where a query cannot see
            scores, kv_cache = self.indexer(c_q, x, rope, positions,
                                            kv_cache, cache_len)
        # W_kvb [R, H, dn + dv]: head i's key up-projection W_UK^i
        # (its first dn columns) and value up-projection W_UV^i
        w_kvb = self.param("wkv_b", nn.initializers.lecun_normal(),
                           (R, H * (dn + dv)), cfg.param_dtype
                           ).astype(cfg.dtype).reshape(R, H, dn + dv)
        w_uk, w_uv = w_kvb[..., :dn], w_kvb[..., dn:]

        new_cache = None
        if kv_cache is None:
            # EXPANDED: every position's K and V a head from its latent
            with jax.named_scope("mla_absorb"):
                k_nope = jnp.einsum("bsr,rhn->bshn", c, w_uk)
                v = jnp.einsum("bsr,rhv->bshv", c, w_uv)
            with jax.named_scope("attn_scores"):
                s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope[:, :, 0],
                                  preferred_element_type=jnp.float32)
                     ) * cfg.softmax_scale
                causal = jnp.tril(jnp.ones((T, T), bool))
                if scores is not None:
                    with jax.named_scope("dsa_topk"):
                        causal = topk_mask(scores, cfg.index_topk)[:, None]
                p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            with jax.named_scope("attn_pv"):
                y = jnp.einsum("bhts,bshv->bthv", p.astype(v.dtype), v)
        elif isinstance(kv_cache, PagedKVLayer) and kv_cache.pages_v is None:
            pc = kv_cache
            # an entry is stored in whole 128-lane tiles
            # (models/kv_cache.py latent_page_width): zeros behind
            # [c | k_r], which score nothing against the query's own
            pad = ((0, 0),) * 3 + ((0, pc.pages_k.shape[-1] - R - dr),)
            with jax.named_scope("kv_append"):
                entry = jnp.pad(
                    jnp.concatenate([c[:, :, None], k_rope], axis=-1), pad)
                (pages,) = paged_append(pc.pages_k, None, pc.page_table,
                                        cache_len, entry, None)
            new_cache = pc._replace(pages_k=pages)
            # ABSORBED: q~ = q_nope W_UK^T lives in the latent's space,
            # so a head's score against a cached token is one dot
            # product with its entry [c | k_r]
            with jax.named_scope("mla_absorb"):
                q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_uk)
                q_full = jnp.pad(
                    jnp.concatenate([q_lat, q_rope], axis=-1), pad)
            if scores is None:
                o_lat = _paged_window_attention(
                    q_full, pages, None, None, None, pc.page_table,
                    cache_len, softmax_scale=cfg.softmax_scale,
                    value_dim=R)
            else:
                o_lat, chosen, read, by_kernel = sparse_attention(
                    q_full, pages, pc.page_table, cache_len, scores,
                    cfg.index_topk, softmax_scale=cfg.softmax_scale,
                    value_dim=R)
                self.sow(SELECTION_STATS, "counts",
                         jnp.stack([positions + 1, chosen, read,
                                    by_kernel]),
                         reduce_fn=lambda _prev, new: new,
                         init_fn=lambda: None)
            with jax.named_scope("mla_absorb"):
                y = jnp.einsum("bthr,rhv->bthv", o_lat, w_uv)
        else:
            raise TypeError(
                f"a latent-attention layer keeps latent pages, not "
                f"{type(kv_cache).__name__}: only the paged engine and "
                f"the cache-less forward pass serve this model")
        out = dense(cfg.dim, name="wo")(
            y.reshape(B, T, H * dv).astype(cfg.dtype))
        return out, new_cache


class _Block(nn.Module):
    config: AXK1Config

    def feed_forward(self, kv_cache):
        raise NotImplementedError

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        return block_forward(
            cfg, MLAttention(cfg, name="attention"),
            self.feed_forward(kv_cache),
            x, freqs, positions, kv_cache, cache_len)


class AXK1DenseBlock(_Block):
    def feed_forward(self, kv_cache):
        return LlamaMLP(self.config.dense_config(), name="feed_forward")


class AXK1MoEBlock(_Block):
    def feed_forward(self, kv_cache):
        moe = MoEFeedForward(self.config, name="moe")
        live = live_rows(kv_cache)
        return lambda h: moe(h, live)


class AXK1(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``PagedKVLayer`` over latent pages a layer (models/kv_cache.py
    ``kv_layer_view``). The block is chosen BY LAYER INDEX: the first
    ``first_k_dense`` layers are dense, the rest mixtures."""
    config: AXK1Config

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        k = self.config.first_k_dense
        return transformer_forward(
            self, self.config,
            lambda i: AXK1DenseBlock if i < k else AXK1MoEBlock,
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at)


def mla_param_count(cfg) -> int:
    """One layer's latent attention: five matrices and two norms, or
    with a direct query (``q_lora_rank`` None) four and one; with an
    indexer (``index_topk``) its three matrices and its key norm's
    scale and bias too."""
    H = cfg.n_heads
    if cfg.q_lora_rank is None:
        query = cfg.dim * H * cfg.qk_head_dim
    else:
        query = (cfg.dim * cfg.q_lora_rank + cfg.q_lora_rank
                 + cfg.q_lora_rank * H * cfg.qk_head_dim)
    indexer = 0
    if getattr(cfg, "index_topk", None):
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        indexer = (cfg.q_lora_rank * Hi * Di + cfg.dim * Di + 2 * Di
                   + cfg.dim * Hi)
    return (query + indexer
            + cfg.dim * cfg.latent_dim + cfg.kv_lora_rank
            + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * cfg.dim)


def axk1_param_count(cfg: AXK1Config,
                     experts: Optional[int] = None) -> int:
    """Parameters, with ``experts`` routed experts a mixture layer (the
    router's whole width where None)."""
    E = cfg.num_experts if experts is None else experts
    D, F = cfg.dim, cfg.hidden_dim
    dense = 3 * D * cfg.dense_hidden_dim
    moe = (E + cfg.n_shared_experts) * 3 * D * F + D * cfg.num_experts
    if cfg.router == "sigmoid_bias":
        moe += cfg.num_experts
    n_dense = min(cfg.first_k_dense, cfg.n_layers)
    return (2 * cfg.vocab_size * D + D
            + cfg.n_layers * (mla_param_count(cfg) + 2 * D)
            + n_dense * dense + (cfg.n_layers - n_dense) * moe)
