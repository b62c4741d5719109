"""DeepSeek-V3.2 (deepseek-ai, ``model_type: deepseek_v32``): A.X-K1's
decoder (models/axk1.py: multi-head latent attention in every layer,
leading dense layers, a sparse mixture with a shared expert behind
them) with two mechanisms more.

LEARNED SPARSE ATTENTION. A query does not attend every entry at or
before it but the ``index_topk`` (2,048) that a LIGHTNING INDEXER ranks
highest. The indexer is a small attention of its own, ``index_n_heads``
(64) heads of ``index_head_dim`` (128) over ONE key a token:

    q_I[j] = (c_q W_Iq)[j]            from the query's low-rank latent
    k_I    = LayerNorm(h W_Ik)        cached, one a token a layer
    w      = h W_Iw x Hi^-0.5 x Di^-0.5
    I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),   s <= t

with rope (the layer's YaRN frequencies) on the first
``qk_rope_head_dim`` columns of ``q_I`` and ``k_I``. The
``min(index_topk, t + 1)`` largest ``I[t, .]`` are the entries query
``t`` attends, every head alike, a tie to the lower ``s``. The serving
engine keeps ``k_I`` in pages of its own that share the latent pages'
ids (models/kv_cache.py ``KIND_INDEXED``), and ``MLAttention`` reads
the chosen entries in the absorbed form
(ops/sparse_latent_attention.py); up to ``index_topk`` positions the
choice is every entry and the layer is A.X-K1's.

GROUP-LIMITED BIASED CHOICE (``topk_method: noaux_tc``). The router's
``s = sigmoid(logits)`` plus a stored choice bias are ranked inside
``n_group`` (8) groups of experts first: a group scores the sum of its
two best, the ``topk_group`` (4) best groups stay, and the
``num_experts_per_tok`` best experts inside them are chosen; the gates
are the chosen ``s`` renormalised (models/mixtral.py
``MoEFeedForward``: ``router = "sigmoid_bias"`` with ``n_group``).

benchmarks/reference/deepseek_v32.py has the equations, token by
token, and says which of them ``config.json`` leaves open (assumed).
The multi-token-prediction module behind the last layer is not here
(ROADMAP M5).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.axk1 import (AXK1Config, MLAttention,
                                 axk1_param_count)
from ray_tpu.models.kv_cache import KIND_INDEXED, PagedKVLayer, live_rows
from ray_tpu.models.llama import (LlamaMLP, block_forward,
                                  transformer_forward)
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.ops.paged_attention import paged_append
from ray_tpu.ops.sparse_latent_attention import index_scores


@dataclasses.dataclass(frozen=True)
class DeepSeekV32Config(AXK1Config):
    """The published sizes (DeepSeek-V3.2) under the names the shared
    modules read (``AXK1Config``'s, and the indexer's and the group
    limit's beside them)."""
    vocab_size: int = 129280
    max_seq_len: int = 163840
    n_heads: int = 128
    rope_factor: float = 40.0
    first_k_dense: int = 3
    num_experts: int = 256
    router: str = "sigmoid_bias"
    n_group: int = 8
    topk_group: int = 4
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return (KIND_INDEXED,) * self.n_layers

    @property
    def model_class(self):
        return DeepSeekV32


def deepseek_v32(**overrides) -> DeepSeekV32Config:
    return DeepSeekV32Config(**overrides)


def deepseek_v32_tiny(**overrides) -> DeepSeekV32Config:
    """Test size: ``axk1_tiny``'s widths with 8 heads, a dense layer and
    two mixture layers of 16 experts in 4 groups of which 2 stay, and
    an indexer of 4 heads of 16 that chooses 24 entries: a context of
    a hundred positions is several ``index_topk`` long and lies past
    YaRN's 64 original positions."""
    d = dict(vocab_size=256, max_seq_len=1024, dim=64, n_layers=3,
             n_heads=8, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=16, v_head_dim=8,
             rope_factor=8.0, rope_original_max_seq_len=64,
             rope_beta_fast=8.0, first_k_dense=1, dense_hidden_dim=96,
             hidden_dim=32, num_experts=16, num_experts_per_tok=4,
             n_shared_experts=1, n_group=4, topk_group=2,
             index_n_heads=4, index_head_dim=32, index_topk=24)
    d.update(overrides)
    return DeepSeekV32Config(**d)


class LightningIndexer(nn.Module):
    """The indexer of one layer: what ``MLAttention`` calls with the
    query's low-rank latent ``c_q`` [B, T, q_lora_rank], the layer's
    normed input ``x`` [B, T, D], its rope and positions, and its
    cache. Returns the scores [B, T, S] float32 (``-inf`` where a query
    cannot see: S = T without a cache, the page table's positions with
    one) and the cache with this chunk's index keys appended.

    The named scopes are metadata only (PERF.md section 3):
    ``dsa_index_q`` (W_Iq, rope, the head weights), ``dsa_index_k``
    (W_Ik, its norm, rope, the append), ``dsa_index_scores``."""
    config: DeepSeekV32Config

    @nn.compact
    def __call__(self, c_q, x, rope, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        Hi, Di, dr = (cfg.index_n_heads, cfg.index_head_dim,
                      cfg.qk_rope_head_dim)
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)

        def roped(v):                               # [B, T, heads, Di]
            return jnp.concatenate([rope(v[..., :dr]), v[..., dr:]], -1)
        with jax.named_scope("dsa_index_q"):
            q = roped(dense(Hi * Di, name="wq_b")(c_q)
                      .reshape(B, T, Hi, Di))
            w = dense(Hi, name="weights_proj")(x).astype(jnp.float32) * (
                Hi ** -0.5 * Di ** -0.5)
        with jax.named_scope("dsa_index_k"):
            k = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="k_norm")(
                dense(Di, name="wk")(x))
            k = roped(k[:, :, None])                     # [B, T, 1, Di]
            if kv_cache is not None:
                (pages,) = paged_append(
                    kv_cache.pages_index, None, kv_cache.page_table,
                    cache_len, k, None)
                kv_cache = kv_cache._replace(pages_index=pages)
        with jax.named_scope("dsa_index_scores"):
            if kv_cache is None:
                s = jnp.einsum("bthd,bsd->bths", q, k[:, :, 0],
                               preferred_element_type=jnp.float32)
                s = jnp.einsum("bths,bth->bts", jax.nn.relu(s), w)
                scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                                   -jnp.inf)
            else:
                scores = index_scores(q, w, kv_cache.pages_index,
                                      kv_cache.page_table, cache_len)
        return scores, kv_cache


class _Block(nn.Module):
    config: DeepSeekV32Config

    def feed_forward(self, kv_cache):
        raise NotImplementedError

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        if kv_cache is not None and not (
                isinstance(kv_cache, PagedKVLayer)
                and kv_cache.pages_index is not None):
            raise TypeError(
                f"a layer that chooses its entries keeps latent pages "
                f"and pages of index keys, not "
                f"{type(kv_cache).__name__}: only the paged engine and "
                f"the cache-less forward pass serve this model")
        attention = MLAttention(
            cfg, indexer=LightningIndexer(cfg, name="indexer"),
            name="attention")
        return block_forward(cfg, attention, self.feed_forward(kv_cache),
                             x, freqs, positions, kv_cache, cache_len)


class DeepSeekV32DenseBlock(_Block):
    def feed_forward(self, kv_cache):
        return LlamaMLP(self.config.dense_config(), name="feed_forward")


class DeepSeekV32MoEBlock(_Block):
    def feed_forward(self, kv_cache):
        moe = MoEFeedForward(self.config, name="moe")
        live = live_rows(kv_cache)
        return lambda h: moe(h, live)


class DeepSeekV32(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``PagedKVLayer`` over latent pages and index-key pages a layer."""
    config: DeepSeekV32Config

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        k = self.config.first_k_dense
        return transformer_forward(
            self, self.config,
            lambda i: (DeepSeekV32DenseBlock if i < k
                       else DeepSeekV32MoEBlock),
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at)


def deepseek_v32_param_count(cfg: DeepSeekV32Config,
                             experts: Optional[int] = None) -> int:
    """Parameters without the multi-token-prediction module, the
    indexers and the routers' choice biases among them, with
    ``experts`` routed experts a mixture layer (the router's whole
    width where None)."""
    return axk1_param_count(cfg, experts)
