"""Kimi-Linear (moonshotai, ``model_type: kimi_linear``): a hybrid
decoder with NO layer of K/V attention at all. Three layers in four are
Kimi Delta Attention (models/solar_open2.py ``KDAAttention``: a gated
delta rule with a per-channel decay over a causal depthwise convolution
of q, k and v, whose state is a fixed-size matrix a head and a SLOT of
the serving engine), the fourth is multi-head LATENT attention
(models/axk1.py ``MLAttention``) with NO position encoding
(``mla_use_nope``) and a direct query (``q_lora_rank`` null): what it
caches is one latent entry ``[c | r]`` a token, in latent pages, and
the 64 decoupled columns stay, unrotated, as a key every head shares.
The first ``first_k_dense`` layers' feed-forward is a dense SwiGLU, the
others' ``models/mixtral.py``'s ``MoEFeedForward`` with a sigmoid
router, a stored choice bias, a shared expert and (where the config
says so) a share ``experts_held`` of the router's experts.

So the serving engine's pool (models/kv_cache.py) holds, for this
model, a ``RecurrentState`` in the KDA layers and one pool of latent
pages in the MLA layers, and no K/V page anywhere: ``layer_kinds`` is
``KIND_RECURRENT`` and ``KIND_LATENT`` only. Which layer is which is
the published lists' to say (``full_attn_layers``, 1-INDEXED as
``config.json`` has them: 4, 8, ..., 24, 27), not a period's.

benchmarks/reference/kimi_linear.py has the equations, token by token,
and says which of them ``config.json`` leaves open (assumed).

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
import jax.numpy as jnp

from ray_tpu.models.axk1 import MLAttention, mla_param_count
from ray_tpu.models.kv_cache import (KIND_LATENT, KIND_RECURRENT,
                                     live_rows)
from ray_tpu.models.llama import (LlamaMLP, block_forward,
                                  transformer_forward)
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.models.solar_open2 import KDAAttention

@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published sizes (Kimi-Linear-48B-A3B) under the names the
    shared modules read: ``hidden_dim`` is ONE expert's width (and the
    shared expert's), ``dense_hidden_dim`` the leading dense layers',
    ``num_experts`` the router's width; ``n_heads`` and the ``*_rank``
    and ``*_head_dim`` fields are the latent attention's (MLAttention),
    the ``kda_*`` fields and ``conv_size`` the delta rule's
    (KDAAttention)."""
    vocab_size: int = 163840
    max_seq_len: int = 1048576
    dim: int = 2304
    n_layers: int = 27
    # the latent-attention layers, 1-INDEXED as published; every other
    # layer is KDA (entries past ``n_layers`` name layers a cut lacks)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    n_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_rope: bool = False         # ``mla_use_nope``
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    kda_allow_neg_eigval: bool = False     # beta = sigmoid(.)
    first_k_dense: int = 1
    dense_hidden_dim: int = 9216
    hidden_dim: int = 1024
    num_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    router: str = "sigmoid_bias"
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    tie_word_embeddings: bool = False

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind of per-request state (models/kv_cache.py):
        never K/V pages."""
        full = set(self.full_attn_layers)
        return tuple(KIND_LATENT if i + 1 in full else KIND_RECURRENT
                     for i in range(self.n_layers))

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return KimiLinear

    @property
    def latent_dim(self) -> int:
        """A cached token's entry an MLA layer: ``[c | r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def recurrent_state_shape(self) -> Tuple[int, int, int]:
        """One slot's delta-rule state, a KDA layer: [H, dk, dv]."""
        return (self.kda_heads, self.kda_head_dim, self.kda_head_dim)

    @property
    def recurrent_conv_shape(self) -> Tuple[int, int]:
        """One slot's convolution tail, a KDA layer: the last
        ``conv_size - 1`` inputs of q, k and v."""
        return (self.conv_size - 1, 3 * self.kda_width)

    def dense_config(self) -> "KimiLinearConfig":
        """What ``LlamaMLP`` reads for a leading dense layer."""
        return dataclasses.replace(self, hidden_dim=self.dense_hidden_dim)


def kimi_linear_48b(**overrides) -> KimiLinearConfig:
    return KimiLinearConfig(**overrides)


def kimi_linear_tiny(**overrides) -> KimiLinearConfig:
    """Test size: two periods of (KDA, KDA, KDA, MLA), the first
    opening with the dense layer; 16 experts of which 4 a token, 1
    shared; positions enough for two 512-token blocks of the page
    window's loop."""
    d = dict(vocab_size=256, max_seq_len=1024, dim=64, n_layers=8,
             n_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=16, v_head_dim=8, kda_heads=4,
             kda_head_dim=16, first_k_dense=1, dense_hidden_dim=96,
             hidden_dim=32, num_experts=16, num_experts_per_tok=4,
             n_shared_experts=1)
    d.update(overrides)
    return KimiLinearConfig(**d)


_MIXING = {KIND_RECURRENT: KDAAttention, KIND_LATENT: MLAttention}


class KimiLinearBlock(nn.Module):
    """Layer ``index``'s block: its token mixing by the layer's kind,
    its feed-forward dense for the first ``first_k_dense`` layers and
    the mixture after them."""
    config: KimiLinearConfig
    index: int = 0

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        mixing = _MIXING[cfg.layer_kinds[self.index]](cfg,
                                                      name="attention")
        if self.index < cfg.first_k_dense:
            ffn = LlamaMLP(cfg.dense_config(), name="feed_forward")
        else:
            moe = MoEFeedForward(cfg, name="moe")
            live = live_rows(kv_cache)
            ffn = lambda h: moe(h, live)                # noqa: E731
        return block_forward(cfg, mixing, ffn, x, freqs, positions,
                             kv_cache, cache_len)


class KimiLinear(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``RecurrentStateView`` for a KDA layer and a ``PagedKVLayer`` over
    latent pages for an MLA layer (models/kv_cache.py
    ``kv_layer_view``)."""
    config: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        return transformer_forward(
            self, self.config,
            lambda i: functools.partial(KimiLinearBlock, index=i),
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at)


def kda_param_count(cfg) -> int:
    """One KDA layer's token mixing: four projections, the
    convolution, the low-rank decay and gate (W_g2 with a bias), the
    decay's bias and rate, beta's projection and the output norm."""
    D, C, d = cfg.dim, cfg.kda_width, cfg.kda_head_dim
    return (4 * D * C + cfg.conv_size * 3 * C
            + 2 * (D * d + d * C) + C
            + C + cfg.kda_heads + D * cfg.kda_heads + d)


def kimi_linear_param_count(cfg: KimiLinearConfig,
                            experts: Optional[int] = None) -> int:
    """Parameters, with ``experts`` routed experts a mixture layer (the
    router's whole width where None)."""
    E = cfg.num_experts if experts is None else experts
    D, F = cfg.dim, cfg.hidden_dim
    dense = 3 * D * cfg.dense_hidden_dim
    moe = ((E + cfg.n_shared_experts) * 3 * D * F
           + D * cfg.num_experts + cfg.num_experts)    # router and bias
    n_dense = min(cfg.first_k_dense, cfg.n_layers)
    n_mla = cfg.layer_kinds.count(KIND_LATENT)
    return (2 * cfg.vocab_size * D + D
            + n_mla * mla_param_count(cfg)
            + (cfg.n_layers - n_mla) * kda_param_count(cfg)
            + cfg.n_layers * 2 * D
            + n_dense * dense + (cfg.n_layers - n_dense) * moe)
