"""Solar-Open2 (upstage, ``model_type: solar_open2``): a hybrid decoder
whose layers are of two kinds, and whose every layer's feed-forward is
a sparse mixture with a shared expert.

- One layer in ``gqa_interval + 1`` (layers 0, 4, 8, ...) is softmax
  grouped-query attention with NO position encoding and an output
  gate: ``models/llama.py``'s ``LlamaAttention(rope=False,
  out_gate=True)``. Its K and V live in the paged pool as any Llama
  layer's do.
- The others are Kimi Delta Attention (``KDAAttention`` below): a gated
  delta rule with a per-channel decay over a causal depthwise
  convolution of q, k and v. Such a layer keeps NO K/V: its state is a
  fixed-size matrix a head (float32) plus the convolution's last
  inputs, a SLOT of the serving engine (models/kv_cache.py
  ``RecurrentState``), whatever the context's length. The recurrence is
  ``ops/linear_attention.py``'s: chunked for a row of tokens, one step
  for a decode token.
- The feed-forward is ``models/mixtral.py``'s ``MoEFeedForward`` with a
  sigmoid router with a choice bias, one shared expert, and (where the
  config says so) only a share ``experts_held`` of the router's
  experts: one chip's of an expert-parallel group.

benchmarks/reference/solar_open2.py has the equations, token by token,
and says which of them ``config.json`` leaves open (assumed).

The model runs through ``transformer_forward`` as Llama and Mixtral do
(the full forward pass without a cache; the serving engine's paged
path). The static-cache ``generate``/``generate_stream`` of
models/llama.py know only K/V caches and do not serve it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kv_cache import (KIND_KV, KIND_RECURRENT,
                                     RecurrentStateView, decay_log_init,
                                     live_rows)
from ray_tpu.models.llama import (LlamaAttention, RMSNorm, block_forward,
                                  transformer_forward)
from ray_tpu.models.mixtral import MoEFeedForward
from ray_tpu.ops.linear_attention import kda_chunked, kda_step

L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """The published sizes (Solar-Open2-250B) under the names the shared
    modules read: ``hidden_dim`` is ONE expert's width, ``num_experts``
    the router's width."""
    vocab_size: int = 196608
    max_seq_len: int = 1048576
    dim: int = 4096
    n_layers: int = 48
    gqa_interval: int = 3          # KDA layers between two GQA layers
    n_heads: int = 64              # GQA
    n_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64            # KDA (its keys' and values' heads)
    kda_head_dim: int = 128
    conv_size: int = 4
    # beta = 2 sigmoid(.) in (0, 2), so that a step's transition may
    # have negative eigenvalues; False: beta = sigmoid(.)
    kda_allow_neg_eigval: bool = True
    hidden_dim: int = 1280
    num_experts: int = 320
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    router: str = "sigmoid_bias"
    experts_held: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"
    tie_word_embeddings: bool = False
    qk_norm: bool = False
    rope: bool = False
    rope_theta: float = 10000.0    # published, unused (``use_rope`` false)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind of per-request state (models/kv_cache.py)."""
        return tuple(KIND_KV if i % (self.gqa_interval + 1) == 0
                     else KIND_RECURRENT for i in range(self.n_layers))

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return SolarOpen2

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


def solar_open2_250b(**overrides) -> SolarOpen2Config:
    return SolarOpen2Config(**overrides)


def solar_open2_tiny(**overrides) -> SolarOpen2Config:
    """Test size: two periods of (GQA, KDA, KDA, KDA), 16 experts of
    which 4 a token, 1 shared."""
    d = dict(vocab_size=256, max_seq_len=256, dim=64, n_layers=8,
             n_heads=4, n_kv_heads=2, head_dim=16, kda_heads=4,
             kda_head_dim=16, hidden_dim=32, num_experts=16,
             num_experts_per_tok=4, n_shared_experts=1)
    d.update(overrides)
    return SolarOpen2Config(**d)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


class KDAAttention(nn.Module):
    """One KDA layer's token mixing on x [B, T, D] (already normed).
    ``kv_cache`` is None (a whole sequence from an empty state) or the
    layer's ``RecurrentStateView``: the state and the convolution tail
    of the rows' slots, which rows and positions are real, and nothing
    else. A row whose ``cache_len`` is 0 and whose first position is
    real STARTS A REQUEST: it begins from zeros, whatever its slot held
    (the engine never clears a slot). Positions that are not real move
    neither the state nor the tail.

    ``config`` is a ``SolarOpen2Config`` or any config with its KDA
    fields (models/kimi_linear.py's)."""
    config: Any

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        H, d, C = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width
        K = cfg.conv_size
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        f32 = jnp.float32
        qkv = jnp.concatenate(
            [dense(C, name=n)(x) for n in ("wq", "wk", "wv")], axis=-1)

        rc = kv_cache
        if rc is None:
            valid, fresh = jnp.ones((B, T), bool), None
            state = jnp.zeros((B,) + cfg.recurrent_state_shape, f32)
            tail = jnp.zeros((B, K - 1, 3 * C), cfg.dtype)
        else:
            if not isinstance(rc, RecurrentStateView):
                raise TypeError(
                    f"a KDA layer keeps a recurrent state, not "
                    f"{type(rc).__name__}: only the paged engine and the "
                    f"cache-less forward pass serve this model")
            valid = rc.valid
            fresh = (cache_len == 0) & valid[:, 0]
            with jax.named_scope("kda_conv"):
                tail = jnp.where(fresh[:, None, None], 0, rc.take(rc.conv))
            with jax.named_scope("kda_recurrence"):
                state = rc.take(rc.state)
                if T > 1:
                    # one token's step resets a fresh row itself, in
                    # the one pass it makes over the state
                    state = jnp.where(fresh[:, None, None, None], 0.0,
                                      state)

        with jax.named_scope("kda_conv"):
            conv = self.param("conv", nn.initializers.normal(K ** -0.5),
                              (K, 3 * C), cfg.param_dtype)
            before = jnp.concatenate([tail.astype(cfg.dtype), qkv], axis=1)
            wide = before.astype(f32)
            qkv = jax.nn.silu(sum(wide[:, j:j + T] * conv[j].astype(f32)
                                  for j in range(K)))
            # the last K-1 inputs up to each row's last real position
            # (real positions are a row's first ones)
            n_real = jnp.sum(valid, axis=1, dtype=jnp.int32)
            tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
                row, n, K - 1, axis=0))(before, n_real)
            q, k, v = (a.reshape(B, T, H, d)
                       for a in jnp.split(qkv, 3, axis=-1))
        with jax.named_scope("kda_gates"):
            q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
            decay_log = self.param("A_log", decay_log_init, (H,), f32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (C,), f32)
            f = dense(C, name="f_b")(dense(d, name="f_a")(x))
            g = -jnp.exp(decay_log)[:, None] * jax.nn.softplus(
                (f.astype(f32) + dt_bias).reshape(B, T, H, d))
            beta = jax.nn.sigmoid(dense(H, name="wb")(x).astype(f32))
            if cfg.kda_allow_neg_eigval:
                beta = 2.0 * beta
        with jax.named_scope("kda_recurrence"):
            if T == 1:
                o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], state, valid[:, 0], fresh)
                o = o[:, None]
            else:
                o, state = kda_chunked(q, k, v, g, beta, state, valid)
        with jax.named_scope("kda_out"):
            o = RMSNorm(cfg.norm_eps, name="o_norm")(o)
            gate = nn.Dense(C, use_bias=True, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name="g_b")(
                dense(d, name="g_a")(x))
            y = (o * jax.nn.sigmoid(gate.astype(f32)).reshape(B, T, H, d)
                 ).reshape(B, T, C).astype(cfg.dtype)
        out = dense(cfg.dim, name="wo")(y)
        if rc is None:
            return out, None
        with jax.named_scope("kda_recurrence"):
            new_state = rc.put(rc.state, state)
        with jax.named_scope("kda_conv"):
            new_conv = rc.put(rc.conv, tail)
        return out, rc._replace(state=new_state, conv=new_conv)


class _Block(nn.Module):
    config: SolarOpen2Config

    def attention(self):
        raise NotImplementedError

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        moe = MoEFeedForward(cfg, name="moe")
        live = live_rows(kv_cache)
        return block_forward(cfg, self.attention(), lambda h: moe(h, live),
                             x, freqs, positions, kv_cache, cache_len)


class SolarOpen2GQABlock(_Block):
    def attention(self):
        return LlamaAttention(self.config, rope=self.config.rope,
                              out_gate=True, name="attention")


class SolarOpen2KDABlock(_Block):
    def attention(self):
        return KDAAttention(self.config, name="attention")


_BLOCKS = {KIND_KV: SolarOpen2GQABlock, KIND_RECURRENT: SolarOpen2KDABlock}


class SolarOpen2(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``PagedKVLayer`` for a GQA layer and a ``RecurrentStateView`` for a
    KDA layer (models/kv_cache.py ``kv_layer_view``)."""
    config: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        kinds = self.config.layer_kinds
        return transformer_forward(self, self.config,
                                   lambda i: _BLOCKS[kinds[i]],
                                   input_ids, kv_caches, cache_len,
                                   rope=self.config.rope,
                                   logits_at=logits_at)


def solar_open2_param_count(cfg: SolarOpen2Config,
                            experts: Optional[int] = None) -> int:
    """Parameters, with ``experts`` routed experts a layer (the
    router's whole width where None)."""
    E = cfg.num_experts if experts is None else experts
    D, F, C, d = cfg.dim, cfg.hidden_dim, cfg.kda_width, cfg.kda_head_dim
    gqa = (2 * D * cfg.n_heads * cfg.head_dim          # wq, w_gate
           + 2 * D * cfg.n_kv_heads * cfg.head_dim
           + cfg.n_heads * cfg.head_dim * D)
    kda = (4 * D * C + cfg.conv_size * 3 * C           # wq wk wv wo, conv
           + 2 * (D * d + d * C) + C                   # f, g (+ g's bias)
           + C + cfg.kda_heads + D * cfg.kda_heads + d)  # dt A_log wb o_norm
    ffn = ((E + cfg.n_shared_experts) * 3 * D * F
           + D * cfg.num_experts + cfg.num_experts)    # router and bias
    n_gqa = cfg.layer_kinds.count(KIND_KV)
    return (2 * cfg.vocab_size * D + D
            + n_gqa * gqa + (cfg.n_layers - n_gqa) * kda
            + cfg.n_layers * (ffn + 2 * D))
