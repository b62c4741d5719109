"""Olmo-Hybrid (allenai, ``model_type: olmo_hybrid``): a DENSE hybrid
decoder. Three layers in four are Gated DeltaNet (``GatedDeltaNet``
below): the delta rule of ``ops/linear_attention.py`` gated by ONE
decay a head, over heads whose values are twice as wide as their keys
(96 x 192), a causal depthwise convolution on q, k and v before it and
an RMSNorm and a SiLU gate after it. Such a layer keeps no K/V: its
state is a fixed-size float32 matrix a head plus the convolution's last
inputs, a SLOT of the serving engine (models/kv_cache.py
``RecurrentState``). The fourth is plain multi-head softmax attention
with NO position encoding and an RMSNorm over the WHOLE projected query
and key (``FullAttention`` below), whose K and V live in the paged pool,
30 heads stored as 32 (``kv_page_heads``). Every layer's feed-forward is
``LlamaMLP``: no mixture anywhere, so a decode step is weights, state
and K/V and nothing else.

The block is the OLMo 2 / OLMo 3 REORDERED NORM: the norm sits on each
branch's OUTPUT, none on its input,

    h = x + RMSNorm(mixer(x));   out = h + RMSNorm(mlp(h)),

so ``block_forward`` (pre-norm) does not serve it and the block below
does.

benchmarks/reference/olmo_hybrid.py has the equations, token by token,
and says which of them ``config.json`` leaves open (assumed).

The model runs through ``transformer_forward`` as the other families do
(the full forward pass without a cache; the serving engine's paged
path). The static-cache ``generate`` of models/llama.py knows only K/V
caches and does not serve it.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kv_cache import (KIND_KV, KIND_RECURRENT, PagedKVLayer,
                                     RecurrentStateView, decay_log_init)
from ray_tpu.models.llama import LlamaMLP, RMSNorm, transformer_forward
from ray_tpu.models.solar_open2 import _l2norm
from ray_tpu.ops.attention import multi_head_attention
from ray_tpu.ops.linear_attention import (kda_chunked, kda_step, pack_heads,
                                          unpack_heads)
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)

LINEAR, FULL = "linear_attention", "full_attention"
_KINDS = {LINEAR: KIND_RECURRENT, FULL: KIND_KV}


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """The published sizes (Olmo-Hybrid-7B) under the names the shared
    modules read: ``n_heads``/``n_kv_heads`` are the full layers'
    (``FullAttention``; their head is ``dim / n_heads``), ``hidden_dim``
    the SwiGLU's (``LlamaMLP``), the ``linear_*`` fields and
    ``conv_size`` the delta rule's."""
    vocab_size: int = 100352
    max_seq_len: int = 65536
    dim: int = 3840
    n_layers: int = 32
    # each layer's type as published; entries past ``n_layers`` name
    # layers a cut lacks
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
    n_heads: int = 30
    n_kv_heads: int = 30
    hidden_dim: int = 11008
    linear_heads: int = 30         # key heads = value heads
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    conv_size: int = 4
    # beta = 2 sigmoid(.) in (0, 2), so that a step's transition may
    # have negative eigenvalues; False: beta = sigmoid(.)
    linear_allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"
    tie_word_embeddings: bool = False
    # an RMSNorm over the whole projected q and k (the family's own;
    # False is a test's control)
    qk_norm: bool = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def kv_page_heads(self) -> int:
        """Head rows a K/V page stores a token (models/kv_cache.py
        ``kv_page_heads`` says why): the K/V heads rounded up to whole
        16-row tiles of a bfloat16 array, 30 as 32, the rest zeros."""
        return -(-self.n_kv_heads // 16) * 16

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind of per-request state (models/kv_cache.py)."""
        return tuple(_KINDS[t] for t in self.layer_types[:self.n_layers])

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: none exist yet."""
        return OlmoHybrid

    @property
    def linear_widths(self) -> Tuple[int, int, int]:
        """The widths of the projected q, k and v of a linear layer."""
        H = self.linear_heads
        return (H * self.linear_key_head_dim, H * self.linear_key_head_dim,
                H * self.linear_value_head_dim)

    @property
    def state_pack(self) -> int:
        """Heads stored side by side on the lanes (ops/linear_attention.py
        ``pack_heads``): the fewest whose values fill whole 128-lane
        tiles (two heads of 192 are three tiles; a head alone is padded
        to 256 by the chip, a third more bytes kept and moved a step),
        where they divide the heads; else 1."""
        p = 128 // math.gcd(self.linear_value_head_dim, 128)
        return p if self.linear_heads % p == 0 else 1

    @property
    def recurrent_state_shape(self) -> Tuple[int, int, int]:
        """One slot's delta-rule state, a linear layer, AS IT IS STORED:
        [H / p, dk, p x dv] with p = ``state_pack``."""
        p = self.state_pack
        return (self.linear_heads // p, self.linear_key_head_dim,
                p * self.linear_value_head_dim)

    @property
    def recurrent_conv_shape(self) -> Tuple[int, int]:
        """One slot's convolution tail, a linear layer: the last
        ``conv_size - 1`` inputs of q, k and v."""
        return (self.conv_size - 1, sum(self.linear_widths))


def olmo_hybrid_7b(**overrides) -> OlmoHybridConfig:
    return OlmoHybridConfig(**overrides)


def olmo_hybrid_tiny(**overrides) -> OlmoHybridConfig:
    """Test size: two periods of (linear, linear, linear, full); 6 heads
    (no multiple of 16) of 12 x 64 (keys and values of different
    widths, the values half a lane tile, so stored two heads side by
    side as the served 96 x 192 are) beside 6 full heads of 8."""
    d = dict(vocab_size=256, max_seq_len=256, dim=48, n_layers=8,
             layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2, n_heads=6,
             n_kv_heads=6, hidden_dim=96, linear_heads=6,
             linear_key_head_dim=12, linear_value_head_dim=64)
    d.update(overrides)
    return OlmoHybridConfig(**d)


class FullAttention(nn.Module):
    """One full layer's attention on x [B, T, D]: causal softmax
    attention of ``n_heads`` query heads on ``n_kv_heads`` K/V heads, no
    position encoding, query and key normed whole before the split into
    heads. ``kv_cache`` is None (a whole sequence from position 0) or
    the layer's ``PagedKVLayer``, whose pages store ``kv_page_heads``
    head rows a token: q, k and v are padded alike with heads of zeros
    (whole K/V heads with their own groups of query heads),
    appended and attended as any paged layer's, and the padding's
    output (zeros) is dropped. The scopes inside ``attn_full`` are
    ``LlamaAttention``'s (``kv_append`` here; the page window's own)."""
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        q = dense(H * hd, name="wq")(x)
        k = dense(KH * hd, name="wk")(x)
        v = dense(KH * hd, name="wv")(x)
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        q = q.reshape(B, T, H, hd)
        k, v = k.reshape(B, T, KH, hd), v.reshape(B, T, KH, hd)
        if kv_cache is None:
            rep = H // KH
            y = multi_head_attention(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                causal=True, impl=cfg.attention_impl)
            new_cache = None
        else:
            if not isinstance(kv_cache, PagedKVLayer) \
                    or kv_cache.pages_v is None:
                raise TypeError(
                    f"a full-attention layer of this model keeps K/V "
                    f"pages, not {type(kv_cache).__name__}: only the "
                    f"paged engine and the cache-less forward pass "
                    f"serve it (its other layers keep a recurrent state)")
            pc = kv_cache
            rows = pc.pages_k.shape[-2] - KH        # the pages' padding

            def padded(a, rows=rows):
                return jnp.pad(a, ((0, 0), (0, 0), (0, rows), (0, 0)))
            with jax.named_scope("attn_full"):
                with jax.named_scope("kv_append"):
                    appended = paged_append(
                        pc.pages_k, pc.pages_v, pc.page_table, cache_len,
                        padded(k), padded(v), pc.scales_k, pc.scales_v)
                if pc.quantized:
                    pk, pv, sk, sv = appended
                    new_cache = pc._replace(pages_k=pk, pages_v=pv,
                                            scales_k=sk, scales_v=sv)
                else:
                    (pk, pv), sk, sv = appended, None, None
                    new_cache = pc._replace(pages_k=pk, pages_v=pv)
                # a padding K/V head's own group of query heads
                y = _paged_window_attention(
                    padded(q, rows * (H // KH)), pk, pv, sk, sv,
                    pc.page_table, cache_len)[:, :, :H]
        out = dense(cfg.dim, name="wo")(
            y.reshape(B, T, H * hd).astype(cfg.dtype))
        return out, new_cache


class GatedDeltaNet(nn.Module):
    """One linear layer's token mixing on x [B, T, D]. ``kv_cache`` is
    None (a whole sequence from an empty state) or the layer's
    ``RecurrentStateView``, read as models/solar_open2.py's
    ``KDAAttention`` reads it: a row whose ``cache_len`` is 0 and whose
    first position is real STARTS A REQUEST and begins from zeros,
    whatever its slot held; positions that are not real move neither
    the state nor the tail. The scopes are that module's too (the trace
    readers find the recurrence by them); what differs is the gate (ONE
    decay a head: ``g`` [B, T, H]), the heads' two widths, and the
    output's gate (SiLU of a full-rank projection)."""
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        H, dk, dv = (cfg.linear_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        widths, K, pack = cfg.linear_widths, cfg.conv_size, cfg.state_pack
        dense = functools.partial(nn.Dense, use_bias=False,
                                  dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)
        f32 = jnp.float32
        qkv = jnp.concatenate(
            [dense(w, name=n)(x)
             for n, w in zip(("wq", "wk", "wv"), widths)], axis=-1)

        rc = kv_cache
        if rc is None:
            valid, fresh = jnp.ones((B, T), bool), None
            state = jnp.zeros((B,) + cfg.recurrent_state_shape, f32)
            tail = jnp.zeros((B, K - 1, sum(widths)), cfg.dtype)
        else:
            if not isinstance(rc, RecurrentStateView):
                raise TypeError(
                    f"a linear-attention layer keeps a recurrent state, "
                    f"not {type(rc).__name__}: only the paged engine and "
                    f"the cache-less forward pass serve this model")
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
                              (K, sum(widths)), cfg.param_dtype)
            before = jnp.concatenate([tail.astype(cfg.dtype), qkv], axis=1)
            wide = before.astype(f32)
            qkv = jax.nn.silu(sum(wide[:, j:j + T] * conv[j].astype(f32)
                                  for j in range(K)))
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
            q, k, v = jnp.split(qkv, (widths[0], widths[0] + widths[1]),
                                axis=-1)
            q, k = q.reshape(B, T, H, dk), k.reshape(B, T, H, dk)
            v = v.reshape(B, T, H, dv)
        with jax.named_scope("kda_gates"):
            q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
            decay_log = self.param("A_log", decay_log_init, (H,), f32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,), f32)
            # ONE log-decay a head
            g = -jnp.exp(decay_log) * jax.nn.softplus(
                dense(H, name="wa")(x).astype(f32) + dt_bias)
            beta = jax.nn.sigmoid(dense(H, name="wb")(x).astype(f32))
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
        with jax.named_scope("kda_recurrence"):
            if T == 1:
                # the state steps as it is stored
                o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], state, valid[:, 0], fresh)
                o = o[:, None]
            else:
                # a call's few rows: a head at a time, and back
                o, state = kda_chunked(q, k, v, g, beta,
                                       unpack_heads(state, pack), valid)
                state = pack_heads(state, pack)
        with jax.named_scope("kda_out"):
            o = RMSNorm(cfg.norm_eps, name="o_norm")(o)
            gate = dense(widths[2], name="wz")(x).astype(f32)
            y = (o * jax.nn.silu(gate).reshape(B, T, H, dv)
                 ).reshape(B, T, widths[2]).astype(cfg.dtype)
        out = dense(cfg.dim, name="wo")(y)
        if rc is None:
            return out, None
        with jax.named_scope("kda_recurrence"):
            new_state = rc.put(rc.state, state)
        with jax.named_scope("kda_conv"):
            new_conv = rc.put(rc.conv, tail)
        return out, rc._replace(state=new_state, conv=new_conv)


class OlmoHybridBlock(nn.Module):
    """Layer ``index``'s block: its token mixing by the layer's type,
    then the SwiGLU, each branch normed on its way OUT."""
    config: OlmoHybridConfig
    index: int = 0

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        mixer = (FullAttention if cfg.layer_types[self.index] == FULL
                 else GatedDeltaNet)
        h, new_cache = mixer(cfg, name="attention")(
            x, freqs, positions, kv_cache, cache_len)
        x = x + RMSNorm(cfg.norm_eps, name="attention_post_norm")(h)
        m = LlamaMLP(cfg, name="feed_forward")(x)
        return x + RMSNorm(cfg.norm_eps, name="ffn_post_norm")(m), new_cache


class OlmoHybrid(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``RecurrentStateView`` for a linear layer and a ``PagedKVLayer`` for
    a full one (models/kv_cache.py ``kv_layer_view``)."""
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        return transformer_forward(
            self, self.config,
            lambda i: functools.partial(OlmoHybridBlock, index=i),
            input_ids, kv_caches, cache_len, rope=False,
            logits_at=logits_at)


def linear_param_count(cfg: OlmoHybridConfig) -> int:
    """One linear layer's token mixing: q, k, v, the output's gate and
    the output projection, the decay's and beta's projections, the
    convolution, the decay's bias and rate and the output norm."""
    D, (wq, wk, wv) = cfg.dim, cfg.linear_widths
    return (D * (wq + wk + 3 * wv) + 2 * D * cfg.linear_heads
            + cfg.conv_size * (wq + wk + wv) + 2 * cfg.linear_heads
            + cfg.linear_value_head_dim)


def olmo_hybrid_param_count(cfg: OlmoHybridConfig) -> int:
    D = cfg.dim
    wq, wkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    # four projections, the q and k norms
    full = 2 * D * wq + 2 * D * wkv + wq + wkv
    n_full = cfg.layer_kinds.count(KIND_KV)
    return (2 * cfg.vocab_size * D + D
            + n_full * full
            + (cfg.n_layers - n_full) * linear_param_count(cfg)
            + cfg.n_layers * (3 * D * cfg.hidden_dim + 2 * D))
