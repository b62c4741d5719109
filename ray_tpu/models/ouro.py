"""Ouro (ByteDance, ``model_type: ouro``): a decoder whose LAYERS RUN
SEVERAL TIMES. One stack of ``n_layers`` blocks is applied
``total_ut_steps`` times to the same hidden state, with the same
weights in every pass:

    h = E[ids]
    for t in 1..T:                         # T = total_ut_steps
      for l in 1..L:                       # the SAME weights every pass
        a = Attn_l(RMSNorm(h; g1_l))       # causal, rope on q and k; the
                                           #   K/V of this (t, l) in ITS
                                           #   OWN cache entry
        h = h + RMSNorm(a; g2_l)           # sandwich norm
        m = MLP_l(RMSNorm(h; g3_l))        # SwiGLU
        h = h + RMSNorm(m; g4_l)
      h = RMSNorm(h; g_final); s_t = h     # the final norm closes EVERY
                                           #   pass; the normed state goes on
      lam_t = sigmoid(w_exit . s_t + b_exit)
    p_t = lam_t prod_{j<t}(1 - lam_j) (t < T), p_T = prod_{j<T}(1 - lam_j)
    exit pass = first t with sum_{j<=t} p_j >= early_exit_threshold, else T
    logits = W_head s_(exit pass)

Attention, rope and the SwiGLU are models/llama.py's (``LlamaAttention``,
``LlamaMLP``, ``RMSNorm``, ``rope_freqs``); what is this module's is
the block of four norms, the loop over passes, the final norm inside it
and the exit gate.

THE CACHE HAS MORE ENTRIES THAN THE MODEL HAS LAYERS. A token keeps
T x L keys and values (192 for Ouro-2.6B behind 48 layers of weights),
and the serving engine's pool (models/kv_cache.py) holds them as L
entries of pages with a PASS AXIS inside the page:
``[n_pages, T, page_size, n_kv_heads, head_dim]`` for k and for v
(``OuroConfig.kv_entries_per_layer``, read by ``page_layout``). One
allocator page id is one page of ALL T x L entries, so the allocator,
copy-on-write, the prefix cache, speculation's offset clamp, int8 pages
and the KV pull's frames deal in page ids as they always did, and
``pool[page]`` is still one whole page. Layer l at pass t sees the free
reshape ``[n_pages x T, page_size, n_kv_heads, head_dim]`` under the
page table ``page_table x T + t`` (``_pass_view``): the two operations
of ops/paged_attention.py run on it unchanged.

THE PASSES ARE A DEVICE LOOP (``nn.scan`` over the pass index with the
parameters broadcast), so a step program holds ONE copy of the stack:
unrolled, Ouro-2.6B's programs would be 192 layer applications each.
The pool rides the loop's carry and is updated in place
(tests/test_chip_compile.py reads that off the compiled programs).

Every position takes every pass: the exit rule only CHOOSES among the
T normed states (at the published threshold of 1 it chooses the last).
Skipping a pass for a token that has exited is not done here
(ROADMAP.md).

The named scopes are metadata only (PERF.md section 3): ``ut_pass``
around a pass's stack, ``exit_gate`` around the gate and the choice,
``head`` around the output matrix, and inside a layer llama.py's
``kv_append``, ``kv_gather``, ``attn_scores``, ``attn_pv``.

The static-cache ``generate`` of models/llama.py knows one cache entry
a layer and does not serve this model: the full forward pass without a
cache and the serving engine's paged path do.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kv_cache import (PagedKVLayer, kv_layer_store,
                                     kv_layer_view)
from ray_tpu.models.llama import (LlamaAttention, LlamaMLP, RMSNorm,
                                  rope_freqs)


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The published sizes (Ouro-2.6B) under the names the shared
    modules read."""
    vocab_size: int = 49152
    max_seq_len: int = 65536
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 16
    hidden_dim: int = 5632
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    # passes of the one stack over a token (``total_ut_steps``), and the
    # cumulative exit probability at which a position's logits are taken
    # (``early_exit_threshold``; 1: the last pass's, for every position)
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    qk_norm: bool = False

    def __post_init__(self):
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps must be >= 1, got {self.total_ut_steps}")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def kv_entries_per_layer(self) -> int:
        """Cache entries a layer of weights keeps a token: one a pass
        (models/kv_cache.py ``page_layout`` gives a page that axis)."""
        return self.total_ut_steps

    @property
    def model_class(self):
        """What a deployment builds (models/llama.py ``LlamaConfig``);
        it declares no partition rules: the pool's sharding
        (serve/sharding.py) knows no page with a pass axis yet."""
        return Ouro


def ouro_2_6b(**overrides) -> OuroConfig:
    return OuroConfig(**overrides)


def ouro_tiny(**overrides) -> OuroConfig:
    """Test size: three layers run four times (twelve cache entries a
    token), 4 heads of 16."""
    d = dict(vocab_size=256, max_seq_len=1024, dim=64, n_layers=3,
             n_heads=4, n_kv_heads=4, hidden_dim=96, rope_theta=10000.0,
             total_ut_steps=4)
    d.update(overrides)
    return OuroConfig(**d)


class OuroBlock(nn.Module):
    """One layer's block: the attention and the SwiGLU each between two
    norms (the branch is normed before it joins the residual). ``x`` is
    the float32 residual stream; the matmuls read ``cfg.dtype``."""
    config: OuroConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        a, new_cache = LlamaAttention(cfg, name="attention")(
            RMSNorm(cfg.norm_eps, name="attention_norm")(x),
            freqs, positions, kv_cache, cache_len)
        # the normed branch joins the float32 stream as float32: the
        # norm computes there anyway, and its result is not rounded back
        x = x + RMSNorm(cfg.norm_eps, name="attention_post_norm")(
            a.astype(jnp.float32))
        m = LlamaMLP(cfg, name="feed_forward")(
            RMSNorm(cfg.norm_eps, name="ffn_norm")(x))
        return x + RMSNorm(cfg.norm_eps, name="ffn_post_norm")(
            m.astype(jnp.float32)), new_cache


def _pass_view(entry, page_table, t, passes: int) -> PagedKVLayer:
    """Layer entry ``entry`` (k, v[, scales]: ``[n_pages, passes, ...]``)
    as pass ``t``'s layer consumes it: the free reshape
    ``[n_pages x passes, ...]`` under the page table of the pass. The
    null page's passes are pages 0..passes-1 of that view: a row that
    carries no request writes there, as it always did."""
    flat = tuple(a.reshape((a.shape[0] * passes,) + a.shape[2:])
                 for a in entry)
    return kv_layer_view(flat, page_table * passes + t)


def _pass_store(cache: PagedKVLayer, like):
    """Inverse of ``_pass_view``: the entry in the pool's own shape."""
    return tuple(a.reshape(b.shape)
                 for a, b in zip(kv_layer_store(cache), like))


class OuroPass(nn.Module):
    """ONE pass of the stack, the body of the loop over passes:
    ``n_layers`` blocks and the final norm. carry = (h, the pool's
    entries or None); ``t`` is the pass index, a value."""
    config: OuroConfig

    @nn.compact
    def __call__(self, carry, t, freqs, positions, page_table, pos):
        cfg = self.config
        h, entries = carry
        new_entries = None if entries is None else []
        with jax.named_scope("ut_pass"):
            for i in range(cfg.n_layers):
                cache = None
                if entries is not None:
                    cache = _pass_view(entries[i], page_table, t,
                                       cfg.total_ut_steps)
                h, cache = OuroBlock(cfg, name=f"layers_{i}")(
                    h, freqs, positions, cache, pos)
                if entries is not None:
                    new_entries.append(_pass_store(cache, entries[i]))
            h = RMSNorm(cfg.norm_eps, name="norm")(h)
        return (h, new_entries), h


def exit_pass(lam, threshold: float):
    """lam [T, ...] float32, each pass's gate -> the index [...] of the
    pass a position's logits are taken from: the first whose cumulative
    exit probability reaches ``threshold``, the last where none does."""
    T = lam.shape[0]
    stay = jnp.cumprod(1.0 - lam, axis=0)        # prod_{j<=t} (1 - lam_j)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)
    reached = jnp.cumsum(p, axis=0) >= threshold
    reached = reached.at[T - 1].set(True)
    return jnp.argmax(reached, axis=0)


class Ouro(nn.Module):
    """Call signature as models/llama.py Llama's; ``kv_caches`` holds a
    ``PagedKVLayer`` a layer whose pages carry the pass axis
    (models/kv_cache.py ``kv_layer_view`` over this config's pool)."""
    config: OuroConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        cfg = self.config
        B, T = input_ids.shape
        passes = cfg.total_ut_steps
        tok = self.param("tok_embeddings", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.dim), cfg.param_dtype)
        # THE RESIDUAL STREAM IS FLOAT32 (the matmuls read and write
        # cfg.dtype): 192 layer applications add 384 unit-size branches
        # to it, and with every add rounded to bfloat16 the served
        # tokens lay 3.6 times further under the float32 reference's
        # best on the chip (PERF.md section 6, PR 46); it is [rows,
        # dim] floats, nothing beside a layer's weights
        x = tok[input_ids].astype(jnp.float32)
        freqs = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        entries = page_table = pos = None
        if kv_caches is None:
            positions = jnp.arange(T)
        else:
            if not all(isinstance(c, PagedKVLayer) for c in kv_caches) \
                    or jnp.ndim(cache_len) != 1:
                raise TypeError(
                    "a looped model keeps total_ut_steps cache entries a "
                    "layer in pages with a pass axis: only the paged "
                    "engine and the cache-less forward pass serve it")
            positions = cache_len[:, None] + jnp.arange(T)[None]
            page_table = kv_caches[0].page_table
            entries = [kv_layer_store(c) for c in kv_caches]
            # a row that carries no request (its page-table row is the
            # null row) may hold a stale, large position: under a pass's
            # table its row is no longer 0, so the window loop would
            # take it for live and widen the attended window to it
            # (ops/paged_attention.py ``live``). It writes at offset 0
            # of the null page instead, and sees one key.
            pos = jnp.where(page_table[:, 0] != 0, cache_len, 0)
        loop = nn.scan(
            OuroPass, variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=(0,) + (nn.broadcast,) * 4, length=passes)
        (_, entries), states = loop(cfg, name="stack")(
            (x, entries), jnp.arange(passes, dtype=jnp.int32), freqs,
            positions, page_table, pos)
        if logits_at is not None:
            # one position's logits a row (models/llama.py
            # transformer_forward): the gate, the choice among passes
            # and the head are each a position's own
            states = states[:, jnp.arange(B), logits_at, None]
        with jax.named_scope("exit_gate"):
            # one number a position and pass, in float32; every pass
            # was taken: the rule only chooses among the normed states
            gate = nn.Dense(1, dtype=jnp.float32, param_dtype=jnp.float32,
                            name="exit_gate")(states.astype(jnp.float32))
            chosen = exit_pass(jax.nn.sigmoid(gate[..., 0]),
                               cfg.early_exit_threshold)        # [B, T]
            x = jnp.take_along_axis(
                states, chosen[None, :, :, None], axis=0)[0]
        if logits_at is not None:
            x = x[:, 0]                                         # [B, dim]
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.vocab_size, cfg.dim), cfg.param_dtype)
        with jax.named_scope("head"):
            logits = jax.lax.dot_general(
                x.astype(cfg.dtype), head.astype(cfg.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        if kv_caches is None:
            return logits, None
        return logits, [kv_layer_view(e, page_table) for e in entries]


def ouro_param_count(cfg: OuroConfig) -> int:
    """Parameters: the one stack's, whatever the passes."""
    D = cfg.dim
    attention = 2 * D * cfg.n_heads * cfg.head_dim + \
        2 * D * cfg.n_kv_heads * cfg.head_dim
    layer = attention + 3 * D * cfg.hidden_dim + 4 * D
    return 2 * cfg.vocab_size * D + D + (D + 1) + cfg.n_layers * layer
