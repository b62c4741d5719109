"""Llama-family transformer in flax, mesh-first, with a jitted
KV-cache generation loop.

The reference has no model zoo; this family exists for the build's
serving north star (BASELINE.md: "Serve Llama-2-7B JAX replicas
autoscaled on v5e") and as the GQA/RoPE/SwiGLU exemplar of the model
stack. TPU design mirrors models/gpt2.py: bf16 matmuls with fp32
norms/logits, MXU-friendly dims, sharding declared as logical-axis
rules (Megatron TP + FSDP), pallas/XLA attention via ray_tpu.ops.
Decode uses a static-shape KV cache updated with dynamic_update_slice
inside one jitted lax.while_loop — no per-token retrace.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ray_tpu.mesh.sharding import ShardingRules
from ray_tpu.models.kv_cache import PagedKVLayer, sampled_only_from
from ray_tpu.ops.paged_attention import (_paged_window_attention,
                                         paged_append)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32          # < n_heads => grouped-query attention
    hidden_dim: int = 11008       # SwiGLU inner dim
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"
    # False: logits go through a separate ``lm_head`` matrix [V, dim]
    # (Mistral, OLMoE publish ``tie_word_embeddings: false``)
    tie_word_embeddings: bool = True
    # True: an RMSNorm with a learned scale over the WHOLE projected
    # query and key vectors, before the split into heads and before
    # rope (OLMoE's ``q_norm``/``k_norm``)
    qk_norm: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    # What serving asks of a family's config: the class a deployment
    # builds (serve/llm.py) and, where the family can be sharded, its
    # partition rules and divisibility check (serve/sharding.py
    # ``family_sharding_rules`` / ``validate_tp``; a config without
    # them is refused there by name).
    @property
    def model_class(self):
        return Llama

    @property
    def serving_rules(self) -> ShardingRules:
        return llama_sharding_rules(fsdp=False)

    def tp_validate(self, tp: int, ep: int = 1) -> None:
        if ep != 1:
            raise ValueError(
                f"expert parallelism ep={ep} needs an MoE config, "
                f"got {type(self).__name__}")
        llama_tp_validate(self, tp)


def llama2_7b(**overrides) -> LlamaConfig:
    return LlamaConfig(**overrides)


def llama_tiny(**overrides) -> LlamaConfig:
    """Test-size config for CPU-mesh tests (GQA exercised: 4 q heads,
    2 kv heads)."""
    d = dict(vocab_size=256, max_seq_len=128, dim=64, n_layers=2,
             n_heads=4, n_kv_heads=2, hidden_dim=128)
    d.update(overrides)
    return LlamaConfig(**d)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, max_len: int, theta: float) -> jnp.ndarray:
    inv = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    return jnp.outer(t, inv)   # [max_len, head_dim/2]


def apply_rope(x: jnp.ndarray, freqs: jnp.ndarray,
               positions: jnp.ndarray) -> jnp.ndarray:
    """x: [B, T, H, D]; positions: [T] or [B, T]."""
    f = freqs[positions]                       # [..., T, D/2]
    if f.ndim == 2:
        f = f[None]                            # [1, T, D/2]
    cos = jnp.cos(f)[..., None, :]             # [B|1, T, 1, D/2]
    sin = jnp.sin(f)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(x.dtype)


class LlamaAttention(nn.Module):
    """``rope`` false: no position encoding (``freqs`` is not read).
    ``out_gate`` true: the heads' output times sigmoid(x W_gate), one
    gate a channel, before ``wo``. The defaults are Llama's, and leave
    its parameter tree and programs as they were."""
    config: LlamaConfig
    rope: bool = True
    out_gate: bool = False

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        B, T, _ = x.shape
        hd = cfg.head_dim
        q = nn.Dense(cfg.n_heads * hd, use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="wq")(x)
        k = nn.Dense(cfg.n_kv_heads * hd, use_bias=False,
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="wk")(x)
        v = nn.Dense(cfg.n_kv_heads * hd, use_bias=False,
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="wv")(x)
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        q = q.reshape(B, T, cfg.n_heads, hd)
        k = k.reshape(B, T, cfg.n_kv_heads, hd)
        v = v.reshape(B, T, cfg.n_kv_heads, hd)
        if self.rope:
            q = apply_rope(q, freqs, positions)
            k = apply_rope(k, freqs, positions)

        new_cache = None
        if isinstance(kv_cache, PagedKVLayer):
            # Paged attention (continuous batching) with per-slot
            # positions. T == 1 is the decode step; T > 1 is a
            # chunked-prefill chunk whose tokens APPEND AT OFFSET
            # (possibly mid-page, possibly spanning pages). Scatter
            # this chunk's K/V into the slots' pages, then attend
            # each query over its slot's gathered page window under
            # a causal mask on absolute positions. Inactive slots
            # carry page_table rows of 0 (the null page) — their
            # writes land there and their outputs are ignored
            # host-side, so no lax.cond is needed.
            pc = kv_cache
            pos = cache_len                       # [B] int32
            # The named scopes (kv_append here; kv_gather, attn_scores,
            # attn_pv inside _paged_window_attention) are metadata
            # only: a device trace splits a step's time by them
            # (PERF.md section 3); the compiled program is the same
            # with or without them.
            with jax.named_scope("kv_append"):
                appended = paged_append(
                    pc.pages_k, pc.pages_v, pc.page_table, pos, k, v,
                    pc.scales_k, pc.scales_v)
            if pc.quantized:
                # int8 pool: append quantizes in place and returns
                # updated per-page scales, which travel WITH the
                # pages through the cache pytree (COW, donation,
                # placement all move them together).
                pk, pv, sk, sv = appended
                new_cache = pc._replace(pages_k=pk, pages_v=pv,
                                        scales_k=sk, scales_v=sv)
            else:
                pk, pv = appended
                sk = sv = None
                new_cache = pc._replace(pages_k=pk, pages_v=pv)
            # decode step and prefill chunk alike: gather and attend
            # over the batch's longest live context, a block of pages
            # at a time
            y = _paged_window_attention(
                q, pk, pv, sk, sv, pc.page_table, pos)
        elif kv_cache is not None:
            # Decode path: append this step's K/V into the static cache.
            ck, cv = kv_cache
            with jax.named_scope("kv_append"):
                ck = jax.lax.dynamic_update_slice(
                    ck, k.astype(ck.dtype), (0, cache_len, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, v.astype(cv.dtype), (0, cache_len, 0, 0))
            new_cache = (ck, cv)
            k, v = ck, cv
            S = k.shape[1]
            # Mask out positions beyond cache_len + T.
            kv_pos = jnp.arange(S)
            valid = kv_pos < (cache_len + T)
            # grouped-query contraction (no repeated-K/V copy; see
            # the paged branch above)
            rep = cfg.n_heads // cfg.n_kv_heads
            qg = q.reshape(B, T, cfg.n_kv_heads, rep, hd)
            with jax.named_scope("attn_scores"):
                scores = jnp.einsum(
                    "btkrd,bskd->bkrts", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) / np.sqrt(hd)
                q_pos = cache_len + jnp.arange(T)
                causal = kv_pos[None, :] <= q_pos[:, None]
                mask = (causal & valid[None, :])[None, None, None]
                scores = jnp.where(mask, scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
            with jax.named_scope("attn_pv"):
                y = jnp.einsum("bkrts,bskd->btkrd",
                               probs.astype(v.dtype), v)
            y = y.reshape(B, T, cfg.n_heads, hd)
        else:
            rep = cfg.n_heads // cfg.n_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            from ray_tpu.ops.attention import multi_head_attention
            y = multi_head_attention(q, k, v, causal=True,
                                     impl=cfg.attention_impl)
        y = y.reshape(B, T, cfg.n_heads * hd)
        if self.out_gate:
            with jax.named_scope("attn_gate"):
                gate = nn.Dense(cfg.n_heads * hd, use_bias=False,
                                dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                name="w_gate")(x)
                y = y.astype(cfg.dtype) * jax.nn.sigmoid(gate)
        out = nn.Dense(cfg.dim, use_bias=False, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="wo")(y)
        return out, new_cache


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = nn.Dense(cfg.hidden_dim, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="w1")(x)
        up = nn.Dense(cfg.hidden_dim, use_bias=False, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="w3")(x)
        return nn.Dense(cfg.dim, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="w2")(
            nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, freqs, positions, kv_cache=None,
                 cache_len=None):
        cfg = self.config
        return block_forward(
            cfg, LlamaAttention(cfg, name="attention"),
            LlamaMLP(cfg, name="feed_forward"),
            x, freqs, positions, kv_cache, cache_len)


def transformer_forward(mod: nn.Module, cfg, block_of, input_ids,
                        kv_caches=None, cache_len=None, rope=True,
                        logits_at=None, norm=None, publishes=False,
                        embed_scale=None):
    """Shared decoder-transformer body (embedding, RoPE table,
    position/cache plumbing, layer loop, final norm, logits through
    the embedding or, with ``tie_word_embeddings`` false, through a
    separate ``lm_head``).
    Every Llama-shaped family (Llama, Mixtral) calls this with its own
    block class, so the decode contract `generate`/`generate_stream`
    rely on cannot drift per family. ``block_of`` gives layer i's
    block class: one class for every layer, or a class by the layer's
    kind (models/solar_open2.py). ``rope`` false: no position
    encoding, so no table (the blocks get None). ``logits_at`` ([B]
    int32): the one position of each row whose logits the caller wants;
    the final norm and the head then see ``[B, dim]`` and the logits are
    ``[B, V]`` (the chunked-prefill program samples one position a row;
    the norm and the head are each a position's own, so the gather may
    come first). Over a cache the gather comes earlier still where the
    model's LAST layers keep no entry (models/kv_cache.py
    ``sampled_only_from``, read from the layers' kinds: ``n_layers``, so
    right here before the norm, for every family whose last layer keeps
    one): before the first of them the call is narrowed to the sampled
    position a row, ``x`` ``[B, 1, dim]`` at ``positions`` and
    ``cache_len`` of that position (``cache_len + logits_at``: the one
    query's absolute position, as a decode step's), and those layers run
    what a decode step runs; every layer before them has seen, and kept,
    every position. (Without a cache nothing is narrowed: a layer that
    borrows then reads the whole sequence's keys under a mask of its OWN
    positions, not pages.) None: every position, ``[B, T, V]``. ``norm``: the
    class of the final norm where it is not ``RMSNorm`` (built as
    ``norm(cfg.norm_eps, name="norm")``). ``publishes``: falsy, or a
    dict; then the blocks take and return, after their five arguments
    and two results, a dict of what earlier blocks of THIS call
    published for later ones (models/phi4flash.py: an activation, a
    layer's pages after its append), empty before layer 0 and dropped
    after the last: nothing of it is cached. ``publishes`` itself names
    every key the blocks may publish and says of each whether its value
    is laid out BY POSITION (``[B, T, ...]``: true) or stands for the
    whole call (false): the publishing family's to say, since a shape
    cannot (a cache-less call's keys of the whole sequence are
    ``[B, T, ...]`` and every later position reads all of them). Where
    the call is narrowed a value laid out by position is narrowed with
    ``x``, every other is handed on as it is. Called from a
    compact __call__: submodules bind into the caller's scope."""
    B, T = input_ids.shape
    tok = mod.param("tok_embeddings",
                    nn.initializers.normal(0.02),
                    (cfg.vocab_size, cfg.dim), cfg.param_dtype)
    x = tok[input_ids].astype(cfg.dtype)
    if embed_scale is not None:
        x = x * jnp.asarray(embed_scale, x.dtype)
    freqs = (rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
             if rope else None)
    if cache_len is None:
        positions = jnp.arange(T)
    elif jnp.ndim(cache_len) == 1:
        # Per-slot positions (paged continuous-batching decode):
        # [B] + [T] -> [B, T]; apply_rope handles batched positions.
        positions = cache_len[:, None] + jnp.arange(T)[None]
    else:
        positions = cache_len + jnp.arange(T)
    new_caches = []
    published = {}
    narrow_at = (sampled_only_from(cfg) if logits_at is not None
                 and kv_caches is not None else cfg.n_layers)

    def sampled(v):
        """``v`` [B, T, ...] at each row's sampled position: [B, ...]."""
        return v[jnp.arange(B), logits_at]
    for i in range(cfg.n_layers):
        if i == narrow_at:
            # from here on no layer keeps an entry: the rest of the
            # stack serves the sampled positions alone, one query a row
            with jax.named_scope("sampled_only"):
                x = sampled(x)[:, None]
                cache_len = cache_len + logits_at
                positions = cache_len[:, None]
                published = {key: sampled(v)[:, None] if publishes[key]
                             else v for key, v in published.items()}
        block = block_of(i)
        if cfg.remat:
            block = nn.remat(block, static_argnums=())
        cache_i = None if kv_caches is None else kv_caches[i]
        if publishes:
            x, nc, published = block(cfg, name=f"layers_{i}")(
                x, freqs, positions, cache_i, cache_len, published)
        else:
            x, nc = block(cfg, name=f"layers_{i}")(
                x, freqs, positions, cache_i, cache_len)
        new_caches.append(nc)
    if logits_at is not None:
        # [B, dim]
        x = x[:, 0] if narrow_at < cfg.n_layers else sampled(x)
    x = (norm or RMSNorm)(cfg.norm_eps, name="norm")(x)
    head = tok
    if not cfg.tie_word_embeddings:
        head = mod.param("lm_head", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.dim), cfg.param_dtype)
    with jax.named_scope("head"):
        logits = jax.lax.dot_general(
            x.astype(cfg.dtype), head.astype(cfg.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    if kv_caches is None:
        return logits, None
    return logits, new_caches


def block_forward(cfg, attention, ffn_module, x, freqs, positions,
                  kv_cache=None, cache_len=None, norm=None):
    """Shared pre-norm block body: attention residual + FFN residual.
    ``attention`` (a module named "attention": LlamaAttention, or a
    layer that keeps a recurrent state instead of K/V) and the FFN
    module are what varies across families and kinds of layer, and
    ``norm`` the two norms' class where it is not ``RMSNorm``."""
    norm = norm or RMSNorm
    h, new_cache = attention(
        norm(cfg.norm_eps, name="attention_norm")(x),
        freqs, positions, kv_cache, cache_len)
    x = x + h
    x = x + ffn_module(norm(cfg.norm_eps, name="ffn_norm")(x))
    return x, new_cache


class Llama(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, kv_caches=None, cache_len=None,
                 logits_at=None):
        """Returns (logits, new_kv_caches). kv_caches: list per layer of
        (k, v) arrays [B, max_seq, n_kv_heads, head_dim]. ``logits_at``
        [B]: one position's logits a row (transformer_forward)."""
        return transformer_forward(self, self.config,
                                   lambda i: LlamaBlock,
                                   input_ids, kv_caches, cache_len,
                                   logits_at=logits_at)


def init_kv_caches(cfg: LlamaConfig, batch: int, max_len: int):
    return [
        (jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                   cfg.dtype),
         jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                   cfg.dtype))
        for _ in range(cfg.n_layers)]


_CACHE_CAP = 32       # compiled decode variants kept per process


def _cache_get(cache: "collections.OrderedDict", key):
    """Bounded LRU for compiled decode closures: long-lived serving
    replicas see many (batch, prompt-length) shapes; unbounded caching
    would pin every jit executable + model closure forever."""
    hit = cache.get(key)
    if hit is not None:
        cache.move_to_end(key)
    return hit


def _cache_put(cache: "collections.OrderedDict", key, value):
    cache[key] = value
    cache.move_to_end(key)
    while len(cache) > _CACHE_CAP:
        cache.popitem(last=False)


import collections

_DECODE_CACHE: "collections.OrderedDict" = collections.OrderedDict()


def generate(model: Llama, params, prompt_ids: jnp.ndarray,
             max_new_tokens: int, temperature: float = 0.0,
             rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None) -> jnp.ndarray:
    """Jitted autoregressive decode: one prefill call, then a
    lax.while_loop of single-token steps over a static KV cache. The
    jitted function is cached per (config, batch, prompt_len,
    max_new_tokens, temperature, eos) so repeated calls — e.g. serve
    requests — reuse one compilation.
    """
    cfg = model.config
    B, T0 = prompt_ids.shape
    total = T0 + max_new_tokens
    if rng is None:
        rng = jax.random.PRNGKey(0)
    cache_key = (cfg, B, T0, max_new_tokens, temperature, eos_id)
    cached = _cache_get(_DECODE_CACHE, cache_key)
    if cached is not None:
        return cached(params, prompt_ids, rng)

    @jax.jit
    def _decode(params, prompt_ids, rng):
        caches = init_kv_caches(cfg, B, total)
        logits, caches = model.apply(params, prompt_ids,
                                     kv_caches=caches, cache_len=0)
        tokens = jnp.zeros((B, total), jnp.int32)
        tokens = jax.lax.dynamic_update_slice(tokens, prompt_ids, (0, 0))

        first = _pick_token(logits[:, -1], rng, temperature)
        tokens = jax.lax.dynamic_update_slice(
            tokens, first[:, None], (0, T0))

        def cond(state):
            i, _tokens, _caches, _key, done_rows = state
            return (i < max_new_tokens) & ~jnp.all(done_rows)

        def body(state):
            i, tokens, caches, key, done_rows = state
            key, sub = jax.random.split(key)
            cur = jax.lax.dynamic_slice(tokens, (0, T0 + i - 1),
                                        (B, 1))
            logits, caches = model.apply(
                params, cur, kv_caches=caches, cache_len=T0 + i - 1)
            nxt = _pick_token(logits[:, -1], sub, temperature)
            tokens = jax.lax.dynamic_update_slice(
                tokens, nxt[:, None], (0, T0 + i))
            if eos_id is not None:
                # Per-row flags track only tokens actually sampled, so
                # the zero-filled tail never counts and eos_id may
                # legitimately be 0.
                done_rows = done_rows | (nxt == eos_id)
            return (i + 1, tokens, caches, key, done_rows)

        done0 = (first == eos_id) if eos_id is not None \
            else jnp.zeros((B,), jnp.bool_)
        state = (jnp.int32(1), tokens, caches, rng, done0)
        _, tokens, _, _, _ = jax.lax.while_loop(cond, body, state)
        return tokens

    _cache_put(_DECODE_CACHE, cache_key, _decode)
    return _decode(params, prompt_ids, rng)


_STREAM_CACHE: "collections.OrderedDict" = collections.OrderedDict()


def generate_stream(model: Llama, params, prompt_ids: jnp.ndarray,
                    max_new_tokens: int, temperature: float = 0.0,
                    rng: Optional[jax.Array] = None,
                    eos_id: Optional[int] = None,
                    chunk_size: int = 8):
    """Incremental decode for streaming serving: a jitted prefill plus
    a jitted lax.scan of ``chunk_size`` single-token steps. Yields each
    batch-row's next token as a numpy int32 array of shape [B], in
    bursts of up to ``chunk_size``.

    Why chunked: a host readback pays the runtime's completion-
    notification latency REGARDLESS of compute size, so syncing per
    token caps streaming at ~1/latency tokens/s. One scan dispatch + one [K, B] readback amortizes that
    latency over K tokens while keeping time-to-first-token at one
    prefill + one sync. The whole-sequence `generate` (on-device
    while_loop) remains the fastest path for full completions.
    (Reference capability: serve streaming responses,
    python/ray/serve/api.py streaming + _private/http_util.py chunked
    responses.)"""
    cfg = model.config
    B, T0 = prompt_ids.shape
    K = max(1, min(chunk_size, max_new_tokens))
    n_chunks = (max_new_tokens - 1 + K - 1) // K
    total = T0 + 1 + n_chunks * K    # cache covers whole-K chunks
    if rng is None:
        rng = jax.random.PRNGKey(0)

    key = (cfg, B, T0, K, n_chunks, temperature)
    cached = _cache_get(_STREAM_CACHE, key)
    if cached is None:
        @jax.jit
        def _prefill(params, prompt_ids, rng):
            caches = init_kv_caches(cfg, B, total)
            logits, caches = model.apply(params, prompt_ids,
                                         kv_caches=caches, cache_len=0)
            first = _pick_token(logits[:, -1], rng, temperature)
            return first, caches

        @jax.jit
        def _chunk(params, cur, caches, cache_len, rng):
            def body(carry, i):
                cur, caches, key = carry
                key, sub = jax.random.split(key)
                logits, caches = model.apply(
                    params, cur[:, None], kv_caches=caches,
                    cache_len=cache_len + i)
                nxt = _pick_token(logits[:, -1], sub, temperature)
                return (nxt, caches, key), nxt
            (cur, caches, rng), toks = jax.lax.scan(
                body, (cur, caches, rng), jnp.arange(K))
            return toks, cur, caches      # toks: [K, B]

        cached = (_prefill, _chunk)
        _cache_put(_STREAM_CACHE, key, cached)
    _prefill, _chunk = cached

    rng, sub = jax.random.split(rng)
    tok, caches = _prefill(params, prompt_ids, sub)
    first = np.asarray(tok)
    done = np.zeros((B,), bool)
    if eos_id is not None:
        done |= (first == eos_id)
    yield first
    emitted = 1
    for c in range(n_chunks):
        if emitted >= max_new_tokens or \
                (eos_id is not None and done.all()):
            return
        rng, sub = jax.random.split(rng)
        # the chunk's first step consumes the last emitted token, which
        # sits at position T0 + emitted - 1
        toks, tok, caches = _chunk(params, tok, caches,
                                   jnp.int32(T0 + emitted - 1), sub)
        out = np.asarray(toks)           # ONE sync per K tokens
        for j in range(out.shape[0]):
            if emitted >= max_new_tokens:
                return
            row = out[j]
            if eos_id is not None:
                done |= (row == eos_id)
            yield row
            emitted += 1
            if eos_id is not None and done.all():
                return


def _pick_token(logits_last, key, temperature: float):
    if temperature <= 0.0:
        return jnp.argmax(logits_last, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits_last / temperature, axis=-1).astype(jnp.int32)


def llama_sharding_rules(fsdp: bool = True) -> ShardingRules:
    """Megatron TP + FSDP rules for flax Llama params.

    Column-parallel: wq/wk/wv, w1/w3. Row-parallel: wo, w2.
    Embeddings are vocab-parallel over (tensor, fsdp) with the model dim
    unsharded — sharding the model dim of tok_embeddings over fsdp
    forces an involuntary-full-remat reshard of the embedding gradient
    on dp x fsdp x tp meshes (see gpt2_sharding_rules).
    """
    f = "fsdp" if fsdp else None
    return ShardingRules([
        (r"attention/w[qkv]/kernel", P(f, "tensor")),
        (r"attention/wo/kernel",     P("tensor", f)),
        (r"feed_forward/w[13]/kernel", P(f, "tensor")),
        (r"feed_forward/w2/kernel",  P("tensor", f)),
        # the norm over a whole projected q / k vector: its scale
        # shards as the projection's columns do
        (r"attention/[qk]_norm/scale", P("tensor")),
        (r"(tok_embeddings|lm_head)$",
         P(("tensor", "fsdp") if fsdp else "tensor", None)),
    ])


def llama_tp_validate(cfg: LlamaConfig, tp: int) -> None:
    """Check that ``cfg`` divides evenly over a ``tp``-way tensor mesh
    under llama_sharding_rules: heads and kv heads (head-sharded
    attention + KV pool), hidden_dim (column/row-parallel MLP), and
    vocab (vocab-parallel embedding / tied logits). Raises ValueError
    naming the offending dimension — GSPMD would otherwise pad or
    fall back to unexpected reshards silently."""
    if tp <= 0:
        raise ValueError(f"tp must be >= 1, got {tp}")
    for what, n in (("n_heads", cfg.n_heads),
                    ("n_kv_heads", cfg.n_kv_heads),
                    ("hidden_dim", cfg.hidden_dim),
                    ("vocab_size", cfg.vocab_size)):
        if n % tp:
            raise ValueError(
                f"tensor parallelism tp={tp} does not divide "
                f"{what}={n} for this Llama config")


def attention_param_count(cfg) -> int:
    """One block's attention: the four projections and, where the
    config has them, the query/key norms' scales."""
    n = (cfg.dim * cfg.n_heads * cfg.head_dim +
         2 * cfg.dim * cfg.n_kv_heads * cfg.head_dim +
         cfg.n_heads * cfg.head_dim * cfg.dim)
    if cfg.qk_norm:
        n += (cfg.n_heads + cfg.n_kv_heads) * cfg.head_dim
    return n


def embedding_param_count(cfg) -> int:
    """The embedding, the final norm and, where untied, the head."""
    heads = 1 if cfg.tie_word_embeddings else 2
    return heads * cfg.vocab_size * cfg.dim + cfg.dim


def llama_param_count(cfg: LlamaConfig) -> int:
    per_layer = (attention_param_count(cfg) +
                 3 * cfg.dim * cfg.hidden_dim + 2 * cfg.dim)
    return embedding_param_count(cfg) + cfg.n_layers * per_layer


def llama_flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs per token: the standard 6N matmul estimate
    (fwd 2N + bwd 4N) plus the attention-score term 12·L·H·hd·T that
    6N misses because QK^T/AV scale with sequence length, not param
    count. This is the denominator MFU is quoted against (PaLM
    appendix B convention), so bench MFU numbers are comparable to
    published ones."""
    return (6.0 * llama_param_count(cfg) +
            12.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * seq_len)
