"""Mistral-7B / Llama decoder, forward only, as the papers and the
Hugging Face ``MistralForCausalLM`` describe it: plain jax.numpy in
float32 at ``highest`` matmul precision, no cache, no kernel, no
batching trick. Pre-norm blocks of grouped-query causal attention with
rotary position embeddings (rotate-half pairing: dimension i with
i + head_dim/2) and a SwiGLU feed-forward; RMSNorm; an output head that
is its own matrix (Mistral-7B-v0.3: ``tie_word_embeddings: false``).

Weights come in whatever type they are served in and are upcast ONE
LAYER AT A TIME: float32 copies of all of them would not fit beside the
served model. Matrices are stored [in, out] (``x @ W``), the transpose
of the checkpoint's ``nn.Linear`` storage.

    weights = {"embed": [V, D], "head": [V, D], "norm": [D],
               "layers": [{"attn_norm": [D], "wq": [D, H*hd],
                           "wk": [D, KH*hd], "wv": [D, KH*hd],
                           "wo": [H*hd, D], "ffn_norm": [D],
                           "w_gate": [D, F], "w_up": [D, F],
                           "w_down": [F, D]}, ...]}

No sliding window: v0.3 publishes ``sliding_window: null``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rotary(x, theta):
    """x: [B, T, heads, hd] at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]   # [T, hd/2]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def attention(x, w, *, n_heads, n_kv_heads, eps, theta):
    """x [B, T, D] float32 plus the block's causal grouped-query
    attention of its pre-norm; ``w`` float32. Not jitted, and no
    precision set: it is a part of ``layer`` (and of another family's
    block that shares this attention)."""
    B, T, D = x.shape
    hd = w["wq"].shape[1] // n_heads
    h = rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(B, T, n_heads, hd)
    k = (h @ w["wk"]).reshape(B, T, n_kv_heads, hd)
    v = (h @ w["wv"]).reshape(B, T, n_kv_heads, hd)
    q, k = rotary(q, theta), rotary(k, theta)
    rep = n_heads // n_kv_heads
    k = jnp.repeat(k, rep, axis=2)          # query head j reads
    v = jnp.repeat(v, rep, axis=2)          # kv head j // rep
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, n_heads * hd)
    return x + a @ w["wo"]


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "eps", "theta"))
def layer(x, w, *, n_heads, n_kv_heads, eps, theta):
    """One decoder block on x [B, T, D] float32."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
        x = attention(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                      eps=eps, theta=theta)
        h = rms_norm(x, w["ffn_norm"], eps)
        gate = h @ w["w_gate"]
        x = x + (jax.nn.silu(gate) * (h @ w["w_up"])) @ w["w_down"]
        return x


@jax.jit
def _embed(embed, ids):
    return embed[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm, eps) @ head.astype(F32).T


def forward(weights, ids, *, eps, block=layer, **sizes):
    """ids [B, T] int32 -> logits [B, T, V] float32. ``sizes`` are the
    block's own (n_heads, n_kv_heads, theta); ``block`` is this file's
    ``layer``, or another family's that shares embedding, final norm
    and head."""
    x = _embed(weights["embed"], ids)
    for w in weights["layers"]:
        x = block(x, w, eps=eps, **sizes)
    return _head(x, weights["norm"], weights["head"], eps=eps)
