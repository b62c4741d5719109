"""Mixtral (Jiang et al. 2024; Hugging Face ``MixtralForCausalLM``),
forward only: the Llama decoder of ``reference/llama.py`` (its
embedding, attention, RMSNorm, rotary positions, final norm and head,
re-used from there) with each block's feed-forward replaced by a
sparse mixture of SwiGLU experts. Plain jax.numpy in float32 at
``highest`` matmul precision.

The router is one matrix [D, E]; its logits are float32. A token's
``top_k`` largest logits choose its experts, and a softmax over THOSE
k logits (not over all E) gives their weights, as Mixtral publishes it
(``norm_topk_prob`` true in later configs). Every token gets every one
of its k experts: no capacity, no dropped token. Each expert is
computed on every token and weighted by zero where it was not chosen,
which is the same sum and needs no gather.

    weights = llama's, with each layer's "w_gate"/"w_up"/"w_down"
              replaced by {"router": [D, E], "w_gate": [E, D, F],
                           "w_up": [E, D, F], "w_down": [E, F, D]}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import llama
from benchmarks.reference.llama import F32


def moe(h, w, top_k: int):
    """h [B, T, D] float32 -> the mixture's output [B, T, D]."""
    B, T, D = h.shape
    tokens = h.reshape(B * T, D)
    logits = tokens @ w["router"]                          # [N, E]
    top_logits, top_idx = jax.lax.top_k(logits, top_k)     # [N, k]
    gates = jax.nn.softmax(top_logits, axis=-1)
    rows = jnp.arange(B * T)[:, None]
    weight = jnp.zeros_like(logits).at[rows, top_idx].set(gates)
    gate = jnp.einsum("nd,edf->enf", tokens, w["w_gate"])
    up = jnp.einsum("nd,edf->enf", tokens, w["w_up"])
    out = jnp.einsum("enf,efd->end", jax.nn.silu(gate) * up, w["w_down"])
    return jnp.einsum("end,ne->nd", out, weight).reshape(B, T, D)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "eps", "theta", "top_k"))
def layer(x, w, *, n_heads, n_kv_heads, eps, theta, top_k):
    """One decoder block on x [B, T, D] float32."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
        x = llama.attention(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                            eps=eps, theta=theta)
        return x + moe(llama.rms_norm(x, w["ffn_norm"], eps), w, top_k)


def forward(weights, ids, *, n_heads, n_kv_heads, eps, theta, top_k):
    """ids [B, T] int32 -> logits [B, T, V] float32."""
    return llama.forward(weights, ids, block=layer, n_heads=n_heads,
                         n_kv_heads=n_kv_heads, eps=eps, theta=theta,
                         top_k=top_k)
