"""OLMoE (Muennighoff et al. 2024; Hugging Face ``OlmoeForCausalLM``,
``modeling_olmoe.py``), forward only: plain jax.numpy in float32 at
``highest`` matmul precision, no cache, no kernel, no sorting. The
embedding, RMSNorm, rotary positions (rotate-half), final norm and the
layer loop are ``reference/llama.py``'s; the attention and the mixture
are this file's, because both differ from Llama's and Mixtral's:

  h = x + Attn(RMSNorm(x)), where q = RMSNorm_q(x Wq) and
      k = RMSNorm_k(x Wk) are normed over their WHOLE width (all heads
      together, a learned scale each) before the split into heads and
      before rope; then causal attention and Wo. No bias, no clipping
      (``clip_qkv`` null).
  y = h + MoE(RMSNorm(h)), where p = softmax(h Wr) over ALL experts in
      float32, the ``top_k`` largest of p are kept AS THEY ARE
      (``norm_topk_prob`` false: they sum to less than 1) and
      MoE = sum_i p_i W2_i(silu(W1_i h) * W3_i h). Every expert is
      computed on every token and weighted by zero where it was not
      chosen: the same sum, and no token can be dropped.
  logits = RMSNorm(y_last) head^T with a head of its own
      (``tie_word_embeddings`` false).

    weights = {"embed": [V, D], "head": [V, D], "norm": [D],
               "layers": [{"attn_norm": [D], "wq": [D, H*hd],
                           "wk": [D, KH*hd], "wv": [D, KH*hd],
                           "q_norm": [H*hd], "k_norm": [KH*hd],
                           "wo": [H*hd, D], "ffn_norm": [D],
                           "router": [D, E], "w_gate": [E, D, F],
                           "w_up": [E, D, F], "w_down": [E, F, D]}]}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import llama
from benchmarks.reference.llama import F32


EXPERT_TENSORS = ("w_gate", "w_up", "w_down")


def attention(x, w, *, n_heads, n_kv_heads, eps, theta):
    """x [B, T, D] float32 plus the block's causal attention of its
    pre-norm, with the query/key norm."""
    B, T, D = x.shape
    hd = w["wq"].shape[1] // n_heads
    h = llama.rms_norm(x, w["attn_norm"], eps)
    q = llama.rms_norm(h @ w["wq"], w["q_norm"], eps)
    k = llama.rms_norm(h @ w["wk"], w["k_norm"], eps)
    q = llama.rotary(q.reshape(B, T, n_heads, hd), theta)
    k = llama.rotary(k.reshape(B, T, n_kv_heads, hd), theta)
    v = (h @ w["wv"]).reshape(B, T, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, n_heads * hd)
    return x + a @ w["wo"]


def moe(h, w, top_k: int):
    """h [B, T, D] float32 -> the mixture's output [B, T, D]."""
    B, T, D = h.shape
    tokens = h.reshape(B * T, D)
    probs = jax.nn.softmax(tokens @ w["router"], axis=-1)   # [N, E]
    top_p, top_idx = jax.lax.top_k(probs, top_k)            # [N, k]
    rows = jnp.arange(B * T)[:, None]
    weight = jnp.zeros_like(probs).at[rows, top_idx].set(top_p)

    def one_expert(acc, ew):
        w_gate, w_up, w_down = (a.astype(F32) for a in ew[:3])
        wt = ew[3]                                          # [N]
        y = (jax.nn.silu(tokens @ w_gate) * (tokens @ w_up)) @ w_down
        return acc + y * wt[:, None], None
    # one expert at a time, upcast as it is used: float32 copies of 64
    # experts, or their activations of every token at once, would not
    # fit beside the served model
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return out.reshape(B, T, D)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "eps", "theta", "top_k"))
def layer(x, w, *, n_heads, n_kv_heads, eps, theta, top_k):
    """One decoder block on x [B, T, D] float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: a if k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        x = attention(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                      eps=eps, theta=theta)
        return x + moe(llama.rms_norm(x, w["ffn_norm"], eps), w, top_k)


def forward(weights, ids, *, n_heads, n_kv_heads, eps, theta, top_k):
    """ids [B, T] int32 -> logits [B, T, V] float32."""
    return llama.forward(weights, ids, block=layer, n_heads=n_heads,
                         n_kv_heads=n_kv_heads, eps=eps, theta=theta,
                         top_k=top_k)
