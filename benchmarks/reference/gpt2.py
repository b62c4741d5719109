"""GPT-2 (Radford et al. 2019; Hugging Face ``GPT2LMHeadModel``),
forward, loss and gradients in plain float32 jax.numpy at ``highest``
matmul precision: learned token and position embeddings, pre-LayerNorm
blocks of causal multi-head attention and a 4x GELU (tanh form,
``gelu_new``) feed-forward, all with biases, a final LayerNorm and the
token embedding as the output head.

    weights = {"wte": [V, C], "wpe": [P, C],
               "ln_f": {"scale", "bias"},
               "layers": [{"ln_1": {...}, "ln_2": {...},
                           "c_attn": {"kernel": [C, 3C], "bias"},
                           "attn_proj": {"kernel": [C, C], "bias"},
                           "c_fc": {"kernel": [C, 4C], "bias"},
                           "mlp_proj": {"kernel": [4C, C], "bias"}}]}

The loss is the mean next-token cross-entropy over ids [B, T+1]:
positions 0..T-1 predict 1..T.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def forward(w, ids, *, n_head, eps):
    """ids [B, T] -> logits [B, T, V] float32."""
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    B, T = ids.shape
    x = w["wte"][ids] + w["wpe"][None, :T]
    C = x.shape[-1]
    hd = C // n_head
    causal = jnp.tril(jnp.ones((T, T), bool))
    for lw in w["layers"]:
        h = layer_norm(x, lw["ln_1"], eps)
        q, k, v = jnp.split(dense(h, lw["c_attn"]), 3, axis=-1)
        q, k, v = (t.reshape(B, T, n_head, hd) for t in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + dense(a.reshape(B, T, C), lw["attn_proj"])
        h = layer_norm(x, lw["ln_2"], eps)
        x = x + dense(gelu_new(dense(h, lw["c_fc"])), lw["mlp_proj"])
    return layer_norm(x, w["ln_f"], eps) @ w["wte"].T


def summed_loss(w, ids, *, n_head, eps):
    """Sum (not mean) of next-token cross-entropies: micro-batches add."""
    logits = forward(w, ids[:, :-1], n_head=n_head, eps=eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -gold.sum()


@functools.partial(jax.jit, static_argnames=("n_head", "eps",
                                             "micro_batch"))
def _loss_and_grad_norm(w, ids, *, n_head, eps, micro_batch):
    with jax.default_matmul_precision("highest"):
        B = ids.shape[0]
        chunks = ids.reshape(B // micro_batch, micro_batch, ids.shape[1])
        grad = jax.value_and_grad(
            functools.partial(summed_loss, n_head=n_head, eps=eps))
        zero = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, F32), w)

        def body(carry, chunk):
            total, acc = carry
            l, g = grad(w, chunk)
            return (total + l,
                    jax.tree_util.tree_map(jnp.add, acc, g)), None

        (total, acc), _ = jax.lax.scan(body, (F32(0.0), zero), chunks)
        n = B * (ids.shape[1] - 1)
        sq = sum(jnp.sum((g / n) ** 2)
                 for g in jax.tree_util.tree_leaves(acc))
        return total / n, jnp.sqrt(sq)


def loss_and_grad_norm(w, ids, *, n_head, eps, micro_batch=4):
    """Mean loss over ids [B, T+1] and the global L2 norm of its
    gradient, accumulated over micro-batches inside ONE program
    (float32 logits of the whole batch would not fit beside the train
    state). B must be a multiple of ``micro_batch``."""
    if ids.shape[0] % micro_batch:
        micro_batch = 1
    loss, gnorm = _loss_and_grad_norm(w, ids, n_head=n_head, eps=eps,
                                      micro_batch=micro_batch)
    return float(loss), float(gnorm)
