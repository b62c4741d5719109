"""Kimi-Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct, ``model_type:
kimi_linear``), forward only: plain jax.numpy in float32 at ``highest``
matmul precision, no cache, no chunk, no kernel, no sorting; the delta
rule scanned token by token, and K and V EXPANDED a head from every
position's latent, never the absorbed form. Embedding, RMSNorm, final
norm and head are ``reference/llama.py``'s; the recurrence itself
(``delta_rule_scan``), the l2norm, the router's rule with its choice
bias, the held experts and the shared expert are
``reference/solar_open2.py``'s, equation for equation (that file cites
this model's layer for its shapes); the two kinds of token mixing as
this model has them, the two feed-forwards and the layer loop are this
file's. What ``config.json`` leaves open is marked (assumed) here and
listed, each with its reason, under ``assumed`` in
benchmarks/configs/kimi-linear-48b-a3b-d8-ep4.json.

Every block is pre-norm: ``h = x + Mix(RMSNorm(x)); y = h +
FFN(RMSNorm(h))`` (RMSNorm eps 1e-5). No position encoding anywhere.
D = hidden, H heads of d.

KDA layer (every layer that ``full_attn_layers`` does not list: Kimi
Delta Attention): q~, k~, v~ = x W_q, x W_k, x W_v (D -> H d each, no
bias). Each passes a causal depthwise convolution of
``short_conv_kernel_size`` over time (no bias: assumed), then SiLU. Per
head q = l2norm(q) d^-1/2, k = l2norm(k) (l2norm(x) = x / sqrt(sum x^2
+ 1e-6): assumed eps). Decay, per head and channel, g_t = -exp(A_h)
softplus((x W_f1) W_f2 + b_dt) (rank d; no bias on W_f2, one on the
sum: assumed). beta_t = sigmoid(x W_b), in (0, 1): this model has no
``kda_allow_neg_eigval``, so no factor of two (Solar-Open2's has). The
state S [d, d] a head, zero before the first token:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

computed exactly in that order by ``lax.scan`` over positions. Then
RMSNorm over d (a learned scale of d) times sigmoid((x W_g1) W_g2 +
b_g) (rank d, a bias on W_g2: assumed), then W_o.

MLA layer (``full_attn_layers``, 1-indexed: assumed), NoPE
(``mla_use_nope``), per token h:

    [q_nope^i | q_r^i] = (h W_q)^i              [128 | 64] a head i,
                                                 ``q_lora_rank`` null:
                                                 one direct matrix
    [c~ | r] = h W_kva                          [kv_lora_rank | 64]
    c = RMSNorm(c~);  r NOT rotated, ONE for all heads
    [k_nope^i | v^i] = (c W_kvb)^i              [128 | 128] a head
    score_i(t, s) = (q_nope^i . k_nope^i_s + q_r^i . r_s) / sqrt(192),
    causal softmax, o^i = sum_s p v^i_s;  out = concat_i(o^i) W_o

no bias anywhere; the DeepSeek-V2/V3 form that the config's keys name
(assumed), with the rotation left out and the 64 decoupled columns
kept, as a key every head shares.

Feed-forward: layers 0 .. ``first_k_dense_replace`` - 1 a SwiGLU of
``intermediate_size``; the others the mixture: the router in float32,
s = sigmoid(h W_r) over ALL ``num_experts`` of its width; the ``top_k``
experts with the largest s + b (b a stored bias a expert, used for the
choice only: assumed; ``num_expert_group`` 1 and ``topk_group`` 1 make
``use_grouped_topk`` choose from the one group that is all experts);
gates s_chosen / sum(s_chosen) (``moe_renormalize``) times
``routed_scaling_factor``. The result is the sum over the chosen
experts THIS SHARE HOLDS (experts lo .. lo + n of the router's width, n
= the expert tensors' leading size) of gate x SwiGLU_e(h), plus the
shared expert's SwiGLU(h). The gates are normalised over all chosen
experts, held or not; what the absent experts would add is left out.
Every held expert is computed on every token and weighted by zero where
it was not chosen.

It fits beside the served model because weights are upcast a layer's
(an expert's) at a time, queries attend in blocks of ``Q_BLOCK``
positions, and the head is applied in blocks of positions.

    weights = {"embed": [V, D], "head": [V, D], "norm": [D], "layers":
      [{"attn_norm": [D], "ffn_norm": [D], "wo",
        KDA: "wq", "wk", "wv": [D, H d], "conv": [K, 3 H d],
             "f_a": [D, d], "f_b": [d, H d], "dt_bias": [H d],
             "A_log": [H], "wb": [D, H], "g_a": [D, d],
             "g_b": [d, H d], "g_bias": [H d], "o_norm": [d]
        MLA: "wq": [D, H (dn + dr)], "wkv_a": [D, R + dr],
             "kv_norm": [R], "wkv_b": [R, H (dn + dv)]
        dense: "w_gate": [D, F0], "w_up": [D, F0], "w_down": [F0, D]
        mixture: "router": [D, E], "router_bias": [E], "w_gate":
          [n, D, F], "w_up": [n, D, F], "w_down": [n, F, D],
          "shared_gate": [D, Fs], "shared_up": [D, Fs],
          "shared_down": [Fs, D]}]}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import llama
from benchmarks.reference.llama import F32
from benchmarks.reference.solar_open2 import (EXPERT_TENSORS,
                                              delta_rule_scan, l2norm,
                                              routed, shared)

Q_BLOCK = 512          # queries attended at once
HEAD_BLOCK = 1024      # positions the head is applied to at once


def kda(x, w, *, eps, doubled_beta=False):
    """x [B, T, D] float32 plus the delta-rule layer of its pre-norm.
    ``doubled_beta`` is a CONTROL of the comparison that decides
    ``correct`` (beta = 2 sigmoid, Solar-Open2's rule): never set by
    the harness."""
    B, T, D = x.shape
    H = w["A_log"].shape[0]
    d = w["wq"].shape[1] // H
    K = w["conv"].shape[0]
    h = llama.rms_norm(x, w["attn_norm"], eps)
    qkv = jnp.concatenate([h @ w["wq"], h @ w["wk"], h @ w["wv"]], -1)
    before = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(before[:, j:j + T] * w["conv"][j]
                          for j in range(K)))
    q, k, v = (a.reshape(B, T, H, d) for a in jnp.split(qkv, 3, axis=-1))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        ((h @ w["f_a"]) @ w["f_b"] + w["dt_bias"]).reshape(B, T, H, d))
    beta = jax.nn.sigmoid(h @ w["wb"]) * (2.0 if doubled_beta else 1.0)
    o, _ = delta_rule_scan(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ w["g_a"]) @ w["g_b"] + w["g_bias"])
    o = llama.rms_norm(o, w["o_norm"], eps) * gate.reshape(B, T, H, d)
    return x + o.reshape(B, T, H * d) @ w["wo"]


def mla(x, w, *, n_heads, nope, eps, unshared_key=False):
    """x [B, T, D] float32 plus the NoPE latent attention of its
    pre-norm. ``unshared_key`` is a CONTROL (the decoupled key left out
    of the scores): never set by the harness."""
    B, T, D = x.shape
    H, R = n_heads, w["kv_norm"].shape[0]
    rope = w["wkv_a"].shape[1] - R
    dv = w["wkv_b"].shape[1] // H - nope
    h = llama.rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(B, T, H, nope + rope)
    kv = h @ w["wkv_a"]
    c = llama.rms_norm(kv[..., :R], w["kv_norm"], eps)
    r = kv[..., None, R:] * (0.0 if unshared_key else 1.0)
    kv = (c @ w["wkv_b"]).reshape(B, T, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(r, (B, T, H, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5

    def attend(t0, n):
        qb = jax.lax.dynamic_slice_in_dim(q, t0, n, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        causal = (jnp.arange(T)[None, :] <= t0 + jnp.arange(n)[:, None])
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, n, H * dv)
    a = jnp.concatenate([attend(t0, min(Q_BLOCK, T - t0))
                         for t0 in range(0, T, Q_BLOCK)], axis=1)
    return x + a @ w["wo"]


def mix(x, w, *, n_heads, nope, eps, doubled_beta=False,
        unshared_key=False):
    """A block's first half: x plus its token mixing, a KDA layer where
    the weights hold a convolution, an MLA layer where not."""
    if "conv" in w:
        return kda(x, w, eps=eps, doubled_beta=doubled_beta)
    return mla(x, w, n_heads=n_heads, nope=nope, eps=eps,
               unshared_key=unshared_key)


def feed_forward(x, w, *, eps, top_k, lo, norm_topk, scaling):
    """A block's second half: x plus its feed-forward, the mixture
    where the weights hold a router, the dense SwiGLU where not."""
    h = llama.rms_norm(x, w["ffn_norm"], eps)
    if "router" not in w:
        return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
                    ) @ w["w_down"]
    return x + routed(h, w, top_k=top_k, lo=lo, norm_topk=norm_topk,
                      scaling=scaling) + shared(h, w)


LOWER = jnp.float8_e4m3fn      # the nearest precision below bfloat16


def _lowered(a):
    """A matrix rounded to ``LOWER`` (the lower-precision control);
    vectors (norms' scales, biases, decays) as they are."""
    return a if a.ndim < 2 else a.astype(LOWER).astype(a.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nope", "eps", "top_k", "lo", "norm_topk", "scaling",
    "doubled_beta", "unshared_key", "lower_precision"))
def layer(x, w, *, n_heads, nope, eps, top_k, lo, norm_topk, scaling,
          doubled_beta=False, unshared_key=False, lower_precision=False):
    """One decoder block on x [B, T, D] float32. ``lower_precision`` is
    the CONTROL that the comparison which decides ``correct`` must
    fail (every matrix rounded to float8 e4m3): never set by the
    harness."""
    with jax.default_matmul_precision("highest"):
        mixture = "router" in w
        if lower_precision:
            w = {k: _lowered(a) for k, a in w.items()}
        w = {k: a if mixture and k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        x = mix(x, w, n_heads=n_heads, nope=nope, eps=eps,
                doubled_beta=doubled_beta, unshared_key=unshared_key)
        return feed_forward(x, w, eps=eps, top_k=top_k, lo=lo,
                            norm_topk=norm_topk, scaling=scaling)


def hidden(weights, ids, **sizes):
    """ids [B, T] -> the last block's output [B, T, D] float32."""
    embed = weights["embed"]
    if sizes.get("lower_precision"):
        embed = _lowered(embed)
    x = llama._embed(embed, ids)
    for w in weights["layers"]:
        x = layer(x, w, **sizes)
    return x


def forward(weights, ids, **sizes):
    """ids [B, T] int32 -> logits [B, T, V] float32 (numpy: the head is
    applied ``HEAD_BLOCK`` positions at a time and each block brought
    to the host)."""
    x = hidden(weights, ids, **sizes)
    head = weights["head"]
    if sizes.get("lower_precision"):
        head = _lowered(head)
    return np.concatenate([
        np.asarray(llama._head(x[:, t0:t0 + HEAD_BLOCK], weights["norm"],
                               head, eps=sizes["eps"]))
        for t0 in range(0, x.shape[1], HEAD_BLOCK)], axis=1)
