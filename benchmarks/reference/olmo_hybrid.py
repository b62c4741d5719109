"""Olmo-Hybrid (allenai/Olmo-Hybrid-7B, ``model_type: olmo_hybrid``),
forward only, token by token: plain jax.numpy in float32 at ``highest``
matmul precision, no cache, no chunk, no kernel, no batching trick,
nothing of ``ray_tpu``. Embedding, RMSNorm, final norm and head are
``reference/llama.py``'s; the two kinds of sub-layer, the block and the
layer loop are this file's. What ``config.json`` leaves open is marked
(assumed) here and listed, each with its reason, under ``assumed`` in
benchmarks/configs/olmo-hybrid-7b-d16.json.

Every block norms each branch on its way OUT and nothing on its way in
(the OLMo 2 / OLMo 3 reordered norm: assumed; ``config.json`` has no key
for the placement):

    h = x + RMSNorm(Mix(x));   y = h + RMSNorm(SwiGLU(h))

RMSNorm eps 1e-6 (``rms_norm_eps``); a final RMSNorm; an untied head.
No position encoding anywhere (``rope_parameters.rope_theta`` null, and
every other hybrid's full layers are NoPE: assumed). D = hidden.

Full-attention layer (``layer_types[i] == "full_attention"``): causal
softmax attention, H query heads on H key/value heads of D / H, no
bias (``attention_bias`` false), scale (D / H)^-1/2; an RMSNorm with a
learned scale over the WHOLE projected query and the whole projected
key, D wide each, before the split into heads (the OLMo 2 / OLMo 3
``q_norm``/``k_norm``: assumed).

Linear-attention layer (the others: Gated DeltaNet, the keys FLA's and
Qwen3-Next's): q~, k~, v~ = x W_q, x W_k, x W_v (D -> H dk, H dk, H dv;
no bias). Each passes a causal depthwise convolution of
``linear_conv_kernel_dim`` over time (no bias: assumed), then SiLU. Per
head q = l2norm(q) dk^-1/2, k = l2norm(k) (l2norm(x) = x / sqrt(sum x^2
+ 1e-6): assumed eps). ONE log-decay a head,
g_t = -exp(A_h) softplus(x W_a + b_dt) (W_a: D -> H, no bias; one rate
A and one bias b_dt a head: assumed). beta_t = 2 sigmoid(x W_b), in
(0, 2) (``linear_allow_neg_eigval``; W_b: D -> H, no bias: assumed). The
state S [dk, dv] a head, float32, zero before the first token:

    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

computed below exactly in that order by ``lax.scan`` over positions.
Then RMSNorm over dv (a learned scale of dv, eps ``rms_norm_eps``:
assumed) times SiLU(x W_z) (W_z: D -> H dv, no bias, full rank:
assumed), then W_o.

    weights = {"embed": [V, D], "head": [V, D], "norm": [D], "layers":
      [{"attn_post_norm": [D], "ffn_post_norm": [D], "wq", "wk", "wv",
        "wo", "w_gate": [D, F], "w_up": [D, F], "w_down": [F, D],
        full:   "q_norm": [D], "k_norm": [D]
        linear: "conv": [K, 2 H dk + H dv], "wa": [D, H], "A_log": [H],
                "dt_bias": [H], "wb": [D, H], "wz": [D, H dv],
                "o_norm": [dv]}]}

The CONTROLS (``forward``'s keyword arguments, which the harness never
sets) each leave out or lower one thing the comparison that decides
``correct`` must catch: the q/k norm, the branch-output norms (a
pre-norm block in their place), the factor 2 on beta, the gate a head
(every head given the heads' mean), a state handed on in bfloat16, and
the precision below the configuration's (``lower_precision``: every
matrix, the embedding and the head rounded to float8 e4m3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import llama
from benchmarks.reference.llama import F32

L2_EPS = 1e-6
CONTROLS = ("no_qk_norm", "pre_norm", "beta_one", "mean_gate",
            "bf16_state", "lower_precision")


def lowered(a):
    """A matrix rounded to float8 e4m3 (the ``lower_precision``
    control); vectors (norms' scales, A, b_dt) stay."""
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim > 1 else a


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def full_attention(x, w, *, n_heads, eps, no_qk_norm=False):
    """x [B, T, D] float32 -> its causal multi-head attention, NoPE
    (assumed), query and key normed whole (assumed)."""
    B, T, D = x.shape
    hd = w["wq"].shape[1] // n_heads
    q, k, v = x @ w["wq"], x @ w["wk"], x @ w["wv"]
    if not no_qk_norm:
        q = llama.rms_norm(q, w["q_norm"], eps)
        k = llama.rms_norm(k, w["k_norm"], eps)
    q, k, v = (a.reshape(B, T, n_heads, hd) for a in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, n_heads * hd)
    return a @ w["wo"]


def delta_rule_scan(q, k, v, g, beta, bf16_state=False):
    """The recurrence itself, one position at a time, from a state of
    zeros. q, k [B, T, H, dk], v [B, T, H, dv], g, beta [B, T, H];
    returns o [B, T, H, dv]."""
    B, T, H, dk = q.shape

    def step(S, xs):
        q, k, v, g, beta = xs
        S = S * jnp.exp(g)[..., None, None]
        b = beta[..., None, None]
        erase = jnp.einsum("bhk,bhkv->bhv", k, S)
        S = S - b * k[..., :, None] * erase[..., None, :]
        S = S + b * k[..., :, None] * v[..., None, :]
        if bf16_state:
            S = S.astype(jnp.bfloat16).astype(F32)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)
    _, o = jax.lax.scan(
        step, jnp.zeros((B, H, dk, v.shape[-1]), F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def gated_delta_net(x, w, *, eps, beta_one=False, mean_gate=False,
                    bf16_state=False):
    """x [B, T, D] float32 -> its delta-rule layer."""
    B, T, D = x.shape
    H = w["A_log"].shape[0]
    dk, dv = w["wq"].shape[1] // H, w["wv"].shape[1] // H
    K = w["conv"].shape[0]
    qkv = jnp.concatenate([x @ w["wq"], x @ w["wk"], x @ w["wv"]], -1)
    # causal, depthwise, no bias (assumed), then SiLU
    before = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(before[:, j:j + T] * w["conv"][j]
                          for j in range(K)))
    q, k, v = jnp.split(qkv, (H * dk, 2 * H * dk), axis=-1)
    q, k = q.reshape(B, T, H, dk), k.reshape(B, T, H, dk)
    v = v.reshape(B, T, H, dv)
    q, k = l2norm(q) * dk ** -0.5, l2norm(k)
    # ONE log-decay a head: A and b_dt a head, no bias on W_a (assumed)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["wa"] + w["dt_bias"])
    if mean_gate:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = (1.0 if beta_one else 2.0) * jax.nn.sigmoid(x @ w["wb"])
    o = delta_rule_scan(q, k, v, g, beta, bf16_state)
    # the output norm a head and the SiLU gate (assumed)
    o = llama.rms_norm(o, w["o_norm"], eps) * jax.nn.silu(
        x @ w["wz"]).reshape(B, T, H, dv)
    return o.reshape(B, T, H * dv) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("n_heads", "eps") + CONTROLS)
def layer(x, w, *, n_heads, eps, no_qk_norm=False, pre_norm=False,
          beta_one=False, mean_gate=False, bf16_state=False,
          lower_precision=False):
    """One decoder block on x [B, T, D] float32: a linear layer where
    the weights hold a convolution, a full one where not."""
    with jax.default_matmul_precision("highest"):
        if lower_precision:
            w = jax.tree_util.tree_map(lowered, w)
        w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)

        def mix(h):
            if "conv" in w:
                return gated_delta_net(h, w, eps=eps, beta_one=beta_one,
                                       mean_gate=mean_gate,
                                       bf16_state=bf16_state)
            return full_attention(h, w, n_heads=n_heads, eps=eps,
                                  no_qk_norm=no_qk_norm)

        def mlp(h):
            return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
                    ) @ w["w_down"]
        if pre_norm:         # the control: the norms on the way in
            x = x + mix(llama.rms_norm(x, w["attn_post_norm"], eps))
            return x + mlp(llama.rms_norm(x, w["ffn_post_norm"], eps))
        # the norm on each branch's OUTPUT (assumed)
        x = x + llama.rms_norm(mix(x), w["attn_post_norm"], eps)
        return x + llama.rms_norm(mlp(x), w["ffn_post_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "slices"))
def head(x, norm, head_w, *, eps, slices=8):
    """The final norm and the untied head on x [B, T, D] float32 ->
    logits [B, T, V]. The vocabulary goes in ``slices`` (where they
    divide it), each upcast as it is used: 100,352 x 3,840 in float32
    is 1.5 GB, beside a chip the served model fills."""
    V, D = head_w.shape
    if V % slices:
        slices = 1
    with jax.default_matmul_precision("highest"):
        h = llama.rms_norm(x, norm, eps)
        out = jax.lax.map(lambda w: h @ w.astype(F32).T,
                          head_w.reshape(slices, V // slices, D))
    return jnp.moveaxis(out, 0, -2).reshape(h.shape[:-1] + (V,))


def head_weights(weights, lower_precision=False, **_):
    """The head's matrix as ``head`` takes it under the controls."""
    return lowered(weights["head"]) if lower_precision else weights["head"]


def blocks(weights, ids, *, n_heads, eps, **control):
    """ids [B, T] int32 -> the last block's output [B, T, D] float32,
    before the final norm."""
    embed = weights["embed"]
    if control.get("lower_precision"):
        embed = lowered(embed)
    x = llama._embed(embed, ids)
    for w in weights["layers"]:
        x = layer(x, w, n_heads=n_heads, eps=eps, **control)
    return x


def forward(weights, ids, *, n_heads, eps, **control):
    """ids [B, T] int32 -> logits [B, T, V] float32."""
    x = blocks(weights, ids, n_heads=n_heads, eps=eps, **control)
    return head(x, weights["norm"], head_weights(weights, **control),
                eps=eps)
