"""Solar-Open2 (upstage/Solar-Open2-250B, ``model_type: solar_open2``),
forward only, token by token: plain jax.numpy in float32 at ``highest``
matmul precision, no cache, no chunk, no kernel, no sorting. Embedding,
RMSNorm, final norm and head are ``reference/llama.py``'s; the three
kinds of sub-layer and the layer loop are this file's. What ``config.json`` leaves
open is marked (assumed) here and listed, each with its reason, under
``assumed`` in benchmarks/configs/solar-open2-250b-d4-ep8.json.

Every block is pre-norm: ``h = x + Mix(RMSNorm(x)); y = h +
MoE(RMSNorm(h))`` (RMSNorm eps 1e-5). No position encoding anywhere
(``use_rope`` false). D = hidden, H heads of d.

GQA layer (layer i with i % (gqa_interval + 1) == 0): causal softmax
attention, H query and KH key/value heads of d, scale d^-1/2; the heads'
output times sigmoid(x W_gate) (``use_gqa_gate``; W_gate: D -> H d, one
gate a channel: assumed), then W_o.

KDA layer (the others: Kimi Delta Attention, a gated delta rule with a
per-channel decay): q~, k~, v~ = x W_q, x W_k, x W_v (D -> H d each, no
bias; ``num_kv_heads`` null = H: assumed). Each passes a causal
depthwise convolution of ``short_conv_kernel_size`` over time (no bias:
assumed), then SiLU. Per head q = l2norm(q) d^-1/2, k = l2norm(k)
(l2norm(x) = x / sqrt(sum x^2 + 1e-6): assumed eps). Decay, per head
and channel, g_t = -exp(A_h) softplus((x W_f1) W_f2 + b_dt)
(``kda_use_full_proj`` false: rank d; no bias on W_f2, one on the sum:
assumed). beta_t = 2 sigmoid(x W_b), in (0, 2)
(``kda_allow_neg_eigval``). The state S [d, d] a head, zero before the
first token:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

computed below exactly in that order by ``lax.scan`` over positions.
Then RMSNorm over d (a learned scale of d) times
sigmoid((x W_g1) W_g2 + b_g) (rank d, bias on W_g2: assumed), then W_o.

Feed-forward (every layer): the router in float32, s = sigmoid(x W_r)
over ALL ``n_routed_experts``; the ``top_k`` experts with the largest
s + b (b a stored bias a expert, used for the choice only: the
solar_open / GLM-4.5 rule, assumed); gates s_chosen / sum(s_chosen)
(``norm_topk_prob``) times ``routed_scaling_factor``. The result is
the sum over the chosen experts THIS SHARE HOLDS (experts lo .. lo + n
of the router's width, n = the expert tensors' leading size) of gate x
SwiGLU_e(x), plus the shared expert's SwiGLU(x). The gates are
normalised over all chosen experts, held or not; what the absent
experts would add is left out. Every held expert is computed on every
token and weighted by zero where it was not chosen.

    weights = {"embed": [V, D], "head": [V, D], "norm": [D], "layers":
      [{"attn_norm": [D], "ffn_norm": [D], "wq", "wk", "wv", "wo",
        GQA: "w_gate_attn": [D, H d]
        KDA: "conv": [K, 3 H d], "f_a": [D, d], "f_b": [d, H d],
             "dt_bias": [H d], "A_log": [H], "wb": [D, H],
             "g_a": [D, d], "g_b": [d, H d], "g_bias": [H d],
             "o_norm": [d]
        "router": [D, E], "router_bias": [E], "w_gate": [n, D, F],
        "w_up": [n, D, F], "w_down": [n, F, D], "shared_gate": [D, Fs],
        "shared_up": [D, Fs], "shared_down": [Fs, D]}]}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import llama
from benchmarks.reference.llama import F32

EXPERT_TENSORS = ("w_gate", "w_up", "w_down")
L2_EPS = 1e-6


def gqa(x, w, *, n_heads, n_kv_heads, eps):
    """x [B, T, D] float32 plus the gated causal attention of its
    pre-norm; no positions."""
    B, T, D = x.shape
    hd = w["wq"].shape[1] // n_heads
    h = llama.rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(B, T, n_heads, hd)
    k = (h @ w["wk"]).reshape(B, T, n_kv_heads, hd)
    v = (h @ w["wv"]).reshape(B, T, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, n_heads * hd)
    return x + (a * jax.nn.sigmoid(h @ w["w_gate_attn"])) @ w["wo"]


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule_scan(q, k, v, g, beta, state=None):
    """The recurrence itself, one position at a time. q, k, g
    [B, T, H, dk], v [B, T, H, dv], beta [B, T, H]; returns
    (o [B, T, H, dv], the last state [B, H, dk, dv])."""
    B, T, H, dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), F32)

    def step(S, xs):
        q, k, v, g, beta = xs
        S = S * jnp.exp(g)[..., None]
        b = beta[..., None, None]
        erase = jnp.einsum("bhk,bhkv->bhv", k, S)
        S = S - b * k[..., :, None] * erase[..., None, :]
        S = S + b * k[..., :, None] * v[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)
    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(a, 1, 0)
                           for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda(x, w, *, eps):
    """x [B, T, D] float32 plus the delta-rule layer of its pre-norm."""
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
    beta = 2.0 * jax.nn.sigmoid(h @ w["wb"])
    o, _ = delta_rule_scan(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ w["g_a"]) @ w["g_b"] + w["g_bias"])
    o = llama.rms_norm(o, w["o_norm"], eps) * gate.reshape(B, T, H, d)
    return x + o.reshape(B, T, H * d) @ w["wo"]


def route(tokens, w, top_k: int, norm_topk: bool, scaling: float):
    """tokens [N, D] -> each token's weight on every expert of the
    router's width [N, E]: zero but for its ``top_k``."""
    s = jax.nn.sigmoid(tokens @ w["router"])
    _, idx = jax.lax.top_k(s + w["router_bias"], top_k)
    gates = jnp.take_along_axis(s, idx, axis=1)
    if norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    rows = jnp.arange(tokens.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(gates * scaling)


def routed(h, w, *, top_k, lo, norm_topk, scaling):
    """The part of the mixture that the experts held here give: experts
    lo .. lo + n of the router's width, n the tensors' leading size."""
    B, T, D = h.shape
    tokens = h.reshape(B * T, D)
    n = w["w_gate"].shape[0]
    weight = route(tokens, w, top_k, norm_topk, scaling)[:, lo:lo + n]

    def one_expert(acc, ew):
        w_gate, w_up, w_down = (a.astype(F32) for a in ew[:3])
        y = (jax.nn.silu(tokens @ w_gate) * (tokens @ w_up)) @ w_down
        return acc + y * ew[3][:, None], None
    # one expert at a time, upcast as it is used (as reference/olmoe.py)
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return out.reshape(B, T, D)


def shared(h, w):
    return (jax.nn.silu(h @ w["shared_gate"]) * (h @ w["shared_up"])
            ) @ w["shared_down"]


def choice_margin(h, w, *, top_k, lo):
    """How far each position's CHOICE of held experts is from
    changing, in units of the hidden state's relative error. h
    [B, T, D] (the router's input) -> [B, T] float32.

    The choice changes where a candidate's s + b crosses the boundary,
    the midpoint of the ``top_k``-th and next largest values. An error
    of h of relative size e in a random direction moves expert j's
    logit by about e |h| |W_r[:, j]| / sqrt(D), and its s by
    s (1 - s) times that: expert j's distance from the boundary over
    that reach is the e that carries it there. The margin is the least
    over the experts HELD here (lo .. lo + n): only their crossing
    changes this share's output. Where it is not well above the served
    path's rounding of h, one computation chooses the expert and the
    other does not (families/solar_open2.py ``NEAR_TIE``)."""
    n = w["w_gate"].shape[0]
    s = jax.nn.sigmoid(h @ w["router"])
    v = s + w["router_bias"]
    top, _ = jax.lax.top_k(v, top_k + 1)
    boundary = 0.5 * (top[..., -1] + top[..., -2])
    reach = (s * (1.0 - s) * jnp.linalg.norm(w["router"], axis=0)
             * jnp.linalg.norm(h, axis=-1, keepdims=True)
             / jnp.sqrt(F32(h.shape[-1])))
    held = slice(lo, lo + n)
    return jnp.min(jnp.abs(v[..., held] - boundary[..., None])
                   / reach[..., held], axis=-1)


def mix(x, w, *, n_heads, n_kv_heads, eps):
    """A block's first half: x plus its token mixing, a KDA layer
    where the weights hold a convolution, a GQA layer where not."""
    if "conv" in w:
        return kda(x, w, eps=eps)
    return gqa(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads, eps=eps)


def feed_forward(x, w, *, eps, top_k, lo, norm_topk, scaling):
    """A block's second half: x plus its mixture; and the positions'
    ``choice_margin``."""
    h = llama.rms_norm(x, w["ffn_norm"], eps)
    y = x + routed(h, w, top_k=top_k, lo=lo, norm_topk=norm_topk,
                   scaling=scaling) + shared(h, w)
    return y, choice_margin(h, w, top_k=top_k, lo=lo)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "eps", "top_k", "lo", "norm_topk",
    "scaling"))
def layer_and_margin(x, w, *, n_heads, n_kv_heads, eps, top_k, lo,
                     norm_topk, scaling):
    """One decoder block on x [B, T, D] float32, and its positions'
    ``choice_margin`` [B, T]."""
    with jax.default_matmul_precision("highest"):
        w = {k: a if k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        x = mix(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads, eps=eps)
        return feed_forward(x, w, eps=eps, top_k=top_k, lo=lo,
                            norm_topk=norm_topk, scaling=scaling)


def layer(x, w, **sizes):
    return layer_and_margin(x, w, **sizes)[0]


def forward(weights, ids, *, n_heads, n_kv_heads, eps, top_k, lo,
            norm_topk=True, scaling=1.0, margins=False):
    """ids [B, T] int32 -> logits [B, T, V] float32; with ``margins``
    also each position's least ``choice_margin`` over the layers
    [B, T]: the same forward pass, read for how sure its choices
    were."""
    x = llama._embed(weights["embed"], ids)
    least = None
    for w in weights["layers"]:
        x, margin = layer_and_margin(
            x, w, n_heads=n_heads, n_kv_heads=n_kv_heads, eps=eps,
            top_k=top_k, lo=lo, norm_topk=norm_topk, scaling=scaling)
        least = margin if least is None else jnp.minimum(least, margin)
    logits = llama._head(x, weights["norm"], weights["head"], eps=eps)
    return (logits, least) if margins else logits
