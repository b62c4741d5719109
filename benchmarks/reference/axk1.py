"""A.X-K1 (skt/A.X-K1, ``model_type: axk1``), forward only: plain
jax.numpy in float32 at ``highest`` matmul precision, no cache, no
kernel, no sorting, and never the absorbed form: every position's K and
V are EXPANDED a head from its latent. Embedding, RMSNorm, final norm
and head are ``reference/llama.py``'s; the latent attention, the two
feed-forwards and the layer loop are this file's. What ``config.json``
leaves open is marked (assumed) here and listed, each with its reason,
under ``assumed`` in benchmarks/configs/a.x-k1-d5-ep16.json.

Every block is pre-norm: ``h = x + MLA(RMSNorm(x)); y = h +
FFN(RMSNorm(h))`` (RMSNorm eps 1e-6). D = hidden, H heads.

Multi-head latent attention (every layer; the DeepSeek-V2/V3 form that
the config's keys name), per token h:

    c_q = RMSNorm(h W_qa)                       [q_lora_rank]
    [q_nope^i | q_rope^i] = (c_q W_qb)^i        [128 | 64] a head i
    [c~ | k~_r] = h W_kva                       [kv_lora_rank | 64]
    c = RMSNorm(c~);  k_r = rope(k~_r), ONE for all heads
    [k_nope^i | v^i] = (c W_kvb)^i              [128 | 128] a head
    score_i(t, s) = (q_nope^i . k_nope^i_s + rope(q_rope^i) . k_r,s)
                    x scale, causal softmax, o^i = sum_s p v^i_s
    out = concat_i(o^i) W_o

no bias anywhere (``attention_bias`` false). Rope is on the 64
decoupled columns only, rotate-half pairing (column i with i + 32:
assumed; the checkpoint's interleaved pairing is a fixed permutation of
W_qb's and W_kva's columns, which random weights cannot tell apart).
YaRN (``rope_scaling``: factor 32 over 4,096 original positions,
``beta_fast`` 32, ``beta_slow`` 1): dimension j's frequency is the
original ``theta^(-2j/64)`` where it turns more than 32 times over the
original positions, that over 32 where fewer than once, and the linear
ramp between the two over the dimensions between (``yarn_inv_freq``:
written here from the YaRN paper's section 3.2 and the DeepSeek-V2
convention, not copied from the program). cos and sin are multiplied by
``mscale(32, mscale) / mscale(32, mscale_all_dim)`` = 1, and ``scale =
192^-0.5 x mscale(32, mscale_all_dim)^2`` with ``mscale(f, m) = 0.1 m
ln f + 1`` = 1.3466 (assumed: that convention).

Feed-forward: layers 0 .. ``first_k_dense_replace`` - 1 a SwiGLU of
``intermediate_size``; the others the mixture. The router in float32,
``s = sigmoid(h W_r)`` over ALL ``n_routed_experts`` of its width;
``topk_method: "none"`` read literally: the ``top_k`` largest s, no
group limit, no stored bias (assumed); gates ``s_chosen /
sum(s_chosen)`` (``norm_topk_prob``) times ``routed_scaling_factor``.
The result is the sum over the chosen experts THIS SHARE HOLDS (experts
lo .. lo + n of the router's width, n = the expert tensors' leading
size) of gate x SwiGLU_e(h), plus the shared expert's SwiGLU(h). The
gates are normalised over all chosen experts, held or not; what the
absent experts would add is left out. Every held expert is computed on
every token and weighted by zero where it was not chosen.

It fits beside the served model because weights are upcast a layer's
(an expert's) at a time, queries attend in blocks of ``Q_BLOCK``
positions, and the head is applied in blocks of positions.

    weights = {"embed": [V, D], "head": [V, D], "norm": [D], "layers":
      [{"attn_norm": [D], "ffn_norm": [D], "wq_a": [D, Rq],
        "q_norm": [Rq], "wq_b": [Rq, H (dn + dr)],
        "wkv_a": [D, R + dr], "kv_norm": [R],
        "wkv_b": [R, H (dn + dv)], "wo": [H dv, D],
        dense: "w_gate": [D, F0], "w_up": [D, F0], "w_down": [F0, D]
        mixture: "router": [D, E], "w_gate": [n, D, F], "w_up":
          [n, D, F], "w_down": [n, F, D], "shared_gate": [D, Fs],
          "shared_up": [D, Fs], "shared_down": [Fs, D]}]}
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import llama
from benchmarks.reference.llama import F32

EXPERT_TENSORS = ("w_gate", "w_up", "w_down")
Q_BLOCK = 512          # queries attended at once
HEAD_BLOCK = 1024      # positions the head is applied to at once


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """[dim / 2] float32: see the module docstring."""
    def dim_turning(turns):
        # the (real-valued) dimension whose wavelength fits ``turns``
        # times into the original positions
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_turning(beta_fast)), 0)
    high = min(math.ceil(dim_turning(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    j = np.arange(dim // 2, dtype=np.float64)
    original_freq = theta ** (-2.0 * j / dim)
    blend = np.clip((j - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(original_freq * (1.0 - blend)
                       + original_freq / factor * blend, F32)


def rotary(x, inv_freq, mscale):
    """x [B, T, heads, d] at positions 0..T-1; rotate-half pairing."""
    T, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * mscale)[None, :, None, :]
    sin = (jnp.sin(ang) * mscale)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def mla(x, w, *, n_heads, nope, rope, eps, yarn, unroped_key=False):
    """x [B, T, D] float32 plus the latent attention of its pre-norm.
    ``yarn``: (theta, factor, original, beta_fast, beta_slow, mscale,
    mscale_all_dim). ``unroped_key`` is a CONTROL of the comparison
    that decides ``correct`` (the rope key left as projected): never
    set by the harness."""
    B, T, D = x.shape
    H, R = n_heads, w["kv_norm"].shape[0]
    dv = w["wkv_b"].shape[1] // H - nope
    theta, factor, original, fast, slow, mscale, mscale_all = yarn
    inv_freq = yarn_inv_freq(rope, theta, factor, original, fast, slow)
    m_rope = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    scale = (nope + rope) ** -0.5 * yarn_mscale(factor, mscale_all) ** 2

    h = llama.rms_norm(x, w["attn_norm"], eps)
    c_q = llama.rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    q = (c_q @ w["wq_b"]).reshape(B, T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], inv_freq, m_rope)
    kv = h @ w["wkv_a"]
    c = llama.rms_norm(kv[..., :R], w["kv_norm"], eps)
    k_rope = kv[..., None, R:]
    if not unroped_key:
        k_rope = rotary(k_rope, inv_freq, m_rope)
    kv = (c @ w["wkv_b"]).reshape(B, T, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, T, H, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q_nope, q_rope], -1)

    def attend(t0, n):
        qb = jax.lax.dynamic_slice_in_dim(q, t0, n, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        causal = (jnp.arange(T)[None, :] <= t0 + jnp.arange(n)[:, None])
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, n, H * dv)
    a = jnp.concatenate([attend(t0, min(Q_BLOCK, T - t0))
                         for t0 in range(0, T, Q_BLOCK)], axis=1)
    return x + a @ w["wo"]


def route(tokens, w, top_k: int, norm_topk: bool, scaling: float):
    """tokens [N, D] -> each token's weight on every expert of the
    router's width [N, E]: zero but for its ``top_k``."""
    s = jax.nn.sigmoid(tokens @ w["router"])
    gates, idx = jax.lax.top_k(s, top_k)
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
    # one expert at a time, upcast as it is used
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return out.reshape(B, T, D)


def shared(h, w):
    return (jax.nn.silu(h @ w["shared_gate"]) * (h @ w["shared_up"])
            ) @ w["shared_down"]


def choice_margin(h, w, *, top_k, lo):
    """How far each position's CHOICE of held experts is from changing,
    in units of the hidden state's relative error. h [B, T, D] (the
    router's input) -> [B, T] float32. This family's own copy of
    reference/solar_open2.py's rule, for a router with no bias.

    The choice changes where a candidate's s crosses the boundary, the
    midpoint of the ``top_k``-th and next largest values. An error of h
    of relative size e in a random direction moves expert j's logit by
    about e |h| |W_r[:, j]| / sqrt(D), and its s by s (1 - s) times
    that: expert j's distance from the boundary over that reach is the
    e that carries it there. The margin is the least over the experts
    HELD here (lo .. lo + n): only their crossing changes this share's
    output."""
    n = w["w_gate"].shape[0]
    s = jax.nn.sigmoid(h @ w["router"])
    top, _ = jax.lax.top_k(s, top_k + 1)
    boundary = 0.5 * (top[..., -1] + top[..., -2])
    reach = (s * (1.0 - s) * jnp.linalg.norm(w["router"], axis=0)
             * jnp.linalg.norm(h, axis=-1, keepdims=True)
             / jnp.sqrt(F32(h.shape[-1])))
    held = slice(lo, lo + n)
    return jnp.min(jnp.abs(s[..., held] - boundary[..., None])
                   / reach[..., held], axis=-1)


def feed_forward(x, w, *, eps, top_k, lo, norm_topk, scaling):
    """A block's second half: x plus its feed-forward, the mixture
    where the weights hold a router, the dense SwiGLU where not; and
    the positions' ``choice_margin`` (infinite in a dense layer)."""
    h = llama.rms_norm(x, w["ffn_norm"], eps)
    if "router" not in w:
        y = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        return x + y, jnp.full(x.shape[:2], jnp.inf, F32)
    y = x + routed(h, w, top_k=top_k, lo=lo, norm_topk=norm_topk,
                   scaling=scaling) + shared(h, w)
    return y, choice_margin(h, w, top_k=top_k, lo=lo)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nope", "rope", "eps", "yarn", "top_k", "lo", "norm_topk",
    "scaling", "unroped_key"))
def layer_and_margin(x, w, *, n_heads, nope, rope, eps, yarn, top_k, lo,
                     norm_topk, scaling, unroped_key=False):
    """One decoder block on x [B, T, D] float32, and its positions'
    ``choice_margin`` [B, T]."""
    with jax.default_matmul_precision("highest"):
        mixture = "router" in w
        w = {k: a if mixture and k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        x = mla(x, w, n_heads=n_heads, nope=nope, rope=rope, eps=eps,
                yarn=yarn, unroped_key=unroped_key)
        return feed_forward(x, w, eps=eps, top_k=top_k, lo=lo,
                            norm_topk=norm_topk, scaling=scaling)


def hidden(weights, ids, **sizes):
    """ids [B, T] -> the last block's output [B, T, D] float32 and each
    position's least ``choice_margin`` over the layers [B, T]."""
    x = llama._embed(weights["embed"], ids)
    least = jnp.full(ids.shape, jnp.inf, F32)
    for w in weights["layers"]:
        x, margin = layer_and_margin(x, w, **sizes)
        least = jnp.minimum(least, margin)
    return x, least


def forward(weights, ids, *, margins=False, **sizes):
    """ids [B, T] int32 -> logits [B, T, V] float32 (numpy: the head is
    applied ``HEAD_BLOCK`` positions at a time and each block brought
    to the host); with ``margins`` also each position's least
    ``choice_margin`` over the layers [B, T]."""
    x, least = hidden(weights, ids, **sizes)
    T = x.shape[1]
    logits = np.concatenate([
        np.asarray(llama._head(x[:, t0:t0 + HEAD_BLOCK], weights["norm"],
                               weights["head"], eps=sizes["eps"]))
        for t0 in range(0, T, HEAD_BLOCK)], axis=1)
    return (logits, np.asarray(least)) if margins else logits
