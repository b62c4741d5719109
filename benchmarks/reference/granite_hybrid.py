"""Granite-4.0-H-Small (ibm-granite, ``model_type: granitemoehybrid``),
forward only: plain jax.numpy in float32 at ``highest`` matmul
precision, no cache, no chunk, no kernel, nothing of ``ray_tpu``. The
norm, the embedding's look-up and the rotary of the ``rotary`` control
are ``reference/llama.py``'s. What ``config.json`` leaves open is marked
(assumed) here and listed, each with what follows if it is wrong, under
``assumed`` in benchmarks/configs/granite-4.0-h-small-d10-ep2.json.

``x_0 = embedding_multiplier E[id]``. Block l, input x [T, D]:

    h   = x + residual_multiplier mixer_l(RMSNorm(x))
    out = h + residual_multiplier (MoE(n) + Shared(n)),  n = RMSNorm(h)

(ONE norm feeds the mixture and the shared SwiGLU: assumed), and after
the last block ``logits = RMSNorm(x) E^T / logits_scaling`` (the tied
embedding, no bias anywhere but the convolution's: assumed). No position
encoding anywhere (``position_embedding_type: nope``). ``mixer_l`` by
``layer_types[l]``:

1. MAMBA-2 (``"mamba"``; arXiv:2405.21060). H heads of P channels, N
   states, one group of B and C. ``[z | xBC | dt] = x W_in`` of widths
   HP / HP + 2N / H (the split's order is assumed; no bias);
   ``xBC' = SiLU(conv(xBC) + b_c)``, causal, depthwise, width
   ``mamba_d_conv``; ``[x' | B | C] = xBC'``; ``dt_h = softplus(dt_h +
   b_dt,h)`` (not clamped: assumed); ``a_h = exp(-exp(A_log,h) dt_h)``;
   a head h, float32, zero before the first token:

       S_t[h] = a_t,h S_{t-1}[h] + dt_t,h x'_t[h] (outer) B_t     [P, N]
       y_t[h] = S_t[h] C_t + D_h x'_t[h]

   ``mixer = RMSNorm(y * SiLU(z)) W_out``: the gate BEFORE the norm, the
   norm over all HP channels with one learned scale (one group; both
   assumed).
2. ATTENTION (``"attention"``). ``q, k, v = x W`` (no bias): H_a query
   heads on H_kv key/value heads of d; ``P = softmax(q k^T
   attention_multiplier)`` over s <= t (the published multiplier, NOT
   1 / sqrt(d)); ``mixer = (P v) W_o``.

``MoE(n)``: ``logits = n W_r`` over the router's whole width; the
``top_k`` largest; gates a softmax over THOSE logits; expert e is
``(SiLU(n W1_e) * (n W3_e)) W2_e``. THE SHARE: the tensors hold experts
``lo .. lo + len`` of the router's width; a chosen expert that is not
held adds nothing (its gate still took part in the softmax), as on the
chip that holds the share. ``Shared(n)`` is the same SwiGLU at
``shared_intermediate_size``, on every token.

    weights = {"embed": [V, D], "norm": [D], "layers": [{"attn_norm",
      "ffn_norm": [D], "router": [D, E], "w_gate", "w_up": [n, D, F],
      "w_down": [n, F, D], "shared_gate", "shared_up": [D, Fs],
      "shared_down": [Fs, D],
      mamba: "w_in": [D, 2HP + 2N + H], "conv": [K, HP + 2N],
        "conv_bias": [HP + 2N], "dt_bias", "A_log", "D": [H], "o_norm":
        [HP], "w_out": [HP, D]
      attention: "wq": [D, H_a d], "wk", "wv": [D, H_kv d], "wo":
        [H_a d, D]}]}

The CONTROLS (``forward``'s keyword arguments, which the harness never
sets) each change one thing the comparison that decides ``correct``
must catch: ``residual_one`` (residual_multiplier 1.0), ``scale_sqrt``
(the attention's scores over sqrt(d)), ``rotary`` (rotary positions on q
and k), ``norm_before_gate`` (``RMSNorm(y) * SiLU(z)``), ``one_decay``
(every head decays by the mean of ``A_log``), ``softmax_all`` (a softmax
over the router's whole width, the k largest as they are) and the
precisions below the configuration's two: ``lower_precision`` (every
matrix and the embedding rounded to float8 e4m3, where the
configuration states bfloat16) and ``bf16_state`` (the recurrent state
handed on from token to token in bfloat16, where it states float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import llama
from benchmarks.reference.llama import F32

CONTROLS = ("residual_one", "scale_sqrt", "rotary", "norm_before_gate",
            "one_decay", "softmax_all", "lower_precision", "bf16_state")
# the expert tensors are upcast one expert at a time, as they are used
EXPERT_TENSORS = ("w_gate", "w_up", "w_down")


def lowered(a):
    """A matrix rounded to float8 e4m3 (the ``lower_precision``
    control); vectors (norms, biases, A, D) stay."""
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim > 1 else a


def ssd_scan(x, dt, A, Bm, Cm, D, bf16_state=False):
    """The recurrence itself, one position at a time, from a state of
    zeros. x [B, T, H, P]; dt [B, T, H]; A, D [H]; Bm, Cm [B, T, N];
    returns y [B, T, H, P]."""
    B, T, H, P = x.shape

    def step(S, xs):
        x, dt, b, c = xs
        S = (jnp.exp(dt * A)[..., None, None] * S
             + (dt[..., None] * x)[..., None] * b[:, None, None, :])
        if bf16_state:
            # not a pair of converts: the compiler elides those
            S = jax.lax.reduce_precision(S, exponent_bits=8,
                                         mantissa_bits=7)
        return S, jnp.einsum("bhpn,bn->bhp", S, c) + D[:, None] * x
    _, y = jax.lax.scan(
        step, jnp.zeros((B, H, P, Bm.shape[-1]), F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def mamba2(h, w, *, eps, norm_before_gate=False, one_decay=False,
           bf16_state=False):
    """h [B, T, D] float32 (normed) -> its Mamba-2 layer's mixing."""
    B, T, _ = h.shape
    K, W = w["conv"].shape
    H, C = w["A_log"].shape[0], w["w_out"].shape[0]
    N = (W - C) // 2
    z, xbc, dt = jnp.split(h @ w["w_in"], (C, C + W), axis=-1)
    before = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(before[:, j:j + T] * w["conv"][j]
                          for j in range(K)) + w["conv_bias"])
    x, Bm, Cm = jnp.split(xbc, (C, C + N), axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A_log = jnp.full_like(w["A_log"], jnp.mean(w["A_log"])) if one_decay \
        else w["A_log"]
    y = ssd_scan(x.reshape(B, T, H, C // H), dt, -jnp.exp(A_log), Bm, Cm,
                 w["D"], bf16_state).reshape(B, T, C)
    gate = jax.nn.silu(z)
    if norm_before_gate:
        y = llama.rms_norm(y, w["o_norm"], eps) * gate
    else:
        y = llama.rms_norm(y * gate, w["o_norm"], eps)
    return y @ w["w_out"]


def attention(h, w, *, n_heads, n_kv_heads, scale, rotary=False,
              theta=10000.0):
    """h [B, T, D] float32 (normed) -> its causal grouped-query
    attention, the scores times ``scale``."""
    B, T, _ = h.shape
    hd = w["wq"].shape[1] // n_heads
    q = (h @ w["wq"]).reshape(B, T, n_heads, hd)
    k = (h @ w["wk"]).reshape(B, T, n_kv_heads, hd)
    v = (h @ w["wv"]).reshape(B, T, n_kv_heads, hd)
    if rotary:
        q, k = llama.rotary(q, theta), llama.rotary(k, theta)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    seen = jnp.tril(jnp.ones((T, T), bool))

    def row(qkv):
        """One row's [H, T, T] map at a time: the served model fills
        the chip."""
        q, k, v = qkv
        s = jnp.einsum("qhd,khd->hqk", q, k) * scale
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)
    return jax.lax.map(row, (q, k, v)).reshape(
        B, T, n_heads * hd) @ w["wo"]


def route(tokens, w, top_k: int, softmax_all: bool = False):
    """tokens [N, D] -> each token's weight on every expert of the
    router's width [N, E]: zero but for its ``top_k``."""
    logits = tokens @ w["router"]
    if softmax_all:
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    else:
        top, idx = jax.lax.top_k(logits, top_k)
        gates = jax.nn.softmax(top, axis=-1)
    rows = jnp.arange(tokens.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(gates)


def routed(n, w, *, top_k, lo, softmax_all=False, lower_precision=False):
    """The part of the mixture that the experts held here give: experts
    lo .. lo + len of the router's width, one at a time, each upcast as
    it is used."""
    B, T, D = n.shape
    tokens = n.reshape(B * T, D)
    held = w["w_gate"].shape[0]
    weight = route(tokens, w, top_k, softmax_all)[:, lo:lo + held]

    def one_expert(acc, ew):
        w_gate, w_up, w_down = (
            (lowered(a) if lower_precision else a).astype(F32)
            for a in ew[:3])
        y = (jax.nn.silu(tokens @ w_gate) * (tokens @ w_up)) @ w_down
        return acc + y * ew[3][:, None], None
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return out.reshape(B, T, D)


def shared(n, w):
    return (jax.nn.silu(n @ w["shared_gate"]) * (n @ w["shared_up"])
            ) @ w["shared_down"]


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "eps", "top_k", "lo", "residual",
    "attn_scale") + CONTROLS)
def layer(x, w, *, n_heads, n_kv_heads, eps, top_k, lo, residual,
          attn_scale, residual_one=False, scale_sqrt=False, rotary=False,
          norm_before_gate=False, one_decay=False, softmax_all=False,
          lower_precision=False, bf16_state=False):
    """One decoder block on x [B, T, D] float32: a Mamba-2 layer where
    the weights hold a convolution, an attention layer where not."""
    with jax.default_matmul_precision("highest"):
        if lower_precision:
            w = {k: a if k in EXPERT_TENSORS else lowered(a)
                 for k, a in w.items()}
        w = {k: a if k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        if residual_one:
            residual = 1.0
        h = llama.rms_norm(x, w["attn_norm"], eps)
        if "conv" in w:
            mixed = mamba2(h, w, eps=eps, norm_before_gate=norm_before_gate,
                           one_decay=one_decay, bf16_state=bf16_state)
        else:
            if scale_sqrt:
                attn_scale = (w["wq"].shape[1] // n_heads) ** -0.5
            mixed = attention(h, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                              scale=attn_scale, rotary=rotary)
        x = x + residual * mixed
        n = llama.rms_norm(x, w["ffn_norm"], eps)
        return x + residual * (
            routed(n, w, top_k=top_k, lo=lo, softmax_all=softmax_all,
                   lower_precision=lower_precision) + shared(n, w))


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "slices"))
def head(x, norm, embed, *, eps, scaling, slices=8):
    """The final norm and the tied head on x [B, T, D] float32 -> logits
    [B, T, V] over ``scaling``. The vocabulary goes in ``slices`` (where
    they divide it), each upcast as it is used: the served model fills
    the chip."""
    V, D = embed.shape
    if V % slices:
        slices = 1
    with jax.default_matmul_precision("highest"):
        h = llama.rms_norm(x, norm, eps)
        out = jax.lax.map(lambda w: h @ w.astype(F32).T,
                          embed.reshape(slices, V // slices, D))
    return jnp.moveaxis(out, 0, -2).reshape(h.shape[:-1] + (V,)) / scaling


def embedding(weights, lower_precision=False, **_):
    """The embedding (and tied head) under the controls."""
    return lowered(weights["embed"]) if lower_precision else weights["embed"]


def blocks(weights, ids, *, embed_scale, **sizes):
    """ids [B, T] int32 -> the last block's output [B, T, D] float32,
    before the final norm. ``sizes``: ``layer``'s, controls among
    them."""
    x = llama._embed(embedding(weights, **sizes), ids) * embed_scale
    for w in weights["layers"]:
        x = layer(x, w, **sizes)
    return x


def forward(weights, ids, *, eps, logits_scaling, **sizes):
    """ids [B, T] int32 -> logits [B, T, V] float32."""
    x = blocks(weights, ids, eps=eps, **sizes)
    return head(x, weights["norm"], embedding(weights, **sizes), eps=eps,
                scaling=logits_scaling)
