"""Laguna (poolside/Laguna-XS.2, ``model_type: laguna``), forward only:
plain jax.numpy in float32 at ``highest`` matmul precision, no cache, no
ring, no kernel, no sorting. Nothing of ``ray_tpu.models`` is imported:
the masks, YaRN and the half rotation are written out from the equations
below. The embedding, RMSNorm, final norm and head are
``reference/llama.py``'s.

Pre-norm blocks, eps 1e-6, a final RMSNorm, an untied head. Layer l of
type t_l (``layer_types``: full, sliding, sliding, sliding, ...) has H_l
query heads (``num_attention_heads_per_layer``: 48 full, 64 sliding)
over KH = 8 K/V heads of d = 128 in EVERY layer:

  h = RMSNorm(x);  q = W_q h (H_l heads of d), k = W_k h, v = W_v h (KH
      heads of d), no bias; g = sigmoid(W_g h), one number a query head
      (``gating``); query head i reads KV head i // (H_l / KH).
      q and k are rotated at their positions by the layer type's rule,
      rotate-half pairing INSIDE the rotated columns (column c with
      c + r/2, r the rotated width):
        sliding_attention: r = d, inv_freq_c = theta_s^(-2c/d), theta_s
          = 1e4, cos and sin as they are;
        full_attention: r = d x ``partial_rotary_factor`` = 64, the
          FIRST r columns; YaRN (the Hugging Face
          ``_compute_yarn_parameters`` form with ``dim`` = r,
          ``truncate`` at its default): low, high = the floor and the
          ceiling of D(beta_fast), D(beta_slow), D(n) = (r/2)
          ln(original / (2 pi n)) / ln(theta), clipped to [0, r - 1],
          ramp_c = clip((c - low) / (high - low), 0, 1) for c in
          0..r/2-1, inv_freq_c = theta^(-2c/r) ((1 - ramp_c) + ramp_c /
          factor); cos and sin both times ``attention_factor``; columns
          r..d-1 of q and k pass unrotated.
      scores q_i k_j / sqrt(d), softmax in float32 over the keys j <= i
      (full) or i - window < j <= i (sliding: ``window`` keys, the
      query's own among them); a_i = sum_j p_ij v_j;
  x = x + W_o concat_i(g_i a_i).
  h2 = RMSNorm(x). ``mlp_layer_types`` dense (layer 0):
      x = x + W_2(SiLU(W_1 h2) * W_3 h2), 8,192 wide. sparse: s =
      sigmoid(R h2) over ALL experts in float32, the ``top_k`` largest,
      gates ``scale`` x s_e / sum of the chosen s; an expert is a SwiGLU
      of 512; x = x + sum_e gate_e expert_e(h2) + shared(h2), the shared
      expert a SwiGLU every token passes, ungated. Every expert is
      computed on every token and weighted by zero where it was not
      chosen: the same sum, and no token can be dropped.

What ``config.json`` leaves open is assumed, and the configuration file
says why each: the gate's activation, its input and that it is a head's;
the router's sigmoid with renormalised gates; no query/key norm, router
bias or gate on the shared expert; which half rotates and YaRN's width;
the window counted with the query's own key.

The CONTROLS (``CONTROLS``) each change one of those and must read NOT
correct; the harness sets none.

Attention is computed ``Q_BLOCK`` queries at a time (each block's whole
softmax row at once), the experts ``EXPERT_BLOCK`` at a time, upcast as
they are used, so that the float32 copy of one layer's 256 experts (3.2
GB) never exists beside the served model.

    weights = {"embed": [V, D], "head": [V, D], "norm": [D],
               "layers": [{"attn_norm": [D], "wq": [D, H_l*d],
                           "wk": [D, KH*d], "wv": [D, KH*d],
                           "wg": [D, H_l], "wo": [H_l*d, D],
                           "ffn_norm": [D],
                           dense:  "ffn_gate", "ffn_up": [D, F0],
                                   "ffn_down": [F0, D]
                           sparse: "router": [D, E], "w_gate", "w_up":
                                   [E, D, F], "w_down": [E, F, D],
                                   "shared_gate", "shared_up": [D, Fs],
                                   "shared_down": [Fs, D]}]}
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
SLIDING, FULL = "sliding_attention", "full_attention"
Q_BLOCK = 256          # queries attended at once
EXPERT_BLOCK = 16      # experts upcast and computed at once
HEAD_BLOCK = 1024      # positions the head is applied to at once
LOWER = jnp.float8_e4m3fn      # the nearest precision below bfloat16

# What each control changes (each a keyword of ``forward`` that defaults
# to false): the comparison that decides ``correct`` must fail every one.
CONTROLS = {
    "no_gate": "the attention's output is not gated",
    "heads_swapped": "a full layer's query heads are grouped over the "
                     "K/V heads by the SLIDING layers' count",
    "rotate_whole_head": "a full layer rotates all of a head's columns "
                         "(YaRN computed for the whole width)",
    "one_theta": "the sliding layers rotate at the full layers' base",
    "window_511": "a sliding query sees one key fewer",
    "no_shared": "the shared expert is left out",
    "scale_one": "the routed gates are not scaled",
    "lower_precision": "every matrix is rounded to float8 e4m3",
}


def plain_inv_freq(width, theta):
    """[width / 2] float32: theta^(-2c/width)."""
    c = np.arange(width // 2, dtype=np.float64)
    return jnp.asarray(theta ** (-2.0 * c / width), F32)


def yarn_inv_freq(width, theta, factor, original, beta_fast, beta_slow):
    """[width / 2] float32: see the module docstring."""
    def turning(turns):
        # the (real-valued) dimension whose wavelength fits ``turns``
        # times into the original positions
        return ((width / 2) * math.log(original / (2 * math.pi * turns))
                / math.log(theta))
    low = max(math.floor(turning(beta_fast)), 0)
    high = min(math.ceil(turning(beta_slow)), width - 1)
    if low == high:
        high += 0.001
    c = np.arange(width // 2, dtype=np.float64)
    ramp = np.clip((c - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(theta ** (-2.0 * c / width)
                       * ((1.0 - ramp) + ramp / factor), F32)


def rotary(x, inv_freq, scale):
    """x [B, T, heads, d] at positions 0..T-1 with its first
    ``2 x len(inv_freq)`` columns rotated (rotate-half pairing inside
    them; cos and sin both times ``scale``) and the rest as they are."""
    T, r = x.shape[1], 2 * inv_freq.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin, rest], axis=-1)


def attention(x, w, *, head_dim, n_kv_heads, eps, window, full_rope,
              sliding_rope, other_heads, layer_type, quadratic=False,
              no_gate=False, heads_swapped=False, rotate_whole_head=False,
              one_theta=False, window_511=False):
    """x [B, T, D] float32 plus the gated attention of its pre-norm, of
    ``layer_type``. ``full_rope``: (theta, rotated fraction, factor,
    original, beta_fast, beta_slow, attention_factor); ``sliding_rope``:
    (theta, rotated fraction); ``other_heads``: the query heads of the
    OTHER layer type (read by ``heads_swapped`` alone)."""
    B, T, D = x.shape
    d = head_dim
    H = w["wq"].shape[1] // d
    full = layer_type == FULL
    if full:
        theta, fraction, factor, original, fast, slow, scale = full_rope
        r = d if rotate_whole_head else int(d * fraction)
        inv_freq = yarn_inv_freq(r, theta, factor, original, fast, slow)
    else:
        theta, fraction = sliding_rope
        if one_theta:
            theta = full_rope[0]
        inv_freq, scale = plain_inv_freq(int(d * fraction), theta), 1.0
        if window_511:
            window = window - 1
    h = llama.rms_norm(x, w["attn_norm"], eps)
    q = rotary((h @ w["wq"]).reshape(B, T, H, d), inv_freq, scale)
    k = rotary((h @ w["wk"]).reshape(B, T, n_kv_heads, d), inv_freq,
               scale)
    v = (h @ w["wv"]).reshape(B, T, n_kv_heads, d)
    g = jax.nn.sigmoid(h @ w["wg"])                     # [B, T, H]
    group = (other_heads if full and heads_swapped else H) // n_kv_heads
    reads = jnp.arange(H) // group      # query head i reads KV head
    k, v = k[:, :, reads], v[:, :, reads]

    def attend(t0, n):
        qb = jax.lax.dynamic_slice_in_dim(q, t0, n, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(F32(d))
        i = t0 + jnp.arange(n)[:, None]
        j = jnp.arange(T)[None, :]
        seen = j <= i
        if not full:
            seen = seen & (j > i - window)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)
    block = T if quadratic else Q_BLOCK
    a = jnp.concatenate([attend(t0, min(block, T - t0))
                         for t0 in range(0, T, block)], axis=1)
    if not no_gate:
        a = a * g[..., None]
    return x + a.reshape(B, T, H * d) @ w["wo"]


def swiglu(t, w_gate, w_up, w_down):
    return (jax.nn.silu(t @ w_gate) * (t @ w_up)) @ w_down


def moe(h, w, top_k: int, scale: float, no_shared=False):
    """h [B, T, D] float32 -> the mixture's output [B, T, D]."""
    B, T, D = h.shape
    tokens = h.reshape(B * T, D)
    s = jax.nn.sigmoid(tokens @ w["router"])                # [N, E]
    top_s, top_idx = jax.lax.top_k(s, top_k)                # [N, k]
    top_s = scale * top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    rows = jnp.arange(B * T)[:, None]
    weight = jnp.zeros_like(s).at[rows, top_idx].set(top_s)
    E = s.shape[-1]
    nb = next(b for b in range(min(EXPERT_BLOCK, E), 0, -1) if E % b == 0)

    def some_experts(acc, ew):
        w_gate, w_up, w_down = (a.astype(F32) for a in ew[:3])
        y = jnp.einsum("enf,efd->end", jax.nn.silu(
            jnp.einsum("nd,edf->enf", tokens, w_gate))
            * jnp.einsum("nd,edf->enf", tokens, w_up), w_down)
        return acc + jnp.einsum("end,en->nd", y, ew[3]), None
    # a block of experts at a time, upcast as it is used
    blocks = tuple(a.reshape((E // nb, nb) + a.shape[1:])
                   for a in (w["w_gate"], w["w_up"], w["w_down"],
                             weight.T))
    out, _ = jax.lax.scan(some_experts, jnp.zeros_like(tokens), blocks)
    if not no_shared:
        out = out + swiglu(tokens, w["shared_gate"], w["shared_up"],
                           w["shared_down"])
    return out.reshape(B, T, D)


def _lowered(a):
    """A matrix rounded to ``LOWER`` (the lower-precision control);
    vectors (norms' scales) as they are."""
    return a if a.ndim < 2 else a.astype(LOWER).astype(a.dtype)


_STATIC = ("head_dim", "n_kv_heads", "eps", "window", "full_rope",
           "sliding_rope", "other_heads", "top_k", "scale", "layer_type",
           "quadratic") + tuple(CONTROLS)


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(x, w, *, top_k, scale, eps, no_shared=False, scale_one=False,
          lower_precision=False, **attn):
    """One decoder block on x [B, T, D] float32; dense or sparse by the
    tensors ``w`` holds."""
    with jax.default_matmul_precision("highest"):
        if lower_precision:
            w = {k: _lowered(a) for k, a in w.items()}
        w = {k: a if k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        x = attention(x, w, eps=eps, **attn)
        h = llama.rms_norm(x, w["ffn_norm"], eps)
        if "router" not in w:
            return x + swiglu(h, w["ffn_gate"], w["ffn_up"],
                              w["ffn_down"])
        return x + moe(h, w, top_k, 1.0 if scale_one else scale,
                       no_shared)


def hidden(weights, ids, *, layer_types, heads, lower_precision=False,
           **sizes):
    """ids [B, T] -> the last block's output [B, T, D] float32. Layer l
    is of type ``layer_types[l]``; ``heads`` maps a layer type to its
    query heads."""
    embed = weights["embed"]
    if lower_precision:
        embed = _lowered(embed)
    x = llama._embed(embed, ids)
    for w, layer_type in zip(weights["layers"], layer_types):
        other = heads[SLIDING if layer_type == FULL else FULL]
        x = layer(x, w, layer_type=layer_type, other_heads=other,
                  lower_precision=lower_precision, **sizes)
    return x


def head(weights, x, *, eps, lower_precision=False):
    """x [B, n, D] -> logits [B, n, V] float32 (numpy: ``HEAD_BLOCK``
    positions at a time, each block brought to the host)."""
    w = weights["head"]
    if lower_precision:
        w = _lowered(w)
    return np.concatenate([
        np.asarray(llama._head(x[:, t0:t0 + HEAD_BLOCK], weights["norm"],
                               w, eps=eps))
        for t0 in range(0, x.shape[1], HEAD_BLOCK)], axis=1)


def forward(weights, ids, **sizes):
    """ids [B, T] int32 -> logits [B, T, V] float32 (numpy)."""
    x = hidden(weights, ids, **sizes)
    return head(weights, x, eps=sizes["eps"],
                lower_precision=sizes.get("lower_precision", False))
