"""Mellum 2 (JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type:
mellum``), forward only: plain jax.numpy in float32 at ``highest``
matmul precision, no cache, no ring, no kernel, no sorting. Nothing of
``ray_tpu.models`` is imported: the sliding mask and YaRN are written
out from the equations below. The embedding, RMSNorm, final norm and
head are ``reference/llama.py``'s.

Pre-norm blocks, eps 1e-6, a final RMSNorm, an untied head. Layer l of
type t_l (``layer_types``: sliding, sliding, sliding, full, ...):

  h = x + Attn_l(RMSNorm(x)):
      q = W_q x (H heads of d), k = W_k x, v = W_v x (KH heads of d),
      no bias; query head j reads KV head j // (H / KH). q and k are
      rotated at their positions, rotate-half over all d columns (column
      i with i + d/2), by the layer type's rule:
        sliding_attention: inv_freq_i = theta^(-2i/d), cos and sin as
          they are;
        full_attention (YaRN, the Hugging Face
          ``_compute_yarn_parameters`` form, ``truncate`` at its
          default): with low, high = the floor and the ceiling of
          D(beta_fast), D(beta_slow), D(r) = (d/2) ln(original /
          (2 pi r)) / ln(theta), clipped to [0, d - 1], and ramp_i =
          clip((i - low) / (high - low), 0, 1) for i in 0..d/2-1:
          inv_freq_i = theta^(-2i/d) ((1 - ramp_i) + ramp_i / factor);
          cos and sin are both multiplied by ``attention_factor``, so
          the scores carry its square.
      scores q_i k_j / sqrt(d), softmax in float32 over the keys
      j <= i (full) or i - window < j <= i (sliding: ``window`` keys,
      the query's own among them), then W_o over H d -> D.
  y = h + MoE(RMSNorm(h)): p = softmax(W_r h) over ALL experts in
      float32, the ``top_k`` largest, gates p_e / sum of the chosen p
      (``norm_topk_prob`` true); an expert is
      W_down(SiLU(W_gate h) * W_up h). Every expert is computed on every
      token and weighted by zero where it was not chosen: the same sum,
      and no token can be dropped. No shared expert.

What ``config.json`` leaves open is assumed, and the configuration file
says why each: NO query/key norm; the window counted as ``window`` keys
with the query's own; ``truncate``; float32 softmax and router; the MTP
head that ``described_as`` names is no part of this forward pass.

Attention is computed ``Q_BLOCK`` queries at a time (each block's whole
softmax row at once: no online softmax, no skipped key) so that 8k
positions fit beside the served model; ``quadratic`` computes the one
[T, T] mask instead, for the test that holds the two forms together.

    weights = {"embed": [V, D], "head": [V, D], "norm": [D],
               "layers": [{"attn_norm": [D], "wq": [D, H*d],
                           "wk": [D, KH*d], "wv": [D, KH*d],
                           "wo": [H*d, D], "ffn_norm": [D],
                           "router": [D, E], "w_gate": [E, D, F],
                           "w_up": [E, D, F], "w_down": [E, F, D]}]}
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
HEAD_BLOCK = 1024      # positions the head is applied to at once
LOWER = jnp.float8_e4m3fn      # the nearest precision below bfloat16


def plain_inv_freq(dim, theta):
    """[dim / 2] float32: theta^(-2i/dim)."""
    i = np.arange(dim // 2, dtype=np.float64)
    return jnp.asarray(theta ** (-2.0 * i / dim), F32)


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """[dim / 2] float32: see the module docstring."""
    def turning(turns):
        # the (real-valued) dimension whose wavelength fits ``turns``
        # times into the original positions
        return ((dim / 2) * math.log(original / (2 * math.pi * turns))
                / math.log(theta))
    low = max(math.floor(turning(beta_fast)), 0)
    high = min(math.ceil(turning(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(theta ** (-2.0 * i / dim)
                       * ((1.0 - ramp) + ramp / factor), F32)


def rotary(x, inv_freq, scale):
    """x [B, T, heads, d] at positions 0..T-1; rotate-half pairing; cos
    and sin both times ``scale``."""
    T, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def attention(x, w, *, n_heads, n_kv_heads, eps, theta, window, yarn,
              layer_type, quadratic=False, sliding_as_full=False,
              plain_full_rope=False):
    """x [B, T, D] float32 plus the attention of its pre-norm, of
    ``layer_type``. ``yarn``: (factor, original, beta_fast, beta_slow,
    attention_factor). ``sliding_as_full`` and ``plain_full_rope`` are
    CONTROLS of the comparison that decides ``correct`` (a sliding
    layer attended as a full one; the full layers rotated by the plain
    rule): never set by the harness."""
    B, T, D = x.shape
    d = w["wq"].shape[1] // n_heads
    full = layer_type == FULL
    if full and not plain_full_rope:
        factor, original, fast, slow, attention_factor = yarn
        inv_freq = yarn_inv_freq(d, theta, factor, original, fast, slow)
    else:
        inv_freq, attention_factor = plain_inv_freq(d, theta), 1.0
    h = llama.rms_norm(x, w["attn_norm"], eps)
    q = rotary((h @ w["wq"]).reshape(B, T, n_heads, d), inv_freq,
               attention_factor)
    k = rotary((h @ w["wk"]).reshape(B, T, n_kv_heads, d), inv_freq,
               attention_factor)
    v = (h @ w["wv"]).reshape(B, T, n_kv_heads, d)
    rep = n_heads // n_kv_heads
    k = jnp.repeat(k, rep, axis=2)          # query head j reads
    v = jnp.repeat(v, rep, axis=2)          # kv head j // rep
    masked = not full and not sliding_as_full

    def attend(t0, n):
        qb = jax.lax.dynamic_slice_in_dim(q, t0, n, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(F32(d))
        i = t0 + jnp.arange(n)[:, None]
        j = jnp.arange(T)[None, :]
        seen = j <= i
        if masked:
            seen = seen & (j > i - window)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(
            B, n, n_heads * d)
    block = T if quadratic else Q_BLOCK
    a = jnp.concatenate([attend(t0, min(block, T - t0))
                         for t0 in range(0, T, block)], axis=1)
    return x + a @ w["wo"]


def moe(h, w, top_k: int):
    """h [B, T, D] float32 -> the mixture's output [B, T, D]."""
    B, T, D = h.shape
    tokens = h.reshape(B * T, D)
    probs = jax.nn.softmax(tokens @ w["router"], axis=-1)   # [N, E]
    top_p, top_idx = jax.lax.top_k(probs, top_k)            # [N, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    rows = jnp.arange(B * T)[:, None]
    weight = jnp.zeros_like(probs).at[rows, top_idx].set(top_p)

    def one_expert(acc, ew):
        w_gate, w_up, w_down = (a.astype(F32) for a in ew[:3])
        y = (jax.nn.silu(tokens @ w_gate) * (tokens @ w_up)) @ w_down
        return acc + y * ew[3][:, None], None
    # one expert at a time, upcast as it is used: float32 copies of 64
    # experts, or their activations of every token at once, would not
    # fit beside the served model
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return out.reshape(B, T, D)


def _lowered(a):
    """A matrix rounded to ``LOWER`` (the lower-precision control);
    vectors (norms' scales) as they are."""
    return a if a.ndim < 2 else a.astype(LOWER).astype(a.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "eps", "theta", "window", "yarn", "top_k",
    "layer_type", "quadratic", "sliding_as_full", "plain_full_rope",
    "lower_precision"))
def layer(x, w, *, n_heads, n_kv_heads, eps, theta, window, yarn, top_k,
          layer_type, quadratic=False, sliding_as_full=False,
          plain_full_rope=False, lower_precision=False):
    """One decoder block on x [B, T, D] float32. ``lower_precision`` is
    the CONTROL that the comparison which decides ``correct`` must fail
    (every matrix rounded to float8 e4m3): never set by the harness."""
    with jax.default_matmul_precision("highest"):
        if lower_precision:
            w = {k: _lowered(a) for k, a in w.items()}
        w = {k: a if k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        x = attention(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                      eps=eps, theta=theta, window=window, yarn=yarn,
                      layer_type=layer_type, quadratic=quadratic,
                      sliding_as_full=sliding_as_full,
                      plain_full_rope=plain_full_rope)
        return x + moe(llama.rms_norm(x, w["ffn_norm"], eps), w, top_k)


def hidden(weights, ids, *, layer_types, lower_precision=False, **sizes):
    """ids [B, T] -> the last block's output [B, T, D] float32. Layer l
    is of type ``layer_types[l]``."""
    embed = weights["embed"]
    if lower_precision:
        embed = _lowered(embed)
    x = llama._embed(embed, ids)
    for w, layer_type in zip(weights["layers"], layer_types):
        x = layer(x, w, layer_type=layer_type,
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
