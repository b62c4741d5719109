"""Ouro (ByteDance/Ouro-2.6B, ``model_type: ouro``), forward only: plain
jax.numpy in float32 at ``highest`` matmul precision, no cache, no
batching, no loop on the device. Nothing of ``ray_tpu.models`` is
imported. The norm, the rotary embedding, the token embedding and the
head's matrix product are ``reference/llama.py``'s.

ONE stack of L blocks is applied T = ``total_ut_steps`` times, with the
same weights in every pass:

  h = E[ids]
  for t in 1..T:
    for l in 1..L:
      a = Attn_l(RMSNorm(h; g1_l))     causal, grouped-query (Ouro-2.6B:
                                       16 heads over 16), q and k rotated
                                       at their positions (rotate-half),
                                       scores q k / sqrt(d), softmax in
                                       float32, then W_o; no bias
      h = h + RMSNorm(a; g2_l)         SANDWICH norm: the branch is
                                       normed again before it joins
      m = W_down(SiLU(W_gate u) * W_up u),  u = RMSNorm(h; g3_l)
      h = h + RMSNorm(m; g4_l)
    h = RMSNorm(h; g_final);  s_t = h  the final norm closes EVERY pass,
                                       and the NORMED state goes on
    lam_t = sigmoid(w_exit . s_t + b_exit)
  p_t = lam_t prod_{j<t} (1 - lam_j)  (t < T),  p_T = prod_{j<T} (1 - lam_j)
  exit pass = first t with sum_{j<=t} p_j >= threshold, else T
  logits = W_head s_(exit pass)        (no further norm: s is normed)

At the published ``early_exit_threshold`` of 1 every position's logits
are the last pass's. A pass's keys and values are its own: pass t of
layer l attends what pass t of layer l computed for the earlier
positions (a served model keeps T x L cache entries a token); without a
cache that is simply each pass's causal attention over its own input.

What ``config.json`` has no key for is assumed, and the configuration
file says why each: the sandwich norms, the final norm inside the loop,
the gate's form.

CONTROLS of the comparison that decides ``correct`` (never set by the
harness): ``passes`` (run another number of passes), ``sandwich``
false (the branches join un-normed), ``norm_every_pass`` false (the
final norm once, after the last pass), ``lower_precision`` (every
matrix rounded to float8 e4m3, the nearest precision below bfloat16).

    weights = {"embed": [V, D], "head": [V, D], "norm": [D],
               "gate_w": [D], "gate_b": [],
               "layers": [{"attn_norm": [D], "attn_post_norm": [D],
                           "wq": [D, H*d], "wk": [D, KH*d],
                           "wv": [D, KH*d], "wo": [H*d, D],
                           "ffn_norm": [D], "ffn_post_norm": [D],
                           "w_gate": [D, F], "w_up": [D, F],
                           "w_down": [F, D]}, ...]}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import llama
from benchmarks.reference.llama import F32

HEAD_BLOCK = 512       # positions the head is applied to at once
LOWER = jnp.float8_e4m3fn      # the nearest precision below bfloat16


def _lowered(a):
    """A matrix rounded to ``LOWER`` (the lower-precision control);
    vectors (norms' scales, the gate) as they are."""
    return a if a.ndim < 2 else a.astype(LOWER).astype(a.dtype)


def attention_branch(x, w, *, n_heads, n_kv_heads, eps, theta):
    """x [B, T, D] float32 -> the attention of its pre-norm, WITHOUT
    the residual (the block norms the branch before it joins)."""
    B, T, _ = x.shape
    d = w["wq"].shape[1] // n_heads
    h = llama.rms_norm(x, w["attn_norm"], eps)
    q = llama.rotary((h @ w["wq"]).reshape(B, T, n_heads, d), theta)
    k = llama.rotary((h @ w["wk"]).reshape(B, T, n_kv_heads, d), theta)
    v = (h @ w["wv"]).reshape(B, T, n_kv_heads, d)
    rep = n_heads // n_kv_heads
    k = jnp.repeat(k, rep, axis=2)          # query head j reads
    v = jnp.repeat(v, rep, axis=2)          # kv head j // rep
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(d))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, n_heads * d)
    return a @ w["wo"]


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "eps", "theta", "sandwich",
    "lower_precision"))
def layer(x, w, *, n_heads, n_kv_heads, eps, theta, sandwich=True,
          lower_precision=False):
    """One block on x [B, T, D] float32; ``w`` as it is served, upcast
    here (one layer's float32 copy at a time)."""
    with jax.default_matmul_precision("highest"):
        if lower_precision:
            w = {k: _lowered(a) for k, a in w.items()}
        w = {k: a.astype(F32) for k, a in w.items()}
        a = attention_branch(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                             eps=eps, theta=theta)
        if sandwich:
            a = llama.rms_norm(a, w["attn_post_norm"], eps)
        x = x + a
        u = llama.rms_norm(x, w["ffn_norm"], eps)
        m = (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]
        if sandwich:
            m = llama.rms_norm(m, w["ffn_post_norm"], eps)
        return x + m


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, scale, *, eps):
    return llama.rms_norm(x, scale, eps)


@jax.jit
def _gate(s, gate_w, gate_b):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(s @ gate_w.astype(F32) + gate_b.astype(F32))


def states(weights, ids, *, total_ut_steps, eps, passes=None,
           sandwich=True, norm_every_pass=True, lower_precision=False,
           **sizes):
    """ids [B, T] -> the T normed states [passes, B, T, D] float32."""
    embed = weights["embed"]
    if lower_precision:
        embed = _lowered(embed)
    x = llama._embed(embed, ids)
    passes = total_ut_steps if passes is None else passes
    out = []
    for t in range(passes):
        for w in weights["layers"]:
            x = layer(x, w, eps=eps, sandwich=sandwich,
                      lower_precision=lower_precision, **sizes)
        if norm_every_pass:
            x = _final_norm(x, weights["norm"], eps=eps)
            out.append(x)
        else:
            # the CONTROL: the state goes on un-normed, and each
            # pass's output is normed only for the gate and the head
            out.append(_final_norm(x, weights["norm"], eps=eps))
    return jnp.stack(out)


def exit_pass(lam, threshold):
    """lam [T, B, S] -> the pass [B, S] each position's logits are
    taken from, by the rule of the module docstring (numpy)."""
    lam = np.asarray(lam, np.float32)
    T = lam.shape[0]
    remaining = np.ones(lam.shape[1:], np.float32)
    total = np.zeros(lam.shape[1:], np.float32)
    chosen = np.full(lam.shape[1:], T - 1, np.int64)
    done = np.zeros(lam.shape[1:], bool)
    for t in range(T):
        p = remaining if t == T - 1 else lam[t] * remaining
        remaining = remaining * (1.0 - lam[t])
        total = total + p
        now = (total >= threshold) & ~done
        chosen[now] = t
        done |= now
    return chosen


def chosen_state(weights, ids, *, early_exit_threshold, **sizes):
    """ids [B, T] -> (the state [B, T, D] the head reads, the pass
    [B, T] it was taken from)."""
    s = states(weights, ids, **sizes)
    lam = _gate(s, weights["gate_w"], weights["gate_b"])
    chosen = exit_pass(lam, early_exit_threshold)
    return jnp.take_along_axis(
        s, jnp.asarray(chosen)[None, :, :, None], axis=0)[0], chosen


@jax.jit
def _project(x, head):
    with jax.default_matmul_precision("highest"):
        return x @ head.astype(F32).T


def head(weights, x, lower_precision=False):
    """x [B, n, D] (normed already) -> logits [B, n, V] float32
    (numpy: ``HEAD_BLOCK`` positions at a time, each block brought to
    the host)."""
    w = weights["head"]
    if lower_precision:
        w = _lowered(w)
    return np.concatenate([
        np.asarray(_project(x[:, t0:t0 + HEAD_BLOCK], w))
        for t0 in range(0, x.shape[1], HEAD_BLOCK)], axis=1)


def forward(weights, ids, **sizes):
    """ids [B, T] int32 -> logits [B, T, V] float32 (numpy)."""
    x, _ = chosen_state(weights, ids, **sizes)
    return head(weights, x, sizes.get("lower_precision", False))
