"""SDAR (JetLM ``SDAR-30B-A3B-Chat``, ``model_type: sdar_moe``; the
source's ``modeling_sdar_moe.py`` and ``generate.py``), forward and
generation: plain jax.numpy in float32 at ``highest`` matmul precision,
no cache, no kernel, no sorting, importing nothing of ``ray_tpu.models``.
The embedding, RMSNorm, rotary positions (rotate-half), final norm, head
and the layer loop are ``reference/llama.py``'s; the mixture is
``reference/mellum2.py``'s (``reference/olmoe.py``'s one expert at a
time with the chosen gates renormalised); the attention is this file's.

  h = x + Wo Attn(q, k, v), q = rope(RMSNorm_hd(x~ Wq)),
      k = rope(RMSNorm_hd(x~ Wk)), v = x~ Wv, x~ = RMSNorm(x): the q/k
      norm is over EACH HEAD's ``head_dim`` columns with one learned
      scale [head_dim] shared by the heads (Qwen3's; not OLMoE's
      whole-width norm). ``head_dim`` is explicit (wq is [D, H x hd]).
  y = h + MoE(RMSNorm(h)), p = softmax(h~ Wr) over ALL experts in
      float32, the ``top_k`` largest renormalised to sum to 1,
      MoE = sum_e p_e W2_e(silu(W1_e h~) * W3_e h~); no shared expert.
  The mask is BLOCK-CAUSAL: with block length L, query i sees key j iff
      j // L <= i // L (causal across blocks, bidirectional inside one;
      L = 1 is the causal mask).
  logits = RMSNorm(y) head^T with a head of its own; the logits AT a
      masked position are for that position's own token (no shift).

Generation (``generate``, the source's ``block_diffusion_generate``). A
prompt of P tokens is run over its P0 = P // L * L leading positions and
their K/V kept; the P - P0 left over open the first generated block
beside L - (P - P0) masks. A block is run forward over everything
before it and itself (here: the whole sequence again, no cache; what
lies behind the block cannot be seen by it); at greedy x^_i = argmax
l_i, c_i = softmax(l_i)[x^_i]. Step s of T denoising steps reveals n_s
positions (L // T, the first L % T steps one more): ``sequential`` the
first n_s masked; ``low_confidence_static`` the n_s masked of largest
c; ``low_confidence_dynamic`` every masked position with c_i > tau if
there are at least n_s of them, else the n_s of largest c. Revealed
tokens stay. When no mask is left one more forward writes the block's
K/V (nothing to do here: there is no cache) and the next block opens
all masked.

DEPARTURES from the source, each the served path's too:
- masks are FLAGS, not ``token == mask_token_id`` (the source compares
  ids; a prompt may hold that id). A masked position's input is the
  mask token's embedding.
- only masked positions are revealed: where fewer than n_s are left
  (a first block that opens with a prompt remainder) the source's
  ``torch.topk`` over confidences of -inf also picks positions that
  were never masked and overwrites them.
- ties in confidence go to the LOWER position (``torch.topk`` leaves
  them open).
- greedy (temperature 0; the source's default is 1.0), no top-k/top-p.
- exactly ``n_new`` tokens are returned (the source returns whole
  blocks) and no stopping criterion is applied.
What ``config.json`` has no key for (the per-head q/k norm, the block
length, the schedule, the mask token) is in the configuration's
``assumed``.

    weights = {"embed": [V, D], "head": [V, D], "norm": [D],
               "layers": [{"attn_norm": [D], "wq": [D, H*hd],
                           "wk": [D, KH*hd], "wv": [D, KH*hd],
                           "q_norm": [hd], "k_norm": [hd],
                           "wo": [H*hd, D], "ffn_norm": [D],
                           "router": [D, E], "w_gate": [E, D, F],
                           "w_up": [E, D, F], "w_down": [E, F, D]}]}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import llama, mellum2
from benchmarks.reference.llama import F32
from benchmarks.reference.mellum2 import EXPERT_TENSORS, _lowered

STRATEGIES = ("sequential", "low_confidence_static",
              "low_confidence_dynamic")


def attention(x, w, *, n_heads, n_kv_heads, eps, theta, block_length,
              whole_width_norm=False):
    """x [B, T, D] float32 plus the block's block-causal attention of
    its pre-norm, with the per-head query/key norm.
    ``whole_width_norm``: the CONTROL that norms the whole projected
    width at once (OLMoE's), with the [hd] scale tiled over the heads."""
    B, T, D = x.shape
    hd = w["wq"].shape[1] // n_heads
    h = llama.rms_norm(x, w["attn_norm"], eps)
    q, k = h @ w["wq"], h @ w["wk"]
    if whole_width_norm:
        q = llama.rms_norm(q, jnp.tile(w["q_norm"], n_heads), eps)
        k = llama.rms_norm(k, jnp.tile(w["k_norm"], n_kv_heads), eps)
    q = q.reshape(B, T, n_heads, hd)
    k = k.reshape(B, T, n_kv_heads, hd)
    if not whole_width_norm:
        q = llama.rms_norm(q, w["q_norm"], eps)
        k = llama.rms_norm(k, w["k_norm"], eps)
    q, k = llama.rotary(q, theta), llama.rotary(k, theta)
    v = (h @ w["wv"]).reshape(B, T, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    blocks = jnp.arange(T) // block_length
    seen = blocks[None, :] <= blocks[:, None]
    s = jnp.where(seen[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, n_heads * hd)
    return x + a @ w["wo"]


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "eps", "theta", "top_k", "block_length",
    "whole_width_norm", "lower_precision"))
def layer(x, w, *, n_heads, n_kv_heads, eps, theta, top_k, block_length,
          whole_width_norm=False, lower_precision=False):
    """One decoder block on x [B, T, D] float32. ``lower_precision`` is
    the CONTROL that the comparison which decides ``correct`` must fail
    (every matrix rounded to float8 e4m3): never set by the harness."""
    with jax.default_matmul_precision("highest"):
        if lower_precision:
            w = {k: _lowered(a) for k, a in w.items()}
        w = {k: a if k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        x = attention(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                      eps=eps, theta=theta, block_length=block_length,
                      whole_width_norm=whole_width_norm)
        return x + mellum2.moe(llama.rms_norm(x, w["ffn_norm"], eps), w,
                               top_k)


def forward(weights, ids, block_length, masked=None, *, mask_token_id,
            eps, rows=None, lower_precision=False, **sizes):
    """ids [B, T] int32 -> logits float32 under the block-causal mask.
    ``masked`` [B, T] bool: those positions take the mask token's
    embedding whatever ``ids`` holds there. ``rows`` (start, stop): the
    head runs over those positions alone, [B, stop - start, V] (the
    blocks run over every position); None: [B, T, V]. ``sizes`` are the
    layer's own (n_heads, n_kv_heads, theta, top_k) and its controls
    (``block_length`` 1 in their place is the causal-mask control)."""
    ids = jnp.asarray(ids, jnp.int32)
    if masked is not None:
        ids = jnp.where(jnp.asarray(masked), mask_token_id, ids)
    embed, head = weights["embed"], weights["head"]
    if lower_precision:
        embed, head = _lowered(embed), _lowered(head)
    x = llama._embed(embed, ids)
    for w in weights["layers"]:
        x = layer(x, w, eps=eps, block_length=block_length,
                  lower_precision=lower_precision, **sizes)
    if rows is not None:
        x = x[:, rows[0]:rows[1]]
    return llama._head(x, weights["norm"], head, eps=eps)


def transfer_counts(block_length: int, steps: int):
    """The source's ``get_num_transfer_tokens``."""
    return [block_length // steps + (s < block_length % steps)
            for s in range(steps)]


def confidences(logits):
    """(x^ [.., L], log c [.., L]) of logits [.., L, V] at greedy: the
    argmax and the log of its softmax probability, float32 (numpy)."""
    logits = np.asarray(logits, np.float32)
    best = logits.argmax(-1)
    top = logits.max(-1)
    lse = top + np.log(np.exp(logits - top[..., None]).sum(-1))
    return best, top - lse


def reveal(masked, log_conf, n, strategy, threshold):
    """[L] bool: the masked positions a step reveals (the module
    docstring's three strategies; ties to the lower position)."""
    masked = np.asarray(masked, bool)
    at = np.flatnonzero(masked)
    if strategy == "sequential":
        pick = at[:n]
    else:
        # stable: equal confidences keep their positions' order
        top = at[np.argsort(-log_conf[at], kind="stable")[:n]]
        high = at[log_conf[at] > np.log(threshold)] \
            if threshold > 0 else at
        pick = high if (strategy == "low_confidence_dynamic"
                        and len(high) >= n) else top
    out = np.zeros(masked.shape, bool)
    out[pick] = True
    return out


def generate(weights, prompt, n_new, *, block_length, denoising_steps,
             remasking, confidence_threshold, mask_token_id, **sizes):
    """The source's loop at greedy for ONE prompt (a list of ids):
    (tokens [n_new], steps [n_new], logits [n_new, V]): each generated
    token, the forward of its block (0 = the block's first) that
    revealed it, and the logits at which it was chosen."""
    if remasking not in STRATEGIES:
        raise ValueError(f"remasking={remasking!r} is not one of "
                         f"{STRATEGIES}")
    L, P = block_length, len(prompt)
    n_blocks = -(-(P + n_new) // L)
    total = n_blocks * L
    x = np.zeros((total,), np.int32)
    x[:P] = prompt
    flags = np.arange(total) >= P
    counts = transfer_counts(L, denoising_steps)
    steps = np.zeros((total,), np.int32)
    chosen_at = np.zeros((total, weights["head"].shape[0]), np.float32)
    for b in range(P // L, n_blocks):
        lo, hi = b * L, (b + 1) * L
        for s in range(denoising_steps):
            if not flags[lo:hi].any():
                break
            logits = np.asarray(forward(
                weights, x[None], L, flags[None], rows=(lo, hi),
                mask_token_id=mask_token_id, **sizes))[0]
            best, log_conf = confidences(logits)
            now = reveal(flags[lo:hi], log_conf, counts[s], remasking,
                         confidence_threshold)
            x[lo:hi][now] = best[now]
            steps[lo:hi][now] = s
            chosen_at[lo:hi][now] = logits[now]
            flags[lo:hi] &= ~now
        # (the commit: one more forward writes the block's K/V; with no
        # cache there is nothing to write)
    return x[P:P + n_new], steps[P:P + n_new], chosen_at[P:P + n_new]
