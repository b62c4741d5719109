"""Phi-4-mini-flash-reasoning (microsoft, ``model_type: phi4flash``: the
SambaY decoder-hybrid-decoder of arXiv:2507.06607 with differential
attention, arXiv:2410.05258), forward only: plain jax.numpy in float32
at ``highest`` matmul precision, no cache, no chunk, no kernel, nothing
of ``ray_tpu``. What ``config.json`` leaves open is marked (assumed)
here and listed, each with its reason, under ``assumed`` in
benchmarks/configs/phi-4-mini-flash-reasoning.json.

Block ``l`` of N, input x [T, D]: ``h = x + mixer_l(LN(x))``, ``out = h
+ W_down (SiLU(LN(h) W_gate) * LN(h) W_up)`` (no bias; which half of the
published fused ``gate_up_proj`` is gated is assumed: with seeded
matrices the two are the same model). LN is LayerNorm with scale AND
bias, eps ``layer_norm_eps`` (assumed: the release's ``nn.LayerNorm``);
a final LN; logits through the embedding (tied, no bias). No position
encoding anywhere (assumed: the row has no rope key; the state-space
layers carry the order). ``mixer_l`` by layer (``mixers``: which layer
is which is assumed from ``mb_per_layer`` 2, the paper's figure 1 and
the parameter count, 3,852.6 M against the published 3.8 B):

1. STATE-SPACE (l even, l <= N/2; Mamba-1, arXiv:2312.00752, its
   defaults assumed: d_state 16, d_conv 4, expand 2, dt_rank D / 16).
   ``[u | z] = x W_in``; ``u' = SiLU(conv(u) + b_c)``, causal, depthwise;
   ``[d | B | C] = u' W_x``; ``delta = softplus(d W_dt + b_dt)``; ``A =
   -exp(A_log)``; a channel c, a state n, float32, zero before the
   first token:

       h_t[c, n] = exp(delta_t[c] A[c, n]) h_{t-1}[c, n]
                   + delta_t[c] u'_t[c] B_t[n]
       y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] u'_t[c]

   ``mixer = (y * SiLU(z)) W_out``. Layer N/2's ``y`` is the MEMORY the
   gated memory units read: taken before the gate, ``D u'`` included
   (assumed). ``A_log`` is handed over as the program keeps it, [N, C]
   (the release's is [C, N]: the same numbers, transposed).
2. DIFFERENTIAL ATTENTION WITH ITS OWN KEYS (l odd, l < N/2: under a
   sliding window; l = N/2 + 1: the whole context). ``q, k, v = x W + b``
   (biases on q, k, v and o, none elsewhere: assumed): H query and KH
   key/value heads of d = D / H, taken in PAIRS (2i, 2i+1) (assumed:
   the release's interleaving): query pair i is (q1, q2) = heads (2i,
   2i+1), key pair g = i // (H / KH) is (k1, k2) = K/V heads (2g, 2g+1),
   its value v_g = [v_2g | v_2g+1], 2d wide.

       P1 = softmax(q1 k1^T / sqrt(d)),  P2 = softmax(q2 k2^T / sqrt(d))
       o_i = P1 v_g - lambda_l P2 v_g

   over s <= t and, under the window, s > t - W: the last W positions,
   t included (assumed). ``lambda_l = exp(lq1 . lk1) - exp(lq2 . lk2) +
   lambda0_l``, four learned d-vectors a layer, ``lambda0_l = 0.8 - 0.6
   exp(-0.3 l)``, l the layer's index from 0 (assumed: the paper's
   depth rule on the model's own index). Then ``o_i <- RMSNorm(o_i)``
   over its 2d columns (one learned scale of 2d a layer, eps
   ``layer_norm_eps``) times ``1 - lambda0_l``, and ``W_o + b_o``.
3. CROSS ATTENTION (l odd, l > N/2 + 1). A query only; the keys and
   values are layer N/2 + 1's, every s <= t; the differential form of 2
   with the layer's own lambdas and scale.
4. GATED MEMORY UNIT (l even, l > N/2). ``mixer = (m * SiLU(x W_1))
   W_2``, m the memory at the same position.

    weights = {"embed": [V, D], "norm": [D], "norm_bias": [D], "layers":
      [{"ln1", "ln1_bias", "ln2", "ln2_bias": [D], "w_gate", "w_up":
        [D, F], "w_down": [F, D],
        state-space: "w_in": [D, 2C], "conv": [K, C], "conv_bias": [C],
          "w_x": [C, R + 2N], "w_dt": [R, C], "dt_bias": [C], "A_log":
          [N, C], "D": [C], "w_out": [C, D]
        attention: "wq", "bq", "wo", "bo", "lambda_q1", "lambda_k1",
          "lambda_q2", "lambda_k2": [d], "subln": [2d]; with its own
          keys also "wk", "bk", "wv", "bv"
        memory unit: "w1": [D, C], "w2": [C, D]}]}

The CONTROLS (``forward``'s keyword arguments, which the harness never
sets) each change one thing the comparison that decides ``correct``
must catch: the cross layers given the last sliding layer's keys and
values (``cross_from_sliding``), the memory taken after the gate
(``memory_after_gate``), lambda fixed at lambda0 (``lambda_fixed``), the
window half as wide again, a prefill chunk more at the published sizes
(``wide_window``), a state handed on in bfloat16 (``bf16_state``), and
the precision below the configuration's (``lower_precision``: every
matrix and the embedding rounded to float8 e4m3).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
SSM, SLIDING, FULL, CROSS, GMU = "ssm", "sliding", "full", "cross", "gmu"
CONTROLS = ("cross_from_sliding", "memory_after_gate", "lambda_fixed",
            "wide_window", "bf16_state", "lower_precision")


def mixers(n_layers: int):
    """Each layer's mixer (assumed: the module's docstring)."""
    half = n_layers // 2

    def of(l):
        if l % 2 == 0:
            return SSM if l <= half else GMU
        return SLIDING if l < half else FULL if l == half + 1 else CROSS
    return tuple(of(l) for l in range(n_layers))


def lambda0(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def lowered(a):
    """A matrix rounded to float8 e4m3 (the ``lower_precision``
    control); vectors (norms, biases, lambdas, D) stay."""
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim > 1 else a


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def selective_scan(u, delta, A, Bm, Cm, D, bf16_state=False):
    """The recurrence itself, one position at a time, from a state of
    zeros. u, delta [B, T, C]; A [N, C]; Bm, Cm [B, T, N]; D [C];
    returns y [B, T, C]."""
    B, T, C = u.shape

    def step(h, xs):
        u, d, b, c = xs
        h = jnp.exp(d[:, None, :] * A) * h + (d * u)[:, None, :] * b[:, :, None]
        if bf16_state:
            h = h.astype(jnp.bfloat16).astype(F32)
        return h, jnp.sum(h * c[:, :, None], axis=1) + D * u
    _, y = jax.lax.scan(
        step, jnp.zeros((B, A.shape[0], C), F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (u, delta, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def state_space(x, w, *, bf16_state=False):
    """x [B, T, D] float32 -> (its state-space layer's mixing, the
    scan's output y before the gate, the same after it)."""
    T = x.shape[1]
    K, C = w["conv"].shape
    N = w["A_log"].shape[0]
    R = w["w_x"].shape[1] - 2 * N
    u, z = jnp.split(x @ w["w_in"], 2, axis=-1)
    before = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(before[:, j:j + T] * w["conv"][j] for j in range(K))
                    + w["conv_bias"])
    d, Bm, Cm = jnp.split(u @ w["w_x"], (R, R + N), axis=-1)
    delta = jax.nn.softplus(d @ w["w_dt"] + w["dt_bias"])
    y = selective_scan(u, delta, -jnp.exp(w["A_log"]), Bm, Cm, w["D"],
                       bf16_state)
    gated = y * jax.nn.silu(z)
    return gated @ w["w_out"], y, gated


def differential_attention(x, w, kv, *, l0, n_heads, n_kv_heads, eps,
                           window=None, lambda_fixed=False):
    """x [B, T, D] float32 -> (its differential attention, the (k, v)
    it attended). ``kv`` None: the layer's own keys and values; else
    another layer's (k, v) [B, T, KH, d]. ``l0``: the layer's lambda0.
    One row and one key pair at a time: the two maps of a key pair's
    query pairs are [2 rep, T, T]."""
    B, T, _ = x.shape
    d = w["wq"].shape[1] // n_heads
    rep = n_heads // n_kv_heads             # query pairs a key pair
    q = (x @ w["wq"] + w["bq"]).reshape(B, T, n_heads, d)
    if kv is None:
        k = (x @ w["wk"] + w["bk"]).reshape(B, T, n_kv_heads, d)
        v = (x @ w["wv"] + w["bv"]).reshape(B, T, n_kv_heads, d)
    else:
        k, v = kv
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    lam = l0 if lambda_fixed else (
        jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + l0)

    def maps(qh, kh):
        """softmax(qh kh^T / sqrt(d)) under the mask: [rep, T, T]."""
        s = jnp.einsum("tpd,sd->pts", qh, kh) / jnp.sqrt(F32(d))
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)

    def key_pair(args):
        q1, q2, k1, k2, vg = args       # [T, rep, d] x 2, [T, d] x 2, [T, 2d]
        o = (jnp.einsum("pts,se->tpe", maps(q1, k1), vg)
             - lam * jnp.einsum("pts,se->tpe", maps(q2, k2), vg))
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        return o * w["subln"] * (1.0 - l0)                   # [T, rep, 2d]

    def row(args):
        q, k, v = args                   # [T, H, d], [T, KH, d] x 2
        G = n_kv_heads // 2
        qp = q.reshape(T, G, rep, 2, d)
        kp, vp = k.reshape(T, G, 2, d), v.reshape(T, G, 2 * d)
        o = jax.lax.map(key_pair, (
            jnp.moveaxis(qp[..., 0, :], 1, 0), jnp.moveaxis(qp[..., 1, :], 1, 0),
            jnp.moveaxis(kp[:, :, 0], 1, 0), jnp.moveaxis(kp[:, :, 1], 1, 0),
            jnp.moveaxis(vp, 1, 0)))                         # [G, T, rep, 2d]
        return jnp.moveaxis(o, 0, 1).reshape(T, n_heads * d)
    o = jax.lax.map(row, (q, k, v))
    return o @ w["wo"] + w["bo"], (k, v)


@functools.partial(jax.jit, static_argnames=(
    "mixer", "n_heads", "n_kv_heads", "eps", "window") + CONTROLS)
def block(x, w, carried, l0, *, mixer, n_heads, n_kv_heads, eps, window,
          cross_from_sliding=False, memory_after_gate=False,
          lambda_fixed=False, wide_window=False, bf16_state=False,
          lower_precision=False):
    """One decoder block on x [B, T, D] float32. ``carried``: {"memory":
    the memory layer's y, "shared": the full layer's (k, v), "sliding":
    the last sliding layer's}, what is there so far; ``l0``: the layer's
    lambda0 (an argument, so that the layers of one kind share one
    compiled program); returns (x, what this block adds to it)."""
    with jax.default_matmul_precision("highest"):
        if lower_precision:
            w = jax.tree_util.tree_map(lowered, w)
        w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
        h = layer_norm(x, w["ln1"], w["ln1_bias"], eps)
        adds = {}
        if mixer == SSM:
            mixed, y, gated = state_space(h, w, bf16_state=bf16_state)
            adds["memory"] = gated if memory_after_gate else y
        elif mixer == GMU:
            mixed = (carried["memory"] * jax.nn.silu(h @ w["w1"])) @ w["w2"]
        else:
            if wide_window:
                window = window + window // 2
            kv = None
            if mixer == CROSS:
                kv = carried["sliding" if cross_from_sliding else "shared"]
            mixed, kv = differential_attention(
                h, w, kv, l0=l0, n_heads=n_heads,
                n_kv_heads=n_kv_heads, eps=eps,
                window=window if mixer == SLIDING else None,
                lambda_fixed=lambda_fixed)
            if mixer != CROSS:
                adds["shared" if mixer == FULL else "sliding"] = kv
        x = x + mixed
        h = layer_norm(x, w["ln2"], w["ln2_bias"], eps)
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        return x, adds


@functools.partial(jax.jit, static_argnames=("eps", "slices"))
def head(x, norm, norm_bias, embed, *, eps, slices=8):
    """The final LayerNorm and the tied head on x [B, T, D] float32 ->
    logits [B, T, V]. The vocabulary goes in ``slices`` (where they
    divide it), each upcast as it is used: 200,064 x 2,560 in float32
    is 2 GB, beside a chip the served model fills."""
    V, D = embed.shape
    if V % slices:
        slices = 1
    with jax.default_matmul_precision("highest"):
        h = layer_norm(x, norm.astype(F32), norm_bias.astype(F32), eps)
        out = jax.lax.map(lambda w: h @ w.astype(F32).T,
                          embed.reshape(slices, V // slices, D))
    return jnp.moveaxis(out, 0, -2).reshape(h.shape[:-1] + (V,))


def embedding(weights, lower_precision=False, **_):
    """The embedding (and tied head) under the controls."""
    return lowered(weights["embed"]) if lower_precision else weights["embed"]


def blocks(weights, ids, *, n_heads, n_kv_heads, eps, window, **control):
    """ids [B, T] int32 -> the last block's output [B, T, D] float32,
    before the final norm."""
    x = embedding(weights, **control)[ids].astype(F32)
    layout = mixers(len(weights["layers"]))
    memory_layer = len(layout) // 2
    carried = {}
    for l, (mixer, w) in enumerate(zip(layout, weights["layers"])):
        x, adds = block(x, w, carried, F32(lambda0(l)), mixer=mixer,
                        n_heads=n_heads, n_kv_heads=n_kv_heads, eps=eps,
                        window=window, **control)
        if mixer != SSM or l == memory_layer:
            carried = {**carried, **adds}
    return x


def forward(weights, ids, *, n_heads, n_kv_heads, eps, window, **control):
    """ids [B, T] int32 -> logits [B, T, V] float32."""
    x = blocks(weights, ids, n_heads=n_heads, n_kv_heads=n_kv_heads,
               eps=eps, window=window, **control)
    return head(x, weights["norm"], weights["norm_bias"],
                embedding(weights, **control), eps=eps)
