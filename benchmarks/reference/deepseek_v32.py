"""DeepSeek-V3.2 (deepseek-ai/DeepSeek-V3.2, ``model_type:
deepseek_v32``), forward only: plain jax.numpy in float32 at ``highest``
matmul precision, no cache, no kernel, never the absorbed form, and the
selection EXPLICIT (scores, a sort, a mask). Norm, rope, YaRN's
frequencies, embedding and head are ``reference/axk1.py``'s and
``reference/llama.py``'s; the indexer, the selection, the attention
under its mask, the router's group limit and the layer loop are this
file's. What ``config.json`` leaves open is marked (assumed) here and
listed, each with its reason, under ``assumed`` in
benchmarks/configs/deepseek-v3.2-d5-ep32.json.

Every block is pre-norm: ``x' = x + DSA(RMSNorm(x)); y = x' +
FFN(RMSNorm(x'))``. A token t of a layer, h its normed input:

1. Query.  c_q = RMSNorm(h W_qa) (q_lora_rank); (c_q W_qb)^i = [q_nope^i
   | q_rope^i] (128 | 64) a head i of 128; rope on the 64 (YaRN: factor
   40 over 4,096 original positions, beta 32 / 1, cos and sin x
   mscale / mscale_all_dim = 1; rotate-half pairing, assumed as A.X-K1's
   file argues).
2. Latent entry.  [c | k_r] = [RMSNorm((h W_kva)[:512]) |
   rope((h W_kva)[512:])]; [k_nope^i | v^i] = (c W_kvb)^i.
3. Indexer.  q_I^j = (c_q W_Iq)^j, 64 heads j of 128, rope on the FIRST
   64 columns (assumed: the release's inference code splits a head
   ``[rope | nope]``; the layer's own YaRN frequencies and pairing);
   k_I = LayerNorm(h W_Ik) (128; scale AND bias, eps = rms_norm_eps:
   assumed, the inference code's ``k_norm``), rope on the same 64;
   w = h W_Iw x 64^-0.5 x 128^-0.5 (assumed: the inference code's two
   scales; the release rounds q_I and k_I to float8 after a Hadamard
   rotation, an orthogonal map that leaves q_I . k_I as it is in exact
   arithmetic: left out).
       I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),  s <= t
   S_t = the min(index_topk, t + 1) positions of largest I[t, .], a tie
   to the lower s (assumed: the release's ``topk`` leaves ties open).
4. Attention over S_t alone.  score_i(t, s) = (q_nope^i . k_nope^i_s +
   q_rope^i . k_r,s) x 192^-0.5 x mscale(40, 1)^2, softmax over s in
   S_t, o^i = sum p v^i_s, out = concat_i(o^i) W_o. For t < index_topk
   this is A.X-K1's layer.
5. Feed-forward.  Layers 0 .. first_k_dense_replace - 1 a SwiGLU of
   ``intermediate_size``. The others (``topk_method: noaux_tc``):
   s = sigmoid(h W_r) in float32 over the router's width; choice scores
   s + b (b the stored bias); a group of E / n_group consecutive
   experts scores the sum of its two largest s + b; the ``topk_group``
   best groups stay (a tie to the lower group); the ``top_k`` largest
   s + b inside them are chosen (a tie to the lower expert); gates
   s_chosen / sum(s_chosen) x routed_scaling_factor; plus the shared
   expert. The result sums the chosen experts THIS SHARE HOLDS (lo ..
   lo + n of the router's width); gates are normalised over all chosen,
   held or not.

CONTROLS of the comparison that decides ``correct`` (never set by the
harness): see ``CONTROLS``.

It fits beside the served model because weights are upcast a layer's
(an expert's) at a time, rows of the batch are taken one at a time,
queries in blocks of ``Q_BLOCK``, and the head in blocks of positions.

    weights = reference/axk1.py's, and a layer's "index_wq" [Rq, Hi Di],
      "index_wk" [D, Di], "index_k_scale" [Di], "index_k_bias" [Di],
      "index_w" [D, Hi]; a mixture layer's "router_bias" [E]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import axk1, llama
from benchmarks.reference.axk1 import EXPERT_TENSORS
from benchmarks.reference.llama import F32

Q_BLOCK = 256          # queries scored and attended at once
HEAD_BLOCK = 1024      # positions the head is applied to at once
LOWER = jnp.float8_e4m3fn      # the nearest precision below bfloat16

CONTROLS = {
    "no_selection": "every entry at or before a query is attended",
    "recent": "the most recent index_topk entries are attended, not "
              "the chosen ones",
    "index_topk": "another number of entries is chosen (a value, not "
                  "a flag)",
    "no_group_limit": "the router ranks all experts, not the best "
                      "groups'",
    "no_bias": "the router's stored choice bias is left out",
    "lower_precision": "every matrix is rounded to float8 e4m3",
}


def _lowered(a):
    return a.astype(LOWER).astype(a.dtype) if a.ndim > 1 else a


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope_first(x, n, inv_freq, m_rope):
    """x [B, T, heads, d]: rope on its first ``n`` columns."""
    return jnp.concatenate([axk1.rotary(x[..., :n], inv_freq, m_rope),
                            x[..., n:]], axis=-1)


def index_scores(h, c_q, w, *, idx_heads, rope, eps, inv_freq, m_rope):
    """I[b, t, s] float32 [B, T, T], ``-inf`` at s > t."""
    B, T, _ = h.shape
    Di = w["index_wk"].shape[1]
    q = _rope_first((c_q @ w["index_wq"]).reshape(B, T, idx_heads, Di),
                    rope, inv_freq, m_rope)
    k = layer_norm(h @ w["index_wk"], w["index_k_scale"],
                   w["index_k_bias"], eps)
    k = _rope_first(k[:, :, None], rope, inv_freq, m_rope)[:, :, 0]
    weight = (h @ w["index_w"]) * (idx_heads ** -0.5 * Di ** -0.5)

    def block(t0, n):
        dots = jnp.einsum("bqjd,bsd->bqjs", q[:, t0:t0 + n], k)
        return jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(dots),
                          weight[:, t0:t0 + n])
    scores = jnp.concatenate([block(t0, min(Q_BLOCK, T - t0))
                              for t0 in range(0, T, Q_BLOCK)], axis=1)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    return jnp.where(causal[None], scores, -jnp.inf)


def selection(scores, topk: int):
    """S_t as a mask [B, T, T]: position s is in S_t where fewer than
    ``topk`` positions rank before it in the order (larger score first,
    among equal scores the lower position first) and s <= t."""
    T = scores.shape[-1]
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    return (rank < topk) & causal[None]


def dsa(x, w, *, n_heads, nope, rope, eps, yarn, idx_heads, topk,
        no_selection=False, recent=False, chosen=False):
    """x [B, T, D] float32 plus the sparse latent attention of its
    pre-norm; with ``chosen`` also the mask S [B, T, T]."""
    B, T, D = x.shape
    H, R = n_heads, w["kv_norm"].shape[0]
    dv = w["wkv_b"].shape[1] // H - nope
    theta, factor, original, fast, slow, mscale, mscale_all = yarn
    inv_freq = axk1.yarn_inv_freq(rope, theta, factor, original, fast,
                                  slow)
    m_rope = (axk1.yarn_mscale(factor, mscale)
              / axk1.yarn_mscale(factor, mscale_all))
    scale = (nope + rope) ** -0.5 * axk1.yarn_mscale(factor,
                                                     mscale_all) ** 2

    h = llama.rms_norm(x, w["attn_norm"], eps)
    c_q = llama.rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    q = (c_q @ w["wq_b"]).reshape(B, T, H, nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         axk1.rotary(q[..., nope:], inv_freq, m_rope)], -1)
    kv = h @ w["wkv_a"]
    c = llama.rms_norm(kv[..., :R], w["kv_norm"], eps)
    k_rope = axk1.rotary(kv[..., None, R:], inv_freq, m_rope)
    kv = (c @ w["wkv_b"]).reshape(B, T, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, T, H, rope))], -1)
    v = kv[..., nope:]

    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    if no_selection:
        member = jnp.broadcast_to(causal[None], (B, T, T))
    elif recent:
        near = jnp.arange(T)[None, :] > jnp.arange(T)[:, None] - topk
        member = jnp.broadcast_to((causal & near)[None], (B, T, T))
    else:
        member = selection(index_scores(
            h, c_q, w, idx_heads=idx_heads, rope=rope, eps=eps,
            inv_freq=inv_freq, m_rope=m_rope), topk)

    def attend(t0, n):
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, t0:t0 + n], k) * scale
        s = jnp.where(member[:, None, t0:t0 + n], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, n, H * dv)
    a = jnp.concatenate([attend(t0, min(Q_BLOCK, T - t0))
                         for t0 in range(0, T, Q_BLOCK)], axis=1)
    out = x + a @ w["wo"]
    return (out, member) if chosen else out


def _best(x, k: int):
    """Indices of the ``k`` largest along the last axis, among equal
    values the lower index first."""
    return jnp.argsort(-x, axis=-1, stable=True)[..., :k]


def group_limit(choice, n_group: int, topk_group: int):
    """choice [N, E] -> (choice with every expert outside the token's
    ``topk_group`` best groups at ``-inf``, the groups' scores [N, G],
    which stay [N, G]): a group of E / n_group consecutive experts
    scores the sum of its two largest values, a tie to the lower
    group."""
    N, E = choice.shape
    G = max(1, n_group)
    groups = choice.reshape(N, G, E // G)
    group_score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)
    if G == 1:
        return choice, group_score, jnp.ones((N, 1), bool)
    stay = _best(group_score, topk_group)
    allowed = jnp.zeros((N, G), bool).at[
        jnp.arange(N)[:, None], stay].set(True)
    return (jnp.where(allowed[:, :, None], groups, -jnp.inf).reshape(N, E),
            group_score, allowed)


def choice_scores(tokens, w, *, n_group, topk_group, no_group_limit=False,
                  no_bias=False):
    """tokens [N, D] -> (s [N, E], choice [N, E], group scores [N, G],
    allowed [N, G]): the sigmoids, what the experts are ranked by
    (``-inf`` outside the groups that stay) and the groups' own."""
    s = jax.nn.sigmoid(tokens @ w["router"])
    choice = s if no_bias else s + w["router_bias"]
    return (s,) + group_limit(choice, 1 if no_group_limit else n_group,
                              topk_group)


def route(tokens, w, *, top_k, norm_topk, scaling, **rule):
    """tokens [N, D] -> each token's weight on every expert of the
    router's width [N, E]: zero but for its ``top_k``."""
    s, choice, _, _ = choice_scores(tokens, w, **rule)
    idx = _best(choice, top_k)
    gates = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    rows = jnp.arange(tokens.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(gates * scaling)


def routed(h, w, *, lo, **rule):
    """The part of the mixture that the experts held here give, and
    which of them each token chose [B, T, n]."""
    B, T, D = h.shape
    tokens = h.reshape(B * T, D)
    n = w["w_gate"].shape[0]
    weight = route(tokens, w, **rule)[:, lo:lo + n]

    def one_expert(acc, ew):
        w_gate, w_up, w_down = (a.astype(F32) for a in ew[:3])
        y = (jax.nn.silu(tokens @ w_gate) * (tokens @ w_up)) @ w_down
        return acc + y * ew[3][:, None], None
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return out.reshape(B, T, D), (weight > 0).reshape(B, T, n)


def choice_margin(h, w, *, top_k, lo, n_group, topk_group):
    """How far each position's CHOICE of held experts is from changing,
    in units of the hidden state's relative error: h [B, T, D] (the
    router's input) -> [B, T] float32. reference/axk1.py's rule for a
    router that ranks inside groups. An error of h of relative size e
    moves expert j's s by about ``reach_j`` x e (axk1.py
    ``choice_margin``). The choice of held experts changes

    - where a held expert that may be chosen (its group stays) crosses
      the boundary between the ``top_k``-th and the next of the ranked
      values: its distance from the boundary over its reach; or
    - where the last group that stays and the first that does not
      change places (each moves by the reaches of its two best: half
      their distance over the larger of the two) AND that exchange
      changes which held experts are chosen: the router is run again
      with the two groups exchanged, and a tie between groups whose
      outcome this share's experts do not feel is no tie here.

    The margin is the least of those."""
    B, T, D = h.shape
    tokens = h.reshape(B * T, D)
    n = w["w_gate"].shape[0]
    s, choice, group_score, allowed = choice_scores(
        tokens, w, n_group=n_group, topk_group=topk_group)
    N, E = s.shape
    held = slice(lo, lo + n)
    reach = (s * (1.0 - s) * jnp.linalg.norm(w["router"], axis=0)
             * jnp.linalg.norm(tokens, axis=-1, keepdims=True)
             / jnp.sqrt(F32(D)))

    def held_choice(choice):
        """(which held experts are chosen [N, n], their margin [N])."""
        top = jnp.sort(choice, axis=-1)[:, -(top_k + 1):]
        boundary = 0.5 * (top[:, 0] + top[:, 1])
        margin = jnp.min(jnp.where(
            jnp.isfinite(choice[:, held]),
            jnp.abs(choice[:, held] - boundary[:, None]) / reach[:, held],
            jnp.inf), axis=-1)
        rows = jnp.arange(N)[:, None]
        chosen = jnp.zeros((N, E), bool).at[
            rows, _best(choice, top_k)].set(True)
        return chosen[:, held], margin
    chosen, margin = held_choice(choice)
    G = group_score.shape[1]
    if G <= topk_group:
        return margin.reshape(B, T)
    ranked = (s + w["router_bias"]).reshape(N, G, E // G)
    two = jnp.argsort(ranked, axis=-1)[..., -2:]
    group_reach = jnp.sum(jnp.take_along_axis(
        reach.reshape(N, G, E // G), two, axis=-1), axis=-1)
    order = _best(group_score, topk_group + 1)
    last_in, first_out = order[:, topk_group - 1], order[:, topk_group]
    rows = jnp.arange(N)
    gap = group_score[rows, last_in] - group_score[rows, first_out]
    group_margin = 0.5 * gap / jnp.maximum(group_reach[rows, last_in],
                                           group_reach[rows, first_out])
    exchanged = allowed.at[rows, last_in].set(False).at[
        rows, first_out].set(True)
    other, _ = held_choice(jnp.where(exchanged[:, :, None], ranked,
                                     -jnp.inf).reshape(N, E))
    felt = jnp.any(other != chosen, axis=-1)
    return jnp.minimum(margin, jnp.where(felt, group_margin, jnp.inf)
                       ).reshape(B, T)


def feed_forward(x, w, *, eps, top_k, lo, norm_topk, scaling, n_group,
                 topk_group, no_group_limit=False, no_bias=False):
    """A block's second half, the positions' ``choice_margin``
    (infinite in a dense layer) and the held experts each chose
    [B, T, n] (None in a dense layer)."""
    h = llama.rms_norm(x, w["ffn_norm"], eps)
    if "router" not in w:
        y = (jax.nn.silu(h @ w["w_gate"].astype(F32))
             * (h @ w["w_up"].astype(F32))) @ w["w_down"].astype(F32)
        return x + y, jnp.full(x.shape[:2], jnp.inf, F32), None
    y, held = routed(h, w, lo=lo, top_k=top_k, norm_topk=norm_topk,
                     scaling=scaling, n_group=n_group,
                     topk_group=topk_group, no_group_limit=no_group_limit,
                     no_bias=no_bias)
    margin = choice_margin(h, w, top_k=top_k, lo=lo, n_group=n_group,
                           topk_group=topk_group)
    return x + y + axk1.shared(h, w), margin, held


_STATIC = ("n_heads", "nope", "rope", "eps", "yarn", "idx_heads", "topk",
           "top_k", "lo", "norm_topk", "scaling", "n_group", "topk_group",
           "no_selection", "recent", "no_group_limit", "no_bias",
           "lower_precision", "chosen")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer_and_margin(x, w, *, n_heads, nope, rope, eps, yarn, idx_heads,
                     topk, top_k, lo, norm_topk, scaling, n_group,
                     topk_group, no_selection=False, recent=False,
                     no_group_limit=False, no_bias=False,
                     lower_precision=False, chosen=False):
    """One decoder block on x [B, T, D] float32, its positions'
    ``choice_margin`` [B, T], with ``chosen`` its S [B, T, T], and the
    held experts each position chose [B, T, n] (None: a dense layer)."""
    with jax.default_matmul_precision("highest"):
        if lower_precision:
            w = {k: _lowered(a) for k, a in w.items()}
        # an expert's matrices, and the dense layer's 3 x 132 M, are
        # upcast as they are used
        w = {k: a if k in EXPERT_TENSORS else a.astype(F32)
             for k, a in w.items()}
        got = dsa(x, w, n_heads=n_heads, nope=nope, rope=rope, eps=eps,
                  yarn=yarn, idx_heads=idx_heads, topk=topk,
                  no_selection=no_selection, recent=recent, chosen=chosen)
        x, member = got if chosen else (got, None)
        x, margin, held = feed_forward(
            x, w, eps=eps, top_k=top_k, lo=lo, norm_topk=norm_topk,
            scaling=scaling, n_group=n_group, topk_group=topk_group,
            no_group_limit=no_group_limit, no_bias=no_bias)
        return x, margin, member, held


def hidden(weights, ids, *, index_topk=None, lower_precision=False,
           chosen=False, **sizes):
    """ids [B, T] -> the last block's output [B, T, D] float32, each
    position's least ``choice_margin`` over the layers [B, T] and, with
    ``chosen``, every layer's S [L, B, T, T]. One row of the batch at a
    time."""
    if index_topk is not None:
        sizes["topk"] = index_topk
    embed = weights["embed"]
    if lower_precision:
        embed = _lowered(embed)
    xs, margins, members = [], [], []
    for row in range(ids.shape[0]):
        x = llama._embed(embed, ids[row:row + 1])
        least = jnp.full((1, ids.shape[1]), jnp.inf, F32)
        sets = []
        for w in weights["layers"]:
            x, margin, member, _ = layer_and_margin(
                x, w, lower_precision=lower_precision, chosen=chosen,
                **sizes)
            least = jnp.minimum(least, margin)
            sets.append(member)
        xs.append(x)
        margins.append(least)
        members.append(sets)
    x, least = jnp.concatenate(xs), jnp.concatenate(margins)
    if not chosen:
        return x, least, None
    return x, least, jnp.stack([jnp.concatenate([m[i] for m in members])
                                for i in range(len(weights["layers"]))])


def forward(weights, ids, *, margins=False, chosen=False, **sizes):
    """ids [B, T] int32 -> logits [B, T, V] float32 (numpy); with
    ``margins`` also each position's least ``choice_margin`` [B, T];
    with ``chosen`` also every layer's S [L, B, T, T] (a test's)."""
    x, least, member = hidden(weights, ids, chosen=chosen, **sizes)
    head = weights["head"]
    if sizes.get("lower_precision"):
        head = _lowered(head)
    logits = np.concatenate([
        np.asarray(llama._head(x[:, t0:t0 + HEAD_BLOCK], weights["norm"],
                               head, eps=sizes["eps"]))
        for t0 in range(0, x.shape[1], HEAD_BLOCK)], axis=1)
    out = (logits,)
    if margins:
        out += (np.asarray(least),)
    if chosen:
        out += (np.asarray(member),)
    return out if len(out) > 1 else logits
