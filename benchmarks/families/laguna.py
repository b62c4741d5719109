"""The Laguna family (poolside/Laguna-XS.2, ``model_type: laguna``) as
the benchmark sees it: a decoder whose layers keep caches of TWO SIZES
(one layer in four attends the whole context under YaRN over HALF of a
head's columns, three a sliding window of 512 keys under plain rope at
another base) and whose QUERY differs by layer type over one K/V pool:
48 heads in a full layer, 64 in a sliding one, 8 K/V heads of an
explicit ``head_dim`` 128 in both; a gate a head on the attention's
output; layer 0's feed-forward a dense SwiGLU of 8,192, the others' a
mixture of 256 experts of 512, 8 a token under a sigmoid router whose
chosen gates are renormalised and times 2.5, beside ONE shared expert;
an untied head. The program serves it as ``ray_tpu.models.laguna``;
``program_config`` refuses at once (SystemExit, before a weight is
made) a program that has no such module or whose config lacks a field
the model needs.

A configuration of this family holds EVERY expert and the WHOLE
vocabulary; it is cut in depth alone. ``layer_types``,
``mlp_layer_types`` and ``num_attention_heads_per_layer`` keep their
published entries and the first ``num_hidden_layers`` of them are read.

What Mellum 2's family counts the same way (a ring's length and bytes,
a window's keys, a token's K/V in the full layers, an expert's bytes,
the sums of a split's parts) is that file's, read here through its
functions: the two configurations name those sizes by the same
published keys. What differs is this file's: the query heads BY LAYER
TYPE in every count that has them, the gate, the dense layer, the
shared expert, and ``ring_copies``, which here are the compiler's
whole-ring copies BY OPCODE (PERF.md section 7 after PR 52 (b): by
shape alone the decode loop's own ``while`` is one).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from benchmarks import (common, costs, trace_dispatch, trace_parts,
                        trace_reduce, weights)
from benchmarks.reference import laguna as ref

SLIDING, FULL = ref.SLIDING, ref.FULL
CONTROLS = ref.CONTROLS

_NEEDS = ("head_dim", "layer_types", "n_heads_per_layer",
          "mlp_layer_types", "sliding_window", "gating",
          "partial_rotary_factor", "sliding_rope_theta",
          "sliding_partial_rotary_factor", "yarn_factor",
          "yarn_original_max_seq_len", "yarn_beta_fast", "yarn_beta_slow",
          "yarn_attention_factor", "dense_hidden_dim", "n_shared_experts",
          "router", "routed_scaling_factor", "norm_topk_prob",
          "tie_word_embeddings")

_mellum = common.load_family("mellum2", "serve")
_types = _mellum._types
n_sliding_layers, n_full_layers = (_mellum.n_sliding_layers,
                                   _mellum.n_full_layers)
key_bytes, kv_bytes_per_token = _mellum.key_bytes, _mellum.kv_bytes_per_token
ring_len, state_bytes = _mellum.ring_len, _mellum.state_bytes
sliding_bytes_per_slot = _mellum.sliding_bytes_per_slot
sliding_step_bytes, unaged_bytes = (_mellum.sliding_step_bytes,
                                    _mellum.unaged_bytes)
expert_bytes = _mellum.expert_bytes
experts_step_bytes = _mellum.experts_step_bytes
experts_step_flops = _mellum.experts_step_flops
under, sliding_s = _mellum.under, _mellum.sliding_s


def heads_by_type(cfg: Dict[str, Any]) -> Dict[str, int]:
    """{layer type: its query heads} over the cut's layers."""
    return dict(zip(_types(cfg), cfg["num_attention_heads_per_layer"]))


def n_moe_layers(cfg: Dict[str, Any]) -> int:
    return cfg["mlp_layer_types"][:cfg["num_hidden_layers"]].count(
        "sparse")


def n_dense_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"] - n_moe_layers(cfg)


def program_config(cfg: Dict[str, Any]):
    """LagunaConfig from the published key names."""
    import jax.numpy as jnp
    try:
        from ray_tpu.models.laguna import LagunaConfig
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program cannot express "
                         f"Laguna: it has no ray_tpu.models.laguna "
                         f"({e})")
    have = {f.name for f in dataclasses.fields(LagunaConfig)}
    lacks = [n for n in _NEEDS if n not in have]
    if lacks:
        raise SystemExit(f"benchmarks: the program's LagunaConfig cannot "
                         f"express Laguna: it has no {lacks}")
    rp = cfg["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    L = cfg["num_hidden_layers"]
    refused = {
        "attention_bias": cfg["attention_bias"],
        "tie_word_embeddings": cfg["tie_word_embeddings"],
        "gating false": cfg["gating"] is not True,
        "moe_apply_router_weight_on_input":
            cfg["moe_apply_router_weight_on_input"],
        "full_attention rope_type other than yarn":
            full["rope_type"] != "yarn",
        "sliding_attention rope_type other than default":
            sliding["rope_type"] != "default",
        "two partial_rotary_factor for the full layers":
            full["partial_rotary_factor"] != cfg["partial_rotary_factor"],
        "a shared expert of another width than the routed ones":
            cfg["shared_expert_intermediate_size"]
            % cfg["moe_intermediate_size"] != 0,
        "lists shorter than the depth": min(
            len(cfg[k]) for k in ("layer_types", "mlp_layer_types",
                                  "num_attention_heads_per_layer")) < L,
        "num_attention_heads other than the full layers'":
            heads_by_type(cfg).get(FULL) != cfg["num_attention_heads"],
    }
    if any(refused.values()):
        raise SystemExit(f"benchmarks: the program's Laguna has no "
                         f"{[k for k, v in refused.items() if v]}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return LagunaConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=L,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"]),
        n_heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        sliding_window=cfg["sliding_window"], gating=True,
        rope_theta=float(full["rope_theta"]),
        partial_rotary_factor=float(full["partial_rotary_factor"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_seq_len=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        sliding_rope_theta=float(sliding["rope_theta"]),
        sliding_partial_rotary_factor=float(
            sliding["partial_rotary_factor"]),
        dense_hidden_dim=cfg["intermediate_size"],
        hidden_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=True, router="sigmoid",
        n_shared_experts=(cfg["shared_expert_intermediate_size"]
                          // cfg["moe_intermediate_size"]),
        routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype,
        param_dtype=dtype, tie_word_embeddings=False)


def model(pcfg):
    from ray_tpu.models.laguna import Laguna
    return Laguna(pcfg)


def _std_of(name: str, leaf):
    if leaf.ndim == 1:
        return None                        # every norm's scale: ones
    if "tok_embeddings" in name or "lm_head" in name:
        return 0.02                        # the model's own
    # 1/sqrt(fan_in); an expert tensor is [E, in, out]. The router's
    # too: its logits then have a standard deviation of 1, and which
    # experts are the 8 largest does not depend on the scale
    return leaf.shape[-2] ** -0.5


def init_params(shapes, seed: int, shardings=None):
    """The ``params`` collection only: the model's ``init`` makes no
    other."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    return weights.seeded_normal(only(shapes), seed, _std_of,
                                 only(shardings))


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names: the
    program's own arrays (the reference upcasts a layer's at a time,
    and a layer's experts a block at a time)."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a = lp["attention"]
        w = {"attn_norm": lp["attention_norm"]["scale"],
             "ffn_norm": lp["ffn_norm"]["scale"],
             "wq": a["wq"]["kernel"], "wk": a["wk"]["kernel"],
             "wv": a["wv"]["kernel"], "wg": a["wg"]["kernel"],
             "wo": a["wo"]["kernel"]}
        if "moe" in lp:
            m = lp["moe"]
            w.update(router=m["router"], w_gate=m["w1"], w_up=m["w3"],
                     w_down=m["w2"], shared_gate=m["shared_w1"],
                     shared_up=m["shared_w3"], shared_down=m["shared_w2"])
        else:
            f = lp["feed_forward"]
            w.update(ffn_gate=f["w1"]["kernel"], ffn_up=f["w3"]["kernel"],
                     ffn_down=f["w2"]["kernel"])
        layers.append(w)
    return {"embed": p["tok_embeddings"], "head": p["lm_head"],
            "norm": p["norm"]["scale"], "layers": layers}


def _sizes(pcfg) -> Dict[str, Any]:
    """The plain reference's keyword arguments for this model."""
    types = tuple(pcfg.layer_types[:pcfg.n_layers])
    return dict(
        head_dim=pcfg.head_dim, n_kv_heads=pcfg.n_kv_heads,
        eps=pcfg.norm_eps, window=pcfg.sliding_window,
        full_rope=(pcfg.rope_theta, pcfg.partial_rotary_factor,
                   pcfg.yarn_factor, pcfg.yarn_original_max_seq_len,
                   pcfg.yarn_beta_fast, pcfg.yarn_beta_slow,
                   pcfg.yarn_attention_factor),
        sliding_rope=(pcfg.sliding_rope_theta,
                      pcfg.sliding_partial_rotary_factor),
        top_k=pcfg.num_experts_per_tok,
        scale=pcfg.routed_scaling_factor, layer_types=types,
        heads=dict(zip(types, pcfg.n_heads_per_layer)))


def reference_forward(rw, ids, pcfg, **control):
    """The plain reference's logits [B, T, V] of ids [B, T]."""
    return ref.forward(rw, ids, **{**_sizes(pcfg), **control})


# The generated positions the comparison that decides ``correct`` reads
# are each row's last ``SCORED_TAIL`` (the configurations' ``parity``
# ``new_tokens``: the harness hands ``reference_logits`` the ids without
# saying where the prompt ends; a test ties the two), and of them at
# most ``FLIPPED_SHARE`` may lie more than the tolerance under the
# reference's best. The reference's blocks run over every position, its
# HEAD over those rows alone. See ``reference_logits``; PERF.md section
# 6, PR 53, has the readings the limit lies between.
SCORED_TAIL = 128
FLIPPED_SHARE = 0.48


def reference_logits(rw, ids, pcfg, **control):
    """The logits the comparison that decides ``correct`` is handed
    (parity.margin_rule: the served token's reference logit within
    2**-5 of the logit scale of the best, at every generated position):
    the plain reference's, [B, T, V] with the rows that predict each
    prompt's last ``SCORED_TAIL`` tokens filled and the others zero
    (never read: the rule takes positions P - 1 .. P + G - 2), and with
    a row of zeros (all tokens tie: the position is neither failed nor
    counted decisive) at the generated positions where the served token
    lies MORE than that tolerance under the best, as long as those are
    at most ``FLIPPED_SHARE`` of the generated positions. Where they
    are more, nothing is excused and the rule fails on them.

    Why a share (families/kimi_linear.py has the rule and its argument,
    families/mellum2.py its second use; this is this family's copy).
    Each of the four mixture layers routes 8 of 256 experts with
    renormalised gates: 1,024 candidates a position, and where the 8th
    and 9th scores lie within bfloat16's rounding of each other the
    served path and the float32 reference choose different experts,
    both right answers of the architecture at that precision, an eighth
    of a layer's routed output (times 2.5) apart; the flip moves later
    layers' choices and, through the keys it wrote, later positions'.
    Here the flips are MANY and each is LARGE: 256 scores a layer lie
    ten times denser at the 8th-to-9th boundary than 64 do, and a
    sigmoid router's chosen scores are all near one, so the
    renormalised gates are near 2.5 / 8 each and a flipped choice swaps
    an eighth of the routed output whole (Mellum 2's softmax gates span
    1-40 % and its flips happen at the small end). On the chip the
    served path misses the tolerance at 20.3-29.3 % of 256 generated
    positions over fourteen seeds (all but one under 25.5), and the
    model's own cache-less bfloat16
    forward pass (no kernel, no cache) teacher-forced on the same ids
    at 19.5-19.9 % where the served path reads 21.1-21.9 %: the
    precision's reading, not the paged path's. The reference with every
    matrix in float8 e4m3 reads 78.9-82.4 % over eight of those seeds,
    one rope base 75 %, unscaled gates 88 %, no gate, swapped head
    groups, the whole head rotated and no shared expert 99.6-100 % (my
    chip runs, PR 53). The limit, 48 %, is the geometric middle of 29.3
    and 78.9: 1.64 times of room above the served path's largest
    reading and below float8's smallest. The window one key short reads
    as the served path does here (22-25 %): tier-1 holds it in float32, where
    nothing flips. The tolerance is the harness's, unchanged, and is
    taken over the positions that stay scored, as the rule itself takes
    it."""
    sizes = {**_sizes(pcfg), **control}
    x = ref.hidden(rw, ids, **sizes)
    ids = np.asarray(ids)
    T = ids.shape[1]
    G = min(SCORED_TAIL, T - 1)
    window = ref.head(rw, x[:, T - 1 - G:T - 1], eps=sizes["eps"],
                      lower_precision=sizes.get("lower_precision", False))
    served = np.take_along_axis(window, ids[:, T - G:, None], -1)[..., 0]
    deficit = window.max(-1) - served
    flipped = np.zeros(deficit.shape, bool)
    while True:
        scale = float(np.abs(window[~flipped]).max()) if (
            ~flipped).any() else 0.0
        now = flipped | (deficit > 2.0 ** -5 * scale)
        if (now == flipped).all():
            break
        flipped = now
    share = float(flipped.mean())
    excused = share <= FLIPPED_SHARE
    common.log(f"[correct] laguna: at {int(flipped.sum())} of "
               f"{flipped.size} generated positions ({100 * share:.1f} %; "
               f"limit {100 * FLIPPED_SHARE:.1f} %) the served token lies "
               f"more than the tolerance {2.0 ** -5 * scale:.4f} under the "
               f"reference's best (worst {float(deficit.max()):.4f}): "
               + ("a choice of experts flipped there or before; not "
                  "scored" if excused else "too many for flipped choices: "
                  "scored as they are"))
    if excused:
        window[flipped] = 0.0
    logits = np.zeros((ids.shape[0], T, window.shape[-1]), np.float32)
    logits[:, T - 1 - G:T - 1] = window
    return logits


# ---------------------------------------------------------- byte counts

def sliding_step_flops(cfg: Dict[str, Any], keys: float) -> float:
    """FLOPs of ONE sliding layer's attention of one decode step over
    ``keys``: every SLIDING query head's score and its read-out."""
    return 2.0 * 2 * heads_by_type(cfg)[SLIDING] * cfg["head_dim"] * keys


def full_step_flops(cfg: Dict[str, Any], context_tokens: float) -> float:
    """The same of ONE full layer over the riders' contexts."""
    return (2.0 * 2 * heads_by_type(cfg)[FULL] * cfg["head_dim"]
            * context_tokens)


def attention_weights(cfg: Dict[str, Any], layer_type: str) -> int:
    """One layer's parameters outside its feed-forward: the four
    projections by ITS query heads, and its gate."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    H = heads_by_type(cfg)[layer_type]
    return 2 * D * d * (H + cfg["num_key_value_heads"]) + D * H


def attention_weight_bytes(cfg: Dict[str, Any], layer_type: str,
                           itemsize: int = costs.BF16) -> int:
    return attention_weights(cfg, layer_type) * itemsize


def shared_expert_bytes(cfg: Dict[str, Any],
                        itemsize: int = costs.BF16) -> int:
    return (3 * cfg["hidden_size"]
            * cfg["shared_expert_intermediate_size"] * itemsize)


def dense_ffn_bytes(cfg: Dict[str, Any],
                    itemsize: int = costs.BF16) -> int:
    """A dense layer's SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] * itemsize


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: float, itemsize: int = costs.BF16,
                      experts_touched: float = None,
                      sliding_keys: float = None) -> float:
    """Bytes ONE decode step of the whole batch must move: each layer's
    projections and gate by its type's query heads, the dense layers'
    SwiGLU, a mixture layer's float32 router, its shared expert and the
    experts a step really touched (``experts_touched`` a mixture layer,
    from the program's counters; the most ``slots`` rows can touch
    where the caller has none: an UPPER bound) with each routed pair's
    row in and out, the full layers' K/V of the tokens in context and
    the step's own writes, the sliding layers' of each rider's window
    (``sliding_keys``: the riders' contexts cut at the window, summed,
    from the program's counters; at most a window a slot where the
    caller has none), the head and an embedding row a slot."""
    D, E = cfg["hidden_size"], cfg["num_experts"]
    attention = sum(n * attention_weight_bytes(cfg, t, itemsize)
                    for t, n in ((FULL, n_full_layers(cfg)),
                                 (SLIDING, n_sliding_layers(cfg))))
    pairs = slots * cfg["num_experts_per_tok"]
    if experts_touched is None:
        experts_touched = min(E, pairs)
    ffn = (n_dense_layers(cfg) * dense_ffn_bytes(cfg, itemsize)
           + n_moe_layers(cfg) * (
               experts_step_bytes(cfg, experts_touched, pairs, itemsize)
               + shared_expert_bytes(cfg, itemsize) + D * E * 4))
    kv = (context_tokens + slots) * kv_bytes_per_token(cfg, itemsize)
    if sliding_keys is None:
        sliding_keys = min(context_tokens, slots * cfg["sliding_window"])
    window = n_sliding_layers(cfg) * sliding_step_bytes(
        cfg, sliding_keys, itemsize)
    head = cfg["vocab_size"] * D * itemsize + slots * D * itemsize
    return float(attention + ffn + kv + window + head)


def decode_step_flops(cfg: Dict[str, Any], context_tokens: float,
                      slots: float, sliding_keys: float = None) -> float:
    """FLOPs of the same step: two a weight a rider in every matrix a
    rider passes (its 8 experts and the shared one of a mixture layer,
    the router, the dense layer, the projections and gate by layer
    type, the head) and each layer type's scores and read-outs."""
    D = cfg["hidden_size"]
    if sliding_keys is None:
        sliding_keys = min(context_tokens, slots * cfg["sliding_window"])
    per_rider = (
        n_full_layers(cfg) * attention_weights(cfg, FULL)
        + n_sliding_layers(cfg) * attention_weights(cfg, SLIDING)
        + n_dense_layers(cfg) * 3 * D * cfg["intermediate_size"]
        + n_moe_layers(cfg) * (
            3 * D * (cfg["num_experts_per_tok"]
                     * cfg["moe_intermediate_size"]
                     + cfg["shared_expert_intermediate_size"])
            + D * cfg["num_experts"])
        + cfg["vocab_size"] * D)
    return (2.0 * slots * per_rider
            + n_full_layers(cfg) * full_step_flops(cfg, context_tokens)
            + n_sliding_layers(cfg) * sliding_step_flops(cfg,
                                                         sliding_keys))


# ---------------------------------------------------------- trace parts

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
GATE = "attn_gate"
# the parts of a layer's attention by its type: the named parts inside
# the outer scope, then the outer scope for what is left under it (and,
# for the sliding layers, ``RING_COPIES`` below, which carries no
# scope). The gate is a part of its own, looked for first: it lies
# inside either type's scope.
SLIDING_PARTS = ("ring_append", "ring_scores", "ring_pv", "attn_sliding",
                 "ring_copies")
FULL_PARTS = ("kv_append", "kv_gather", "attn_scores", "attn_pv",
              "attn_full")

parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": (GATE,) + SLIDING_PARTS[:4] + FULL_PARTS,
    "dense": (*((s, (s,)) for s in MOE_SCOPES),
              ("moe_shared", ("moe_shared",)),
              ("moe", ("moe", "moe_stats")),
              ("projections", ("wq", "wk", "wv", "wo")),
              ("mlp", ("feed_forward",)),
              ("norms", ("attention_norm", "ffn_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention",))),
}

# Whole-ring operations that carry NO scope of the table: where the
# sliding layers run as ``ring_append`` + ``ring_attention`` the chip's
# compiler moves a ring through its fast memory with asynchronous
# copies every step (families/mellum2.py ``RING_COPIES`` has the
# story). They are found by what they ARE and what they move: an
# operation that the table sorts under ``unnamed`` or ``other``, whose
# opcode is a copy's, and whose result is a whole ring or a quarter of
# one. The decode loop's ``while`` names a ring's shape first too and
# is no copy. Under the ring kernel (PR 52) the rings are pinned to HBM
# and this part reads 0.
RING_COPIES = "ring_copies"
COPY_OPCODES = ("copy", "copy-start", "copy-done", "slice-start",
                "slice-done")
_UNSORTED = ("unnamed", "other")


def _ring_shapes(cfg: Dict[str, Any]):
    """A whole ring as the trace names it, and a quarter of one (the
    compiler splits a ring's copy in four)."""
    dep = cfg["deployment"]
    dims = (dep["max_slots"], cfg["num_key_value_heads"], ring_len(cfg),
            cfg["head_dim"])
    whole = int(np.prod(dims))
    return {whole, whole // 4}


def _elements(shape: str) -> int:
    """Elements of ``bf16[128,8,832,128]``; 0 for a shape without
    dimensions."""
    inside = shape.partition("[")[2].rstrip("]")
    return int(np.prod([int(d) for d in inside.split(",")])) if inside \
        else 0


def is_ring_copy(cfg: Dict[str, Any], op_name: str, sizes=None) -> bool:
    """``sizes``: ``_ring_shapes(cfg)``, where the caller asks of many
    operations."""
    if "copy" not in op_name and "slice" not in op_name:
        return False
    opcode, shape = trace_reduce.op_kind(op_name.lstrip("!?"))
    return opcode in COPY_OPCODES and _elements(shape) in (
        sizes or _ring_shapes(cfg))


def _with_ring_copies(run, got, spans):
    """``got`` (a split over ``spans``, a program's executions: [name,
    start, duration]) with the whole-ring copies that the table left
    unsorted (``RING_COPIES`` above) taken out of ``unnamed`` and
    ``other`` and made a part of their own."""
    spans = sorted((s, s + d) for _n, s, d in spans)
    sizes = _ring_shapes(run.cfg)
    moved = dict.fromkeys(_UNSORTED, 0.0)
    i = 0
    for name, start, dur, tf_op in sorted(run._trace_parts["ir"]["ops"],
                                          key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if (i < len(spans) and spans[i][0] <= start
                and is_ring_copy(run.cfg, name, sizes)):
            part = trace_parts.part_of(tf_op, parts)
            if part in moved:
                moved[part] += dur / 1e9
    parts_ = dict(got["parts"])
    parts_[RING_COPIES] = 0.0
    for part, took in moved.items():
        took = min(took, parts_.get(part, 0.0))
        parts_[part] = parts_.get(part, 0.0) - took
        parts_[RING_COPIES] += took
    return dict(got, parts=parts_)


def typed_parts(run, module: str):
    """``trace_parts.for_run`` of ``module`` where the program names
    the two layer types' scopes, with the unnamed whole-ring copies as
    the part ``ring_copies``; None without a trace or on a program
    that names neither."""
    got = trace_parts.for_run(run, module)
    if not got or not any(got["parts"].get(s)
                          for s in ("attn_sliding", "attn_full",
                                    "ring_scores")):
        return None
    spans = [m for m in run._trace_parts["ir"]["modules"]
             if trace_reduce.module_name(m[0]) == module]
    return _with_ring_copies(run, got, spans)


def decode_counters(run) -> Optional[Dict[str, float]]:
    """The mixture's counters of the DECODE steps, a mixture layer-step
    (the ``round`` events' moe_decode_experts_touched, moe_decode_pairs
    over moe_decode_layer_steps), over the traced seconds or, where
    those hold none, the window; None without them."""
    spans = [run.window]
    if getattr(run, "trace_span", None) and None not in run.trace_span:
        spans.insert(0, run.trace_span)
    for t0, t1 in spans:
        touched = pairs = layer_steps = 0
        for e in run.events:
            if e[2] == "round" and t0 <= e[1] < t1:
                touched += e[5].get("moe_decode_experts_touched", 0)
                pairs += e[5].get("moe_decode_pairs", 0)
                layer_steps += e[5].get("moe_decode_layer_steps", 0)
        if layer_steps:
            return {"experts_touched": touched / layer_steps,
                    "pairs": pairs / layer_steps,
                    "layer_steps": layer_steps}
    return None


def decode_parts_by_rounds(run) -> Optional[Dict[str, Any]]:
    """``jit_decode``'s self time by part over EXACTLY the executions
    that benchmarks/trace_dispatch.py matched to their rounds, with the
    engine's own count of what those rounds dispatched
    (families/mellum2.py's join, over this family's table of parts):
    {"parts": {part: s}, "module_s", "steps", "riders" (a step's mean),
    "context_tokens" (a step's mean of the riders' own contexts),
    "sliding_keys" (a step's mean of the riders' contexts cut at the
    window), "rounds"}. None without a joined trace, or where the spans
    and the rows disagree in number."""
    if hasattr(run, "_laguna_decode_parts"):
        return run._laguna_decode_parts
    run._laguna_decode_parts = None
    got = trace_dispatch.joined(run)
    if not got or not trace_parts.for_run(run, "jit_decode"):
        return None
    rows = [r for r in got["rows"]
            if r["program"] == "jit_decode" and r["steps"]]
    ir = run._trace_parts["ir"]
    mods = sorted(ir["modules"], key=lambda m: m[1])
    spans = [m for m in mods[:-1]
             if trace_reduce.module_name(m[0]) == "jit_decode"]
    steps = sum(r["steps"] for r in rows)
    if not steps or len(spans) != len(rows):
        return None
    split = _with_ring_copies(run, trace_parts.split(
        {"ops": ir["ops"], "modules": spans}, "jit_decode", parts), spans)
    by_round = got["by_round"]
    riders = tokens = keys = 0.0
    for r in rows:
        d, n = by_round[r["round"]], r["steps"]
        riders += d.get("decode_riders", 0) * n
        tokens += (d.get("decode_context_tokens", 0)
                   - d.get("decode_riders", 0) * (n - 1) / 2.0) * n
        keys += d.get("decode_sliding_keys", 0) * n
    run._laguna_decode_parts = {
        "parts": split["parts"], "module_s": split["module_s"],
        "steps": steps, "riders": riders / steps,
        "context_tokens": tokens / steps, "sliding_keys": keys / steps,
        "rounds": [r["round"] for r in rows]}
    kernels = {k: sum(by_round[r["round"]].get(k, 0) for r in rows)
               for k in ("decode_kernel_pages", "sliding_kernel_keys")}
    common.log(f"[laguna] jit_decode over the {len(rows)} matched "
               f"executions: {steps} steps of {riders / steps:.1f} riders, "
               f"{tokens / steps:.0f} context tokens and "
               f"{keys / steps:.0f} sliding keys; their rounds' "
               f"decode_kernel_pages {kernels['decode_kernel_pages']} and "
               f"sliding_kernel_keys {kernels['sliding_kernel_keys']}; a "
               f"step {1e3 * split['module_s'] / steps:.3f} ms: sliding "
               f"{1e3 * sliding_s(split) / steps:.3f}, full "
               f"{1e3 * under(split, FULL_PARTS) / steps:.3f}, gate "
               f"{1e3 * split['parts'].get(GATE, 0.0) / steps:.3f}; "
               + ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in sorted(
                   split["parts"].items(), key=lambda kv: -kv[1])[:16]))
    return run._laguna_decode_parts
