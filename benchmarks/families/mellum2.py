"""The Mellum 2 family (JetBrains/Mellum2-12B-A2.5B-Instruct,
``model_type: mellum``) as the benchmark sees it: a decoder whose
layers keep caches of TWO SIZES: three layers in four attend a sliding
window of 1,024 keys under plain rope, the fourth the whole context
under YaRN; grouped-query heads of an explicit ``head_dim`` (32 over 4
of 128 on a hidden size of 2,304); every layer's feed-forward a mixture
of 64 experts of 896, 8 a token, gates renormalised, no shared expert;
an untied head. The program serves it as ``ray_tpu.models.mellum``;
``program_config`` refuses at once (SystemExit, before a weight is
made) a program that has no such module or whose config lacks a field
the model needs.

A configuration of this family holds EVERY expert and the WHOLE
vocabulary; it is cut in depth alone. ``layer_types`` keeps its
published entries and the first ``num_hidden_layers`` of them are read.

The byte and FLOP counts are BY KIND of layer: a sliding layer-step's
window (``sliding_step_bytes``), a full layer's K/V pages
(``kv_bytes_per_token``), a mixture layer-step's experts
(``experts_step_bytes``), and the readers divide a scope's time by the
layers OF THAT KIND (``n_sliding_layers``, ``n_full_layers``,
``n_moe_layers``) and by the decode steps the engine's own rounds
dispatched (``decode_parts_by_rounds``).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

import numpy as np

from benchmarks import (common, costs, trace_dispatch, trace_parts,
                        trace_reduce, weights)
from benchmarks.reference import mellum2 as ref

SLIDING, FULL = ref.SLIDING, ref.FULL

_NEEDS = ("head_dim", "layer_types", "sliding_window", "yarn_factor",
          "yarn_original_max_seq_len", "yarn_beta_fast", "yarn_beta_slow",
          "yarn_attention_factor", "norm_topk_prob", "router",
          "tie_word_embeddings")


def _types(cfg: Dict[str, Any]):
    """The cut's layers' types: the first ``num_hidden_layers`` of the
    published list."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def n_sliding_layers(cfg: Dict[str, Any]) -> int:
    return _types(cfg).count(SLIDING)


def n_full_layers(cfg: Dict[str, Any]) -> int:
    return _types(cfg).count(FULL)


def n_moe_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"]


def program_config(cfg: Dict[str, Any]):
    """MellumConfig from the published key names."""
    import jax.numpy as jnp
    try:
        from ray_tpu.models.mellum import MellumConfig
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program cannot express "
                         f"Mellum 2: it has no ray_tpu.models.mellum "
                         f"({e})")
    have = {f.name for f in dataclasses.fields(MellumConfig)}
    lacks = [n for n in _NEEDS if n not in have]
    if lacks:
        raise SystemExit(f"benchmarks: the program's MellumConfig cannot "
                         f"express Mellum 2: it has no {lacks}")
    rp = cfg["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    refused = {
        "attention_bias": cfg["attention_bias"],
        "tie_word_embeddings": cfg["tie_word_embeddings"],
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "use_sliding_window false": not cfg["use_sliding_window"],
        "a dense layer (mlp_layer_types)":
            set(cfg["mlp_layer_types"]) != {"sparse"},
        "norm_topk_prob false": not cfg["norm_topk_prob"],
        "full_attention rope_type other than yarn":
            full["rope_type"] != "yarn",
        "sliding_attention rope_type other than default":
            sliding["rope_type"] != "default",
        "two rope_theta": full["rope_theta"] != sliding["rope_theta"],
        "layer_types shorter than the depth":
            len(cfg["layer_types"]) < cfg["num_hidden_layers"],
    }
    if any(refused.values()):
        raise SystemExit(f"benchmarks: the program's Mellum 2 has no "
                         f"{[k for k, v in refused.items() if v]}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return MellumConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"]),
        sliding_window=cfg["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_seq_len=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        hidden_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=True, router="softmax",
        norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype,
        param_dtype=dtype, tie_word_embeddings=False)


def model(pcfg):
    from ray_tpu.models.mellum import Mellum
    return Mellum(pcfg)


# The standard deviation a router's logits are given (its weights are
# this over sqrt(fan_in)): see ``reference_logits``.
ROUTER_LOGIT_STD = 3.0


def _std_of(name: str, leaf):
    if leaf.ndim == 1:
        return None                        # every norm's scale: ones
    if "tok_embeddings" in name or "lm_head" in name:
        return 0.02                        # the model's own
    # 1/sqrt(fan_in); an expert tensor is [E, in, out]
    scale = leaf.shape[-2] ** -0.5
    return ROUTER_LOGIT_STD * scale if "router" in name else scale


def init_params(shapes, seed: int, shardings=None):
    """The ``params`` collection only: the model's ``init`` also sows
    its load-balance losses, which are no weights."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    return weights.seeded_normal(only(shapes), seed, _std_of,
                                 only(shardings))


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names: the
    program's own arrays (the reference upcasts a layer's at a time)."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a, m = lp["attention"], lp["moe"]
        layers.append({
            "attn_norm": lp["attention_norm"]["scale"],
            "ffn_norm": lp["ffn_norm"]["scale"],
            "wq": a["wq"]["kernel"], "wk": a["wk"]["kernel"],
            "wv": a["wv"]["kernel"], "wo": a["wo"]["kernel"],
            "router": m["router"], "w_gate": m["w1"], "w_up": m["w3"],
            "w_down": m["w2"]})
    return {"embed": p["tok_embeddings"], "head": p["lm_head"],
            "norm": p["norm"]["scale"], "layers": layers}


def _sizes(pcfg) -> Dict[str, Any]:
    """The plain reference's keyword arguments for this model."""
    return dict(
        n_heads=pcfg.n_heads, n_kv_heads=pcfg.n_kv_heads,
        eps=pcfg.norm_eps, theta=pcfg.rope_theta,
        window=pcfg.sliding_window,
        yarn=(pcfg.yarn_factor, pcfg.yarn_original_max_seq_len,
              pcfg.yarn_beta_fast, pcfg.yarn_beta_slow,
              pcfg.yarn_attention_factor),
        top_k=pcfg.num_experts_per_tok,
        layer_types=tuple(pcfg.layer_types[:pcfg.n_layers]))


def reference_forward(rw, ids, pcfg, **control):
    """The plain reference's logits [B, T, V] of ids [B, T]."""
    return ref.forward(rw, ids, **{**_sizes(pcfg), **control})


# The generated positions the comparison that decides ``correct`` reads
# are each row's last ``SCORED_TAIL`` (the configurations' ``parity``
# ``new_tokens``: the harness hands ``reference_logits`` the ids without
# saying where the prompt ends; a test ties the two), and of them at
# most ``FLIPPED_SHARE`` may lie more than the tolerance under the
# reference's best. The reference's blocks run over every position, its
# HEAD over those rows alone: at the cell's parity the logits of all
# 2 x 8,448 positions over 98,304 tokens are 6.6 GB of float32 that
# nothing reads. See ``reference_logits``; PERF.md section 6, PR 42, has
# the readings the limit lies between.
SCORED_TAIL = 128
FLIPPED_SHARE = 0.10


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

    Why a share (families/kimi_linear.py has the rule and its argument;
    this is this family's copy). Every layer routes 8 of 64 experts
    with renormalised gates: 512 candidates a position, and where the
    8th and 9th lie within bfloat16's rounding of each other the served
    path and the float32 reference choose different experts, both right
    answers of the architecture at that precision, an eighth of a
    layer's output apart; the flip moves later layers' choices and,
    through the keys it wrote, later positions'. On the chip the served
    path so misses the tolerance at 0-2 % of 256 generated positions
    with routers of 0.02 (OLMoE's scale, ISSUE 42's) and the plain rule
    read ``correct`` FALSE in the cell's first run; but there the
    controls hide in the same noise (the window one key short 2-9 %,
    float8 4-21 %). With the routers' logits at a standard deviation of
    3 (``ROUTER_LOGIT_STD``: the gates of the 8 chosen then span 1-40 %
    as a trained router's do, where 0.02 gives 7-22 %) the mixture
    amplifies every real difference: over 4 seeds x 256 positions the
    served path reads 3.1-4.3 %, the window one key short 21-29 %, the
    full layers without YaRN 25-34 %, float8 45-53 %, a sliding layer
    attended as a full one 98-100 % (my chip runs, PR 42). The limit,
    10 %, has twice the room on either side. The tolerance is the
    harness's, unchanged, and is taken over the positions that stay
    scored, as the rule itself takes it."""
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
    common.log(f"[correct] mellum2: at {int(flipped.sum())} of "
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

def key_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One position's key AND value in ONE layer, every KV head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def kv_bytes_per_token(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """What one token of context costs the POOL: K and V in the FULL
    layers; the sliding layers keep nothing a token."""
    return n_full_layers(cfg) * key_bytes(cfg, itemsize)


def ring_len(cfg: Dict[str, Any]) -> int:
    """Positions a sliding layer's ring keeps a slot under this
    configuration's deployment (models/kv_cache.py
    ``sliding_ring_len``'s rule, by hand): the window and one prefill
    chunk in whole pages, and one page more."""
    dep = cfg["deployment"]
    page = dep["page_size"]
    span = cfg["sliding_window"] + (dep.get("prefill_chunk") or 256)
    return -(-span // page) * page + page


def state_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One slot's ring in ONE sliding layer, whatever its context."""
    return ring_len(cfg) * key_bytes(cfg, itemsize)


def sliding_bytes_per_slot(cfg: Dict[str, Any]) -> int:
    """One slot's rings over the sliding layers."""
    return n_sliding_layers(cfg) * state_bytes(cfg)


def sliding_step_bytes(cfg: Dict[str, Any], keys: float,
                       itemsize: int = costs.BF16) -> float:
    """Bytes ONE sliding layer's decode step MUST move for its cache:
    ``keys`` = the riders' min(context, window) summed, each key and
    value read once."""
    return keys * key_bytes(cfg, itemsize)


def sliding_step_flops(cfg: Dict[str, Any], keys: float) -> float:
    """FLOPs of ONE sliding layer's attention of one decode step over
    ``keys``: every head's score and its read-out."""
    return 2.0 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def unaged_bytes(cfg: Dict[str, Any], kv_bytes_in_use: float) -> float:
    """What the sliding layers would hold for the contexts whose FULL
    layers hold ``kv_bytes_in_use`` of pages, did nothing age: the same
    pages a layer."""
    return kv_bytes_in_use * n_sliding_layers(cfg) / n_full_layers(cfg)


def expert_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One expert's three matrices."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * itemsize)


def experts_step_bytes(cfg: Dict[str, Any], experts_touched: float,
                       pairs: float, itemsize: int = costs.BF16) -> float:
    """Bytes the experts' matmuls of ONE layer's step must move: the
    three matrices of each expert touched, once, and each routed pair's
    row in and out. ``experts_touched`` and ``pairs`` are the program's
    counters a layer-step."""
    rows = 2 * pairs * cfg["hidden_size"] * itemsize
    return experts_touched * expert_bytes(cfg, itemsize) + rows


def experts_step_flops(cfg: Dict[str, Any], pairs: float) -> float:
    return (2.0 * 3 * pairs * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def attention_weight_bytes(cfg: Dict[str, Any],
                           itemsize: int = costs.BF16) -> int:
    """One layer's four projections."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * D * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"]) * itemsize


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: int, itemsize: int = costs.BF16,
                      experts_touched: float = None) -> float:
    """Bytes ONE decode step of the whole batch must move: each layer's
    projections and float32 router, the experts a step really touched
    (``experts_touched`` a layer, from the program's counters; the most
    ``slots`` rows can touch where the caller has none: an UPPER
    bound), the full layers' K/V of the tokens in context, the sliding
    layers' of at most a window a slot, the head and an embedding row
    a slot."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    E = cfg["num_experts"]
    if experts_touched is None:
        experts_touched = min(E, slots * cfg["num_experts_per_tok"])
    ffn = L * (experts_touched * expert_bytes(cfg, itemsize) + D * E * 4)
    kv = (context_tokens + slots) * kv_bytes_per_token(cfg, itemsize)
    window = n_sliding_layers(cfg) * sliding_step_bytes(
        cfg, min(context_tokens, slots * cfg["sliding_window"]), itemsize)
    head = cfg["vocab_size"] * D * itemsize + slots * D * itemsize
    return float(L * attention_weight_bytes(cfg, itemsize) + ffn + kv
                 + window + head)


# ---------------------------------------------------------- trace parts

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
# the parts of a layer's attention by its type: the named parts inside
# the outer scope, then the outer scope for what is left under it (and,
# for the sliding layers, ``RING_COPIES`` below, which carries no scope)
SLIDING_PARTS = ("ring_append", "ring_scores", "ring_pv", "attn_sliding",
                 "ring_copies")
FULL_PARTS = ("kv_append", "kv_gather", "attn_scores", "attn_pv",
              "attn_full")

# trace_parts.DEFAULT_PARTS with the two layer types' attention as
# parts of their own (an inner scope is looked for before the outer
# one that holds it: trace_parts.part_of takes the first of this list
# that the path names), and the mixture's four scopes before the module
# names that would otherwise claim their operations
parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": SLIDING_PARTS[:4] + FULL_PARTS,
    "dense": (*((s, (s,)) for s in MOE_SCOPES),
              ("moe", ("moe", "moe_stats")),
              ("projections", ("wq", "wk", "wv", "wo")),
              ("norms", ("attention_norm", "ffn_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention",))),
}


# Whole-ring operations that carry NO scope of the table. The chip's
# compiler keeps a ring in its fast memory across decode steps and moves
# it out and back in with asynchronous copies every step (copy-start/
# -done, slice-start/-done: the core waits in the -done), and it
# flattens a prefill call's ring scatter into a fusion of its own; their
# metadata names no ``attn_sliding``, so a split by scope alone reads
# the sliding layers too cheap (0.335 ms a step for 1.28, and a
# roofline share of 118 %, on the first chip run: PERF.md section 6,
# PR 42). They are found by what they move: an operation that the table
# sorts under ``unnamed`` or ``other`` and whose result is a whole ring
# or a quarter of one.
RING_COPIES = "ring_copies"
_UNSORTED = ("unnamed", "other")


def _ring_elements(cfg: Dict[str, Any]):
    whole = (cfg["deployment"]["max_slots"] * cfg["num_key_value_heads"]
             * ring_len(cfg) * cfg["head_dim"])
    return {whole, whole // 4}


def _result_elements(op_name: str) -> int:
    """Elements of the first array shape an operation's name holds (its
    result, or for an asynchronous start its operand): 0 without one."""
    m = re.search(r"(?:bf16|f32)\[([0-9,]+)\]", op_name)
    if not m:
        return 0
    n = 1
    for d in m.group(1).split(","):
        n *= int(d)
    return n


def _with_ring_copies(run, got, spans):
    """``got`` (a split over ``spans``, a program's executions: [name,
    start, duration]) with the whole-ring operations that the table
    left unsorted (``RING_COPIES`` above) taken out of ``unnamed`` and
    ``other`` and made a part of their own."""
    sizes = _ring_elements(run.cfg)
    spans = sorted((s, s + d) for _n, s, d in spans)
    moved = dict.fromkeys(_UNSORTED, 0.0)
    i = 0
    for name, start, dur, tf_op in sorted(run._trace_parts["ir"]["ops"],
                                          key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if (i < len(spans) and spans[i][0] <= start
                and _result_elements(name) in sizes):
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
    the two layer types' scopes, with the unnamed whole-ring operations
    as the part ``ring_copies``; None without a trace or on a program
    that names neither."""
    got = trace_parts.for_run(run, module)
    if not got or not any(got["parts"].get(s)
                          for s in ("attn_sliding", "attn_full",
                                    "ring_scores")):
        return None
    spans = [m for m in run._trace_parts["ir"]["modules"]
             if trace_reduce.module_name(m[0]) == module]
    return _with_ring_copies(run, got, spans)


def under(got, scopes) -> float:
    """Seconds of ``got``'s parts under ``scopes``."""
    return sum(got["parts"].get(s, 0.0) for s in scopes)


def sliding_s(got) -> float:
    """Seconds of ``got`` (a split) in the sliding layers' attention:
    the scoped parts and, with them, the unnamed whole-ring operations;
    0 for a program that names no such scope."""
    scoped = under(got, SLIDING_PARTS[:4])
    return scoped + got["parts"].get(RING_COPIES, 0.0) if scoped else 0.0


def decode_parts_by_rounds(run) -> Optional[Dict[str, Any]]:
    """``jit_decode``'s self time by part over EXACTLY the executions
    that benchmarks/trace_dispatch.py matched to their rounds, with the
    engine's own count of what those rounds dispatched: {"parts": {part:
    s}, "steps", "riders" (a step's mean), "context_tokens" (a step's
    mean of the riders' own contexts, from the ``round`` events'
    ``decode_context_tokens``: the sum after a dispatch's last step,
    less half a step's growth a step before it), "sliding_keys" (a
    step's mean of the riders' contexts cut at the window, from
    ``decode_sliding_keys``: the last step's, which is every step's
    once a context has passed the window), "rounds"}. The join matches
    every execution of the program in order but the chip's last of any
    program (which the stop may have cut), so those are the spans the
    split is made over. None without a joined trace, or where the spans
    and the rows disagree in number. (families/kimi_linear.py has the
    same join for its own table of parts.)"""
    if hasattr(run, "_mellum_decode_parts"):
        return run._mellum_decode_parts
    run._mellum_decode_parts = None
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
    run._mellum_decode_parts = {
        "parts": split["parts"], "module_s": split["module_s"],
        "steps": steps, "riders": riders / steps,
        "context_tokens": tokens / steps, "sliding_keys": keys / steps,
        "rounds": [r["round"] for r in rows]}
    common.log(f"[mellum] jit_decode over the {len(rows)} matched "
               f"executions: {steps} steps of {riders / steps:.1f} riders, "
               f"{tokens / steps:.0f} context tokens and "
               f"{keys / steps:.0f} sliding keys; a step "
               f"{1e3 * split['module_s'] / steps:.3f} ms: sliding "
               f"{1e3 * sliding_s(split) / steps:.3f}, full "
               f"{1e3 * under(split, FULL_PARTS) / steps:.3f}; "
               + ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in sorted(
                   split["parts"].items(), key=lambda kv: -kv[1])[:14]))
    return run._mellum_decode_parts
