"""The A.X-K1 family (skt/A.X-K1, ``model_type: axk1``) as the benchmark
sees it: a decoder whose every layer is multi-head LATENT attention
(MLA: a low-rank query, one compressed key-value vector and one shared
rope key a token, YaRN positions), whose first layer's feed-forward is
a dense SwiGLU and whose others a sparse mixture with a sigmoid router
(no stored choice bias) and a shared expert. The program serves it as
``ray_tpu.models.axk1``; ``program_config`` refuses at once
(SystemExit, before a weight is made) a program that has no such module
or whose config lacks a field the model needs.

A configuration of this family is ONE CHIP'S SHARE of an
expert-parallel group (model-configs, section 4): ``n_routed_experts``
counts the experts HELD, ``router_width`` the router's published width,
``experts_held_from`` the first held expert. The plain reference
(benchmarks/reference/axk1.py: the EXPANDED form, never the absorbed
one) is handed the same share.

The weights are ``seeded``: every leaf from ``--seed`` and its name,
and nothing else (the routers need no balancing here: PERF.md section
6, PR 34, has the held share by the seed). ``reference_logits`` does
not let the comparison that decides ``correct`` score a position whose
choice of held experts is a near-tie (``NEAR_TIE``); everywhere else
the held experts weigh in the logits at their natural scale.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

from benchmarks import common, costs, trace_parts, weights
from benchmarks.reference import axk1 as ref

_NEEDS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "rope_factor",
          "rope_original_max_seq_len", "first_k_dense",
          "dense_hidden_dim", "n_shared_experts", "router",
          "routed_scaling_factor", "experts_held")


def n_moe_layers(cfg: Dict[str, Any]) -> int:
    return max(0, cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])


def program_config(cfg: Dict[str, Any]):
    """AXK1Config from the published key names."""
    try:
        from ray_tpu.models.axk1 import AXK1Config
    except ImportError as e:
        raise SystemExit(f"benchmarks: the program cannot express "
                         f"A.X-K1: it has no ray_tpu.models.axk1 ({e})")
    have = {f.name for f in dataclasses.fields(AXK1Config)}
    lacks = [n for n in _NEEDS if n not in have]
    if lacks:
        raise SystemExit(f"benchmarks: the program's AXK1Config cannot "
                         f"express A.X-K1: it has no {lacks}")
    from ray_tpu.models.mixtral import ROUTERS
    if "sigmoid" not in ROUTERS:
        raise SystemExit("benchmarks: the program's mixture has no "
                         "sigmoid router without a choice bias")
    rs = cfg["rope_scaling"]
    refused = {
        "attention_bias": cfg["attention_bias"],
        "tie_word_embeddings": cfg["tie_word_embeddings"],
        "rope_scaling.type other than yarn": rs["type"] != "yarn",
        "scoring_func other than sigmoid": cfg["scoring_func"] != "sigmoid",
        "topk_method other than none": cfg["topk_method"] != "none",
        "moe_layer_freq other than 1": cfg["moe_layer_freq"] != 1,
        "hidden_act other than silu": cfg["hidden_act"] != "silu",
        "num_key_value_heads other than the heads":
            cfg["num_key_value_heads"] != cfg["num_attention_heads"],
    }
    if any(refused.values()):
        raise SystemExit(f"benchmarks: the program's A.X-K1 has no "
                         f"{[k for k, v in refused.items() if v]}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return AXK1Config(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max_seq_len=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        first_k_dense=cfg["first_k_dense_replace"],
        dense_hidden_dim=cfg["intermediate_size"],
        hidden_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router="sigmoid",
        experts_held=(cfg["experts_held_from"], cfg["n_routed_experts"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype,
        param_dtype=dtype)


def model(pcfg):
    from ray_tpu.models.axk1 import AXK1
    return AXK1(pcfg)


def _std_of(name: str, leaf):
    if leaf.ndim == 1:
        return None                        # every norm's scale: ones
    if "tok_embeddings" in name:
        # a token's own vector leads its hidden state (the argument of
        # solar-open2-250b-d4-ep8.json's assumed.weights)
        return 1.0
    if "lm_head" in name or "router" in name:
        return 0.02                        # the model's own
    # 1/sqrt(fan_in); an expert tensor is [n, in, out]
    return leaf.shape[-2] ** -0.5


def seeded(shapes, seed: int, shardings=None):
    """Every leaf of ``shapes['params']`` from ``--seed`` and its name
    alone (``_std_of``)."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    return weights.seeded_normal(only(shapes), seed, _std_of,
                                 only(shardings))


def _sizes(pcfg) -> Dict[str, Any]:
    """The plain reference's keyword arguments for this model."""
    return dict(
        n_heads=pcfg.n_heads, nope=pcfg.qk_nope_head_dim,
        rope=pcfg.qk_rope_head_dim, eps=pcfg.norm_eps,
        yarn=(pcfg.rope_theta, pcfg.rope_factor,
              pcfg.rope_original_max_seq_len, pcfg.rope_beta_fast,
              pcfg.rope_beta_slow, pcfg.rope_mscale,
              pcfg.rope_mscale_all_dim),
        top_k=pcfg.num_experts_per_tok,
        lo=(pcfg.experts_held or (0, None))[0],
        norm_topk=pcfg.norm_topk_prob,
        scaling=pcfg.routed_scaling_factor)


def init_params(shapes, seed: int, shardings=None):
    """``shapes``: what ``model(pcfg).init`` gives. The seeded weights
    as they are. (Solar-Open2's family balances its routers' choice
    biases at set-up, because a router of random weights there prefers
    some experts for EVERY token and the held share moved 11.7-12.9 %
    by the seed. This model declares no choice bias, and needs no cure:
    a token's own embedding leads its hidden state and SwiGLU adds no
    component that all tokens share, so the 12 held experts' share of
    the routing reads 6.19-6.34 % by the seed around the even 6.25 %,
    and making the routers' columns orthogonal to the mean router input
    moved no layer's share by more than 0.0007: my chip runs, PR 34.)"""
    return seeded(shapes, seed, shardings)


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a = lp["attention"]
        w = {"attn_norm": lp["attention_norm"]["scale"],
             "ffn_norm": lp["ffn_norm"]["scale"],
             "wq_a": a["wq_a"]["kernel"], "q_norm": a["q_norm"]["scale"],
             "wq_b": a["wq_b"]["kernel"], "wkv_a": a["wkv_a"]["kernel"],
             "kv_norm": a["kv_norm"]["scale"], "wkv_b": a["wkv_b"],
             "wo": a["wo"]["kernel"]}
        if "moe" in lp:
            m = lp["moe"]
            w.update(router=m["router"], w_gate=m["w1"], w_up=m["w3"],
                     w_down=m["w2"], shared_gate=m["shared_w1"],
                     shared_up=m["shared_w3"], shared_down=m["shared_w2"])
        else:
            f = lp["feed_forward"]
            w.update(w_gate=f["w1"]["kernel"], w_up=f["w3"]["kernel"],
                     w_down=f["w2"]["kernel"])
        layers.append(w)
    return {"embed": p["tok_embeddings"], "head": p["lm_head"],
            "norm": p["norm"]["scale"], "layers": layers}


# A position is SCORED by the comparison that decides ``correct`` only
# where no relative error of the hidden state under this changes any
# layer's choice of held experts (reference/axk1.py ``choice_margin``).
# See ``reference_logits``. The limit's two readings (my chip runs, PR
# 34; PERF.md section 6): the served precision flips a held expert's
# choice, against the float32 reference, at 4.0 % of positions, ALL of
# them at margins under 0.0449 (3 seeds x 8,192 positions); the nearest
# precision below (every matrix rounded to float8 e4m3) at 93 % of
# positions, at margins up to 0.5464. About half the positions are then
# not scored; without the rule one seed in three read not correct.
NEAR_TIE = 0.07


def reference_forward(rw, ids, pcfg, margins: bool = False, **control):
    """The plain reference's logits [B, T, V] of ids [B, T]; with
    ``margins`` also each position's least ``choice_margin`` over the
    layers (reference/axk1.py)."""
    return ref.forward(rw, ids, margins=margins, **_sizes(pcfg), **control)


def reference_logits(rw, ids, pcfg):
    """The logits the comparison that decides ``correct`` is handed
    (parity.margin_rule: the served token's reference logit within
    2**-5 of the logit scale of the best, at every position): the plain
    reference's, but a row of zeros at every position whose CHOICE OF
    HELD EXPERTS the reference itself calls a near-tie
    (``choice_margin`` under ``NEAR_TIE`` in some layer). All tokens
    tie there, so the rule neither fails the position nor counts it
    decisive: it is not scored (families/solar_open2.py
    ``reference_logits`` has the argument; this family's own copy of
    the rule, for a router without a bias: the 8th and 9th of 192
    candidates' sigmoids lie closer than bfloat16's rounding of the
    hidden state moves them at some positions, and a chosen held expert
    carries a whole gate of about 2.5 / 8)."""
    logits, margin = reference_forward(rw, ids, pcfg, margins=True)
    unsure = margin < NEAR_TIE
    common.log(f"[correct] axk1: {int(unsure.sum())} of {unsure.size} "
               f"positions are near-ties of the choice of held experts "
               f"(margin under {NEAR_TIE}) and are not scored")
    return np.where(unsure[..., None], np.float32(0.0), logits)


# ---------------------------------------------------------- byte counts

def latent_entry_bytes(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """One token's latent entry in ONE layer: ``[c | k_r]``."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def kv_bytes_per_token(cfg: Dict[str, Any],
                       itemsize: int = costs.BF16) -> int:
    """What one token of context costs the pool: a latent entry a
    layer (no K, no V)."""
    return cfg["num_hidden_layers"] * latent_entry_bytes(cfg, itemsize)


def latent_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      itemsize: int = costs.BF16) -> float:
    """Bytes ONE layer's absorbed attention of one decode step MUST
    move for the cache: each context token's entry read once (keys and
    values are the same entry, so once, not twice)."""
    return context_tokens * latent_entry_bytes(cfg, itemsize)


def latent_step_flops(cfg: Dict[str, Any], context_tokens: float) -> float:
    """FLOPs of ONE layer's absorbed attention of one decode step over
    ``context_tokens`` (summed over the rows): every head's score
    against the whole entry and its read-out of the compressed part."""
    R, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (2.0 * cfg["num_attention_heads"] * ((R + dr) + R)
            * context_tokens)


def mla_weight_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One layer's five attention matrices."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    R, Rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    return (D * Rq + Rq * H * (dn + dr) + D * (R + dr)
            + R * H * (dn + dv) + H * dv * D) * itemsize


def expert_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One expert's three matrices."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * itemsize)


def _moe_layer_share(cfg: Dict[str, Any]) -> float:
    """The readers of the mixture's rooflines divide the scope's time
    by ALL the cut's layers (``num_hidden_layers``), and the first
    ``first_k_dense_replace`` of them have no mixture: what a mixture
    layer moves, as a mean over all the layers."""
    return n_moe_layers(cfg) / cfg["num_hidden_layers"]


def experts_step_bytes(cfg: Dict[str, Any], experts_touched: float,
                       pairs: float, itemsize: int = costs.BF16) -> float:
    """Bytes the HELD experts' matmuls must move a layer-step, AS A
    MEAN OVER ALL THE CUT'S LAYERS (``_moe_layer_share``): the three
    matrices of each held expert touched, once, and each pair's row in
    and out. ``experts_touched`` and ``pairs`` are the program's
    counters a MIXTURE layer-step, which count held experts and the
    pairs that landed on them."""
    rows = 2 * pairs * cfg["hidden_size"] * itemsize
    return _moe_layer_share(cfg) * (
        experts_touched * expert_bytes(cfg, itemsize) + rows)


def experts_step_flops(cfg: Dict[str, Any], pairs: float) -> float:
    return _moe_layer_share(cfg) * (
        2.0 * 3 * pairs * cfg["hidden_size"] * cfg["moe_intermediate_size"])


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: int, itemsize: int = costs.BF16,
                      experts_touched: float = None) -> float:
    """Bytes ONE decode step of the whole batch must move: each layer's
    attention matrices, the dense layers' SwiGLU, the mixture layers'
    router (float32), shared expert and the held experts a step really
    touched (``experts_touched`` a mixture layer, from the program's
    counters; the most ``slots`` rows can touch where the caller has
    none: an UPPER bound, as the OLMoE family's), the latent entries of
    the tokens in context, the head and an embedding row a slot."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    n_moe = n_moe_layers(cfg)
    if experts_touched is None:
        experts_touched = min(cfg["n_routed_experts"],
                              slots * cfg["num_experts_per_tok"])
    dense = (L - n_moe) * 3 * D * cfg["intermediate_size"] * itemsize
    ffn = n_moe * ((experts_touched + cfg["n_shared_experts"])
                   * expert_bytes(cfg, itemsize) + D * cfg["router_width"] * 4)
    kv = (context_tokens + slots) * kv_bytes_per_token(cfg, itemsize)
    head = cfg["vocab_size"] * D * itemsize + slots * D * itemsize
    return float(L * mla_weight_bytes(cfg, itemsize) + dense + ffn + kv
                 + head)


# ---------------------------------------------------------- trace parts

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
MLA_SCOPES = ("mla_q", "mla_kv", "mla_absorb")
# what decode_latent_attn_ms and prefill_attn_share add up: the latent
# attention's own scopes and the shared ones around the page window
LATENT_ATTN_SCOPES = MLA_SCOPES + ("kv_append", "kv_gather",
                                   "attn_scores", "attn_pv")
# what latent_attn_roofline times: the block loop over the pool
LATENT_WINDOW_SCOPES = ("kv_gather", "attn_scores", "attn_pv")



def latent_parts(run, module: str):
    """``trace_parts.for_run`` of ``module`` where the program names
    the latent attention's scopes; None without a trace or on a program
    that names none (the parent, with this family's readers laid over
    it)."""
    got = trace_parts.for_run(run, module)
    if not got or not any(s.startswith("mla_") for s in got["parts"]):
        return None
    return got


def decode_steps_traced(run):
    """Decode steps the traced ``jit_decode`` runs took: a step applies
    the head once, so the executions of the most frequent operation
    under the ``head`` scope inside the program's runs.
    (``trace_reduce.loop_steps`` takes the most frequent count among
    the ten heaviest operations, which in this family's decode are the
    page window's, run once a 512-token BLOCK a layer, 17 a step at
    8.7k tokens of context: it reads 16 times too many steps here.)
    None without the trace's operations or without such an operation."""
    ir = getattr(run, "_trace_parts", {}).get("ir")
    if not ir:
        return None
    from benchmarks import trace_reduce
    spans = sorted((s, s + d) for n, s, d in ir["modules"]
                   if trace_reduce.module_name(n) == "jit_decode")
    counts: Dict[str, int] = {}
    for name, start, _dur, tf_op in ir["ops"]:
        if (trace_parts.part_of(tf_op, parts) == "head"
                and any(s <= start < e for s, e in spans)):
            counts[name] = counts.get(name, 0) + 1
    return float(max(counts.values())) if counts else None


# trace_parts.DEFAULT_PARTS with the latent attention's three scopes
# among attention's, and the mixture's four scopes and its shared
# expert as parts of their own, each before the module names that would
# otherwise claim their operations
parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": LATENT_ATTN_SCOPES,
    "dense": (*((s, (s,)) for s in MOE_SCOPES),
              ("moe_shared", ("moe_shared",)),
              ("moe", ("moe", "moe_stats")),
              ("projections", ("wo",)),
              ("mlp", ("feed_forward",)),
              ("norms", ("attention_norm", "ffn_norm", "norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention",))),
}
