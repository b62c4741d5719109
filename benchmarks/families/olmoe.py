"""The OLMoE family (allenai OLMoE-1B-7B: Llama-shaped blocks whose
feed-forward is a mixture of 64 SwiGLU experts, 8 a token) as the
benchmark sees it. The program serves it as ``ray_tpu.models.mixtral``'s
block with three declared differences, each a field of its config, and
``program_config`` refuses a program that lacks one of them:

- the router: softmax over ALL experts in float32, the k largest kept
  as they are (``norm_topk_prob`` false);
- an RMSNorm with a learned scale over the whole projected query and
  key (unconditional in ``modeling_olmoe.py``; the configuration file
  lists it under ``assumed``, for config.json has no key for it);
- an output head of its own (``tie_word_embeddings`` false), which goes
  to the plain reference as the program's own separate matrix, so
  ``correct`` sees it.

The mixture is dropless in the program (sorted pairs through a grouped
matmul) and in the reference (every expert on every token).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmarks import common, costs, trace_parts, weights

# KV pages and the attention's byte counts are the Llama family's
_llama = common.load_family("llama", "serve")

_NEEDS = ("norm_topk_prob", "qk_norm", "tie_word_embeddings")


def program_config(cfg: Dict[str, Any]):
    """MixtralConfig from the published key names (num_experts,
    num_experts_per_tok, intermediate_size = one expert's width,
    norm_topk_prob, tie_word_embeddings)."""
    import jax.numpy as jnp
    from ray_tpu.models.mixtral import MixtralConfig
    have = {f.name for f in dataclasses.fields(MixtralConfig)}
    lacks = [n for n in _NEEDS if n not in have]
    if lacks:
        raise SystemExit(f"benchmarks: the program's MixtralConfig "
                         f"cannot express OLMoE: it has no {lacks}")
    if cfg["hidden_size"] // cfg["num_attention_heads"] != cfg["head_dim"]:
        raise SystemExit("benchmarks: the program derives head_dim as "
                         "hidden_size / heads; this file disagrees")
    if cfg.get("clip_qkv") is not None or cfg.get("attention_bias"):
        raise SystemExit("benchmarks: the program clips no q/k/v and "
                         "has no attention bias")
    if cfg.get("rope_scaling") is not None:
        raise SystemExit("benchmarks: the program scales no rope")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return MixtralConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        hidden_dim=cfg["intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype,
        param_dtype=dtype, norm_topk_prob=bool(cfg["norm_topk_prob"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        qk_norm=True)


def model(pcfg):
    from ray_tpu.models.mixtral import Mixtral
    return Mixtral(pcfg)


def _std_of(name: str, leaf):
    if leaf.ndim == 1:
        return None                        # every norm's scale: ones
    if "tok_embeddings" in name or "lm_head" in name or "router" in name:
        return 0.02                        # the model's own
    # 1/sqrt(fan_in); an expert tensor is [E, in, out]
    return leaf.shape[-2] ** -0.5


def init_params(shapes, seed: int, shardings=None):
    """The ``params`` collection only: the model's ``init`` also sows
    its load-balance losses, which are no weights."""
    only = (lambda tree: None if tree is None
            else {"params": tree["params"]})
    return weights.seeded_normal(only(shapes), seed, _std_of,
                                 only(shardings))


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names. The
    head is the program's own ``lm_head``, not the embedding."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        a, m = lp["attention"], lp["moe"]
        layers.append({**_llama.reference_attention_weights(lp),
                       "q_norm": a["q_norm"]["scale"],
                       "k_norm": a["k_norm"]["scale"],
                       "router": m["router"], "w_gate": m["w1"],
                       "w_up": m["w3"], "w_down": m["w2"]})
    return {"embed": p["tok_embeddings"], "head": p["lm_head"],
            "norm": p["norm"]["scale"], "layers": layers}


def reference_logits(rw, ids, pcfg):
    from benchmarks.reference import olmoe as ref
    return ref.forward(rw, ids, n_heads=pcfg.n_heads,
                       n_kv_heads=pcfg.n_kv_heads, eps=pcfg.norm_eps,
                       theta=pcfg.rope_theta,
                       top_k=pcfg.num_experts_per_tok)


kv_bytes_per_token = _llama.kv_bytes_per_token


# ---------------------------------------------------------- byte counts

def expert_bytes(cfg: Dict[str, Any], itemsize: int = costs.BF16) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] * itemsize


def experts_step_bytes(cfg: Dict[str, Any], experts_touched: float,
                       pairs: float, itemsize: int = costs.BF16) -> float:
    """Bytes the experts' matmuls of ONE layer's step must move: the
    three matrices of each expert touched, once, and each routed pair's
    row in and out (hidden in, hidden out; the 2 x expert-width
    intermediates stay on the chip). ``experts_touched`` and ``pairs``
    are what the program's counters say, a layer-step."""
    rows = 2 * pairs * cfg["hidden_size"] * itemsize
    return experts_touched * expert_bytes(cfg, itemsize) + rows


def experts_step_flops(cfg: Dict[str, Any], pairs: float) -> float:
    """FLOPs of one layer's expert matmuls over ``pairs`` routed rows."""
    return 2.0 * 3 * pairs * cfg["hidden_size"] * cfg["intermediate_size"]


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: int, itemsize: int = costs.BF16,
                      experts_touched: float = None) -> float:
    """As the Llama family's, with each layer's feed-forward replaced
    by the float32 router and by the experts a step really touched:
    ``experts_touched`` a layer, from the program's counters
    (``moe_decode_experts_touched`` / ``moe_decode_layer_steps``).
    Where the caller has no counter (decode_roofline's reader passes
    none, and no OLMoE cell lists that metric) the count is the most
    ``slots`` rows can touch, min(E, slots x k): an UPPER bound, so a
    share over it may pass 100 % and must not be reported."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    if experts_touched is None:
        experts_touched = min(E, slots * k)
    dense_ffn = 3 * D * F * itemsize       # what the Llama count holds
    ffn = experts_touched * expert_bytes(cfg, itemsize) + D * E * 4
    qk_norm = (cfg["num_attention_heads"] + cfg["num_key_value_heads"]
               ) * cfg["head_dim"] * 4
    return (_llama.decode_step_bytes(cfg, context_tokens, slots, itemsize)
            + cfg["num_hidden_layers"] * (ffn - dense_ffn + qk_norm))


# ---------------------------------------------------------- trace parts

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")

# trace_parts.DEFAULT_PARTS with the mixture's four scopes as parts of
# their own (before the module's name, which keeps what is left of it
# and the engine's reduction of the routing), and the query/key norms
# with the norms, not with rope
parts = {
    "wrapped": trace_parts.DEFAULT_PARTS["wrapped"],
    "attention": trace_parts.DEFAULT_PARTS["attention"],
    "dense": (*((s, (s,)) for s in MOE_SCOPES),
              ("moe", ("moe", "moe_stats")),
              ("projections", ("wq", "wk", "wv", "wo")),
              ("norms", ("attention_norm", "ffn_norm", "norm", "q_norm",
                         "k_norm")),
              ("head", ("head",)),
              ("sample", ("sample",)),
              ("rope", ("attention",))),
}
