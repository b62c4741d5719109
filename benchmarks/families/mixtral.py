"""The Mixtral family (Llama blocks whose feed-forward is a top-k routed
mixture of SwiGLU experts; OLMoE is of this shape too) as the benchmark
sees it. A REHEARSAL family so far (PR 27): it came as new files only,
to prove that the seam takes a second family, and runs as the toy
``rehearsal/toy-mixtral.json`` on the CPU. No cell of BENCHMARK.json
names it, and nothing here has run at published widths or on a chip.

What the PR that brings such a cell must still settle: the program
drops tokens over an expert's capacity once a call holds more than 4096
(token, expert) pairs (ray_tpu.models.mixtral.MoEFeedForward), and the
plain reference never drops; ``decode_step_bytes`` counts an upper
bound, not the experts the router really chose.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmarks import common, costs, trace_parts, weights

# attention, KV pages, norms and the head are the Llama family's
_llama = common.load_family("llama", "serve")


def program_config(cfg: Dict[str, Any]):
    """MixtralConfig from the published key names (num_local_experts,
    num_experts_per_tok, intermediate_size = one expert's width)."""
    from ray_tpu.models.mixtral import MixtralConfig
    a = _llama.program_config(cfg)     # its refusals hold here too
    return MixtralConfig(
        vocab_size=a.vocab_size, max_seq_len=a.max_seq_len, dim=a.dim,
        n_layers=a.n_layers, n_heads=a.n_heads, n_kv_heads=a.n_kv_heads,
        hidden_dim=a.hidden_dim, num_experts=cfg["num_local_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        rope_theta=a.rope_theta, norm_eps=a.norm_eps, dtype=a.dtype,
        param_dtype=a.param_dtype)


def model(pcfg):
    from ray_tpu.models.mixtral import Mixtral
    return Mixtral(pcfg)


def _std_of(name: str, leaf):
    if leaf.ndim == 1:
        return None
    if "tok_embeddings" in name or "router" in name:
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
    """The program's flax tree under the plain reference's names; the
    head is the embedding, as in the Llama family."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        m = lp["moe"]
        layers.append({**_llama.reference_attention_weights(lp),
                       "router": m["router"], "w_gate": m["w1"],
                       "w_up": m["w3"], "w_down": m["w2"]})
    return {"embed": p["tok_embeddings"], "head": p["tok_embeddings"],
            "norm": p["norm"]["scale"], "layers": layers}


def reference_logits(rw, ids, pcfg):
    from benchmarks.reference import mixtral as ref
    return ref.forward(rw, ids, n_heads=pcfg.n_heads,
                       n_kv_heads=pcfg.n_kv_heads, eps=pcfg.norm_eps,
                       theta=pcfg.rope_theta,
                       top_k=pcfg.num_experts_per_tok)


kv_bytes_per_token = _llama.kv_bytes_per_token


def decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                      slots: int, itemsize: int = costs.BF16) -> float:
    """As the Llama family's, with each layer's feed-forward replaced by
    the router and by the experts a step can touch: ``slots`` tokens
    choose ``num_experts_per_tok`` each, so at most min(E, slots x k)
    experts stream once. An UPPER bound on what routing needs (a skewed
    router touches fewer): a roofline share over it can pass 100 %, so
    a cell's reader must count the experts really chosen first."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    experts = min(E, slots * k)
    ffn = (experts - 1) * 3 * D * F * itemsize + D * E * 4
    return (_llama.decode_step_bytes(cfg, context_tokens, slots)
            + cfg["num_hidden_layers"] * ffn)


# flax names the mixture's module ``moe``
parts = dict(trace_parts.DEFAULT_PARTS, dense=(
    ("moe", ("moe",)),) + trace_parts.DEFAULT_PARTS["dense"])
