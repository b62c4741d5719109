"""The Llama family (Llama, Mistral: pre-norm GQA decoder blocks, RoPE,
SwiGLU) as the benchmark sees it: the program's classes, the seeded
weights, the plain reference and the byte counts of every ``kind:
serve`` configuration whose file says ``"family": "llama"``.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmarks import costs, trace_parts, weights


def program_config(cfg: Dict[str, Any]):
    """LlamaConfig from the configuration file's published keys."""
    import jax.numpy as jnp
    from ray_tpu.models.llama import LlamaConfig
    if cfg["hidden_size"] // cfg["num_attention_heads"] != cfg["head_dim"]:
        raise SystemExit("benchmarks: LlamaConfig derives head_dim as "
                         "hidden_size / heads; this file disagrees")
    if cfg.get("sliding_window") is not None:
        raise SystemExit("benchmarks: the program has no sliding window")
    if (not cfg["tie_word_embeddings"] and "tie_word_embeddings"
            not in cfg.get("unsupported_by_program", {})):
        raise SystemExit("benchmarks: the program ties its output head")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        hidden_dim=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype=dtype, param_dtype=dtype)


def model(pcfg):
    from ray_tpu.models.llama import Llama
    return Llama(pcfg)


def _std_of(name: str, leaf):
    if leaf.ndim == 1:
        return None
    return 0.02 if "tok_embeddings" in name else leaf.shape[0] ** -0.5


def init_params(shapes, seed: int, shardings=None):
    """The model's own scales: normal with std 1/sqrt(fan_in) for
    matrices, 0.02 for the embedding, ones for the norms
    (chip_smoke.init_llama's rule, copied)."""
    return weights.seeded_normal(shapes, seed, _std_of, shardings)


def reference_attention_weights(lp) -> Dict[str, Any]:
    """One block's norms and attention under the reference's names."""
    a = lp["attention"]
    return {"attn_norm": lp["attention_norm"]["scale"],
            "wq": a["wq"]["kernel"], "wk": a["wk"]["kernel"],
            "wv": a["wv"]["kernel"], "wo": a["wo"]["kernel"],
            "ffn_norm": lp["ffn_norm"]["scale"]}


def reference_weights(params, pcfg) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names. The
    program has no head of its own, so the reference's (untied) head is
    handed the embedding: ``correct`` cannot see that the published
    model has a separate one (the configuration file's
    ``unsupported_by_program``; PERF.md, Open questions)."""
    p = params["params"]
    layers = []
    for i in range(pcfg.n_layers):
        lp = p[f"layers_{i}"]
        f = lp["feed_forward"]
        layers.append({**reference_attention_weights(lp),
                       "w_gate": f["w1"]["kernel"],
                       "w_up": f["w3"]["kernel"],
                       "w_down": f["w2"]["kernel"]})
    return {"embed": p["tok_embeddings"], "head": p["tok_embeddings"],
            "norm": p["norm"]["scale"], "layers": layers}


def reference_logits(rw, ids, pcfg):
    """ids [B, T] -> the plain float32 reference's logits [B, T, V]."""
    from benchmarks.reference import llama as ref
    return ref.forward(rw, ids, n_heads=pcfg.n_heads,
                       n_kv_heads=pcfg.n_kv_heads, eps=pcfg.norm_eps,
                       theta=pcfg.rope_theta)


# the counts stay the benchmark's, in benchmarks/costs.py
kv_bytes_per_token = costs.llama_kv_bytes_per_token
decode_step_bytes = costs.llama_decode_step_bytes

# the scopes ray_tpu.models.llama.LlamaAttention names around the KV
# window, and flax's names of the modules of a block
parts = trace_parts.DEFAULT_PARTS
