"""Operations and bytes computed from shapes: the denominators of every
utilization the benchmark prints. They live here, where a PR that claims
a gain cannot change them. Each takes the configuration FILE's keys (the
published names), not a program object.
"""
from __future__ import annotations

from typing import Any, Dict

BF16 = 2


# ------------------------------------------------------------- GPT-2

def gpt2_param_count(cfg: Dict[str, Any]) -> int:
    """Parameters the 6N rule counts (ray_tpu.models.gpt2
    .flops_per_token's N, copied): token and position embeddings, 12 C^2
    a layer (qkv 3C^2, proj C^2, mlp 8C^2), final norm. Biases and the
    layers' norms are left out, as there."""
    C = cfg["n_embd"]
    return (cfg["vocab_size"] * C + cfg["n_positions"] * C
            + cfg["n_layer"] * 12 * C * C + 2 * C)


def gpt2_train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward + backward FLOPs a token needs: 6 N for the matmuls plus
    12 * layers * C * T for the attention scores and values (PaLM
    appendix B; bench.py's count, copied). Causal masking is NOT
    discounted, recomputation is not counted."""
    return (6.0 * gpt2_param_count(cfg)
            + 12.0 * cfg["n_layer"] * cfg["n_embd"] * seq)


def flash_causal_flops(batch: int, seq: int, heads: int, head_dim: int,
                       backward: bool = True) -> float:
    """FLOPs causal attention NEEDS for one layer: forward QK^T and PV
    are 2 matmuls of 2*B*H*T*T*D each, halved by the causal mask; the
    backward needs 2.5x the forward (dV, dP, dS->dQ, dS->dK, and the
    recomputed QK^T, which the algorithm cannot avoid). tools/
    flash_bench.py's count, copied."""
    fwd = 2 * 2.0 * batch * heads * seq * seq * head_dim / 2.0
    return fwd * (3.5 if backward else 1.0)


def flash_bytes(batch: int, seq: int, heads: int, head_dim: int,
                backward: bool = True, itemsize: int = BF16) -> float:
    """Bytes one layer's attention must move: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv (the
    log-sum-exp rows are T/D smaller and left out)."""
    t = batch * seq * heads * head_dim * itemsize
    return t * (4 + (8 if backward else 0))


# ------------------------------------------------------- Llama decode

def llama_layer_weight_bytes(cfg: Dict[str, Any],
                             itemsize: int = BF16) -> int:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q = D * cfg["num_attention_heads"] * hd
    kv = 2 * D * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * D
    return (q + kv + o + 3 * D * F) * itemsize


def llama_kv_bytes_per_token(cfg: Dict[str, Any],
                             itemsize: int = BF16) -> int:
    """K and V of one token over all layers."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"] * itemsize)


def llama_decode_step_bytes(cfg: Dict[str, Any], context_tokens: float,
                            slots: int, itemsize: int = BF16) -> float:
    """Bytes ONE decode step of the whole batch must move: every layer's
    weights once, the output head once (vocab x hidden, tied or not),
    one embedding row per slot, and the K/V of the tokens really in
    context (``context_tokens``, summed over the batch) read once.
    The step's own K/V writes (one token per slot) are counted too.
    Activations are negligible at T=1 and left out. Whole model: on a
    tensor-parallel mesh divide by the chips for one chip's share."""
    weights = cfg["num_hidden_layers"] * llama_layer_weight_bytes(
        cfg, itemsize)
    head = cfg["vocab_size"] * cfg["hidden_size"] * itemsize
    embed_rows = slots * cfg["hidden_size"] * itemsize
    kv = (context_tokens + slots) * llama_kv_bytes_per_token(
        cfg, itemsize)
    return float(weights + head + embed_rows + kv)
