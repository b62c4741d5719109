"""GPT-2 as the benchmark sees it: the program's classes, the seeded
weights, the loss, the plain reference and the FLOP counts of every
``kind: train`` configuration whose file says ``"family": "gpt2"``.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmarks import costs
from benchmarks.common import jax_key


def program_config(cfg: Dict[str, Any]):
    from ray_tpu.models import gpt2_124m
    if cfg["n_positions"] != cfg["n_ctx"]:
        raise SystemExit("benchmarks: n_positions != n_ctx")
    if cfg["activation_function"] != "gelu_new":
        raise SystemExit("benchmarks: the program's MLP is gelu_new")
    return gpt2_124m(vocab_size=cfg["vocab_size"], n_ctx=cfg["n_ctx"],
                     n_embd=cfg["n_embd"], n_layer=cfg["n_layer"],
                     n_head=cfg["n_head"])


def model(pcfg):
    from ray_tpu.models import GPT2
    return GPT2(pcfg)


def init_params(model, seed: int):
    """Master weights from the model's own initialisers, as bench.py
    and the examples make them; key and ids are arguments. The ids are
    1 x 8: no parameter's shape depends on them, and a 24 x 1024 batch
    makes the init trace the flash kernel for 8 s."""
    import jax
    import jax.numpy as jnp
    ids = jnp.zeros((1, 8), jnp.int32)
    return jax.block_until_ready(
        jax.jit(model.init)(jax_key(seed, 0), ids))


def loss_fn(model):
    """(params, {"ids": [B, T+1]}) -> mean next-token cross-entropy,
    through the program's own fused projection and loss."""
    from ray_tpu.models.gpt2 import linear_cross_entropy

    def loss(params, b):
        x, y = b["ids"][:, :-1], b["ids"][:, 1:]
        feats = model.apply(params, x, return_features=True)
        return linear_cross_entropy(feats, params["params"]["wte"], y)
    return loss


def sharding_rules():
    from ray_tpu.models import gpt2_sharding_rules
    return gpt2_sharding_rules(fsdp=False)


def reference_weights(params, n_layer: int) -> Dict[str, Any]:
    """The program's flax tree under the plain reference's names."""
    p = params["params"]
    layers = []
    for i in range(n_layer):
        h = p[f"h_{i}"]
        layers.append({"ln_1": h["ln_1"], "ln_2": h["ln_2"],
                       "c_attn": h["attn"]["c_attn"],
                       "attn_proj": h["attn"]["c_proj"],
                       "c_fc": h["mlp"]["c_fc"],
                       "mlp_proj": h["mlp"]["c_proj"]})
    return {"wte": p["wte"], "wpe": p["wpe"], "ln_f": p["ln_f"],
            "layers": layers}


def reference_loss_and_grad_norm(params, ids, cfg: Dict[str, Any],
                                 micro_batch: int = 4):
    """Mean loss over ids [B, T+1] and the global norm of its gradient
    by the plain float32 reference, at the PUBLISHED LayerNorm eps."""
    from benchmarks.reference import gpt2 as ref
    return ref.loss_and_grad_norm(
        reference_weights(params, cfg["n_layer"]), ids,
        n_head=cfg["n_head"], eps=float(cfg["layer_norm_epsilon"]),
        micro_batch=micro_batch)


# the count stays the benchmark's, in benchmarks/costs.py
train_flops_per_token = costs.gpt2_train_flops_per_token


def attention_shape(cfg: Dict[str, Any]):
    """(layers, heads, head_dim) of the causal attention the flash
    kernel computes: what flash_roofline needs from the shapes."""
    return (cfg["n_layer"], cfg["n_head"],
            cfg["n_embd"] // cfg["n_head"])
