"""Headline benchmark: SPMD training throughput on local TPU chips.

Models (``--model``): ``gpt2`` (default, GPT-2-124M) and
``llama-1.1b`` (TinyLlama-1.1B shape — GQA + SwiGLU, the serving
family's training path). Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

vs_baseline is measured MFU / 0.40 (the north-star target from BASELINE.md:
>=40% MFU for GPT-2 on TPU; the reference has no TPU numbers to compare
against, so the target ratio is the baseline).

One process, one attempt, at the published width: without a TPU, or on
a device whose peak is not in the table below, it fails — there is no
CPU branch and no toy configuration. A number printed here is always a
chip number.
"""
from __future__ import annotations

import json
import sys
import time


# Peak dense bf16 FLOP/s per chip by TPU generation (Google Cloud TPU
# documentation, per-generation system architecture pages).
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_flops(device) -> float:
    kind = device.device_kind
    for k, v in _PEAK_FLOPS.items():
        if kind.startswith(k):
            return v
    raise ValueError(
        f"no peak FLOP/s on record for device kind {kind!r}; add it "
        f"to bench._PEAK_FLOPS with its source")


_METRICS_BY_MODEL = {
    "gpt2": "gpt2_124m_train_tokens_per_sec_per_chip",
    "llama-1.1b": "llama_1_1b_train_tokens_per_sec_per_chip",
}


def _model_arg(argv) -> str:
    if "--model" in argv:
        name = argv[argv.index("--model") + 1]
        if name not in _METRICS_BY_MODEL:
            raise SystemExit(f"unknown --model {name!r} "
                             f"(choices: {sorted(_METRICS_BY_MODEL)})")
        return name
    return "gpt2"


def main(model_name: str = "gpt2"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.mesh import create_mesh
    from ray_tpu.train.spmd import (TrainState, make_train_step,
                                    put_batch, shard_state)
    from ray_tpu.util.compile_cache import enable_compile_cache

    metric = _METRICS_BY_MODEL[model_name]
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench.py measures the chip; JAX found "
                         f"{devices}")
    peak = peak_flops(devices[0])
    n_chips = len(devices)
    enable_compile_cache()

    seq = 1024
    if model_name == "llama-1.1b":
        from ray_tpu.models.llama import (Llama, LlamaConfig,
                                          llama_flops_per_token,
                                          llama_sharding_rules)
        # TinyLlama-1.1B shape: GQA (32q/4kv) + SwiGLU. remat: fp32
        # master params + adam state already cost ~13GB of a v5e's
        # 16GB HBM, so activations must be cheap.
        cfg = LlamaConfig(vocab_size=32000, max_seq_len=seq,
                          dim=2048, n_layers=22, n_heads=32,
                          n_kv_heads=4, hidden_dim=5632,
                          remat=True)
        batch = 8 * n_chips
        model = Llama(cfg)
        rules = llama_sharding_rules(fsdp=True)

        def loss_fn(params, b):
            x, y = b["ids"][:, :-1], b["ids"][:, 1:]
            logits, _ = model.apply(params, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y).mean()

        fpt = llama_flops_per_token(cfg, seq)
    else:
        from ray_tpu.models import GPT2, gpt2_124m, gpt2_sharding_rules
        from ray_tpu.models.gpt2 import (flops_per_token,
                                         linear_cross_entropy)
        # batch 24 + packed flash attention (blk 1024) + lse-gather CE
        # was the per-chip sweet spot of the pre-PR-1 sweeps; not
        # re-measured on the current machine.
        batch = 24 * n_chips
        cfg = gpt2_124m()
        model = GPT2(cfg)
        rules = gpt2_sharding_rules(fsdp=False)

        def loss_fn(params, b):
            x, y = b["ids"][:, :-1], b["ids"][:, 1:]
            feats = model.apply(params, x, return_features=True)
            return linear_cross_entropy(feats, params["params"]["wte"],
                                        y)

        fpt = flops_per_token(cfg, seq)

    mesh = create_mesh({"data": -1}, devices=devices)

    ids = jnp.zeros((batch, seq + 1), dtype=jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[:, :-1])
    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    state = shard_state(TrainState.create(params, optimizer), rules, mesh)

    train_step = make_train_step(loss_fn, optimizer)
    rng = np.random.RandomState(0)
    data = rng.randint(0, cfg.vocab_size, size=(batch, seq + 1),
                       dtype=np.int32)

    # All shardings below are explicit NamedShardings; the ambient
    # mesh is what lets the flash kernel run per shard on several
    # chips (ops/attention.py).
    with jax.set_mesh(mesh):
        b = put_batch({"ids": jnp.asarray(data)}, mesh)
        # warmup / compile
        state, metrics = train_step(state, b)
        jax.block_until_ready(metrics["loss"])

        n_steps = 30
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, metrics = train_step(state, b)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        final_loss = float(metrics["loss"])

    tokens = batch * seq * n_steps
    tok_per_s = tokens / dt
    tok_per_s_chip = tok_per_s / n_chips
    mfu = (tok_per_s_chip * fpt) / peak

    print(json.dumps({
        "metric": metric,
        "value": round(tok_per_s_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "mfu": round(mfu, 4),
        "chips": n_chips,
        "device": devices[0].device_kind,
        "batch": batch,
        "seq": seq,
        "step_time_ms": round(1000 * dt / n_steps, 2),
        "final_loss": round(final_loss, 3),
    }))


if __name__ == "__main__":
    main(_model_arg(sys.argv))
