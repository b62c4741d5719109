"""ResNet-50 image train/predict throughput on the local TPU chip.

Targets the reference's own headline image rows
(/root/reference/doc/source/ray-air/benchmarks.rst):
  - GPU image training: 746.29 img/s on 4x g3.16xlarge (16 GPUs)
  - GPU batch prediction (RN50-class): 183.19 img/s on the same 16 GPUs
Both are measured here on ONE chip with synthetic 224x224x3 data
(bf16 compute, fp32 params/BN, SGD+momentum) and reported per-chip and
against the reference's whole-cluster numbers. Writes IMAGES_r05.json.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REF_TRAIN_IMG_S = 746.29      # 16 GPUs, benchmarks.rst:171-173
REF_PREDICT_IMG_S = 183.19    # 16 GPUs, benchmarks.rst:133-135


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import ResNet, resnet50
    from ray_tpu.models.resnet import ResNetConfig

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    cfg = resnet50() if on_tpu else ResNetConfig(
        stage_sizes=(1, 1, 1, 1), width=16)
    model = ResNet(cfg)
    train_batch = 128 if on_tpu else 4
    pred_batch = 256 if on_tpu else 4
    size = 224 if on_tpu else 64

    rng = np.random.RandomState(0)
    imgs = jnp.asarray(rng.rand(train_batch, size, size, 3),
                       jnp.float32)
    labels = jnp.asarray(rng.randint(0, cfg.num_classes, train_batch))

    variables = jax.jit(
        lambda: model.init(jax.random.PRNGKey(0), imgs[:1],
                           train=False))()
    params = variables["params"]
    batch_stats = variables["batch_stats"]
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)

    def loss_fn(params, batch_stats, x, y):
        logits, new_state = model.apply(
            {"params": params, "batch_stats": batch_stats}, x,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        loss = -jnp.take_along_axis(logp, y[:, None], -1).mean()
        return loss, new_state["batch_stats"]

    @jax.jit
    def train_step(params, batch_stats, opt_state, x, y):
        (loss, batch_stats), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, x, y)
        upd, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, upd), batch_stats, \
            opt_state, loss

    # warmup/compile
    params, batch_stats, opt_state, loss = train_step(
        params, batch_stats, opt_state, imgs, labels)
    jax.block_until_ready(loss)
    n_steps = 20 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, imgs, labels)
    float(loss)
    dt = time.perf_counter() - t0
    train_img_s = train_batch * n_steps / dt

    pimgs = jnp.asarray(rng.rand(pred_batch, size, size, 3),
                        jnp.float32)

    @jax.jit
    def predict(params, batch_stats, x):
        return model.apply(
            {"params": params, "batch_stats": batch_stats}, x,
            train=False).argmax(-1)

    _ = np.asarray(predict(params, batch_stats, pimgs))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        out = predict(params, batch_stats, pimgs)
    np.asarray(out)
    dt = time.perf_counter() - t0
    pred_img_s = pred_batch * n_steps / dt

    result = {
        "model": "resnet50", "image_size": size,
        "device": getattr(dev, "device_kind", "cpu"), "chips": 1,
        "dtype": "bfloat16",
        "train": {
            "images_per_s_per_chip": round(train_img_s, 1),
            "batch": train_batch, "steps": n_steps,
            "reference_images_per_s": REF_TRAIN_IMG_S,
            "reference_hw": "16x GPU (4x g3.16xlarge)",
            "vs_reference_cluster": round(
                train_img_s / REF_TRAIN_IMG_S, 3),
            "vs_reference_per_accelerator": round(
                train_img_s / (REF_TRAIN_IMG_S / 16), 2),
        },
        "predict": {
            "images_per_s_per_chip": round(pred_img_s, 1),
            "batch": pred_batch,
            "reference_images_per_s": REF_PREDICT_IMG_S,
            "reference_hw": "16x GPU (4x g3.16xlarge)",
            "vs_reference_cluster": round(
                pred_img_s / REF_PREDICT_IMG_S, 3),
        },
    }
    print(json.dumps(result, indent=1))
    if on_tpu:
        out_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "IMAGES_r05.json")
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
