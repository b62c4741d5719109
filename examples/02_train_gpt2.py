"""Train GPT-2 on a device mesh with the SPMD trainer.

Run (real chip or CPU mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/02_train_gpt2.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))      # repo root (run from anywhere)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.mesh import create_mesh
from ray_tpu.models import GPT2, gpt2_sharding_rules
from ray_tpu.models.gpt2 import cross_entropy_loss, gpt2_tiny
from ray_tpu.train.spmd import (TrainState, make_train_step, put_batch,
                                shard_state)

mesh = create_mesh({"data": -1})          # all devices on the data axis
cfg = gpt2_tiny(n_embd=64, n_head=4, n_layer=2, vocab_size=256,
                n_ctx=64)
model = GPT2(cfg)
ids = jnp.zeros((8, 33), jnp.int32)
params = jax.jit(lambda: model.init(jax.random.PRNGKey(0),
                                    ids[:, :-1]))()
optimizer = optax.adamw(3e-4)
state = shard_state(TrainState.create(params, optimizer),
                    gpt2_sharding_rules(), mesh)

def loss_fn(params, batch):
    x, y = batch["ids"][:, :-1], batch["ids"][:, 1:]
    return cross_entropy_loss(model.apply(params, x), y)

step = make_train_step(loss_fn, optimizer)
rng = np.random.RandomState(0)
with jax.set_mesh(mesh):
    for i in range(3):
        batch = put_batch(
            {"ids": rng.randint(0, 256, (8, 33)).astype(np.int32)},
            mesh)
        state, metrics = step(state, batch)
        print(f"step {i}: loss={float(metrics['loss']):.3f}")
