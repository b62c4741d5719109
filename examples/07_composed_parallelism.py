"""Composed parallelism: pipeline x sequence x data in ONE train step.

SURVEY §7 step 7 in action: pick mesh axes, hand the stage function to
make_composed_train_step, and the GPipe schedule, ring attention and
the data-parallel gradient sync all compile into a single XLA program
(train/compose.py). On a v4-32 the same code spans hosts — the mesh
comes from ScalingConfig and each process feeds its local batch shard.

Run: python examples/07_composed_parallelism.py
(CPU demo: forces an 8-device virtual mesh.)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.mesh.device_mesh import create_mesh
from ray_tpu.parallel.sequence import ring_attention
from ray_tpu.train.compose import (make_composed_train_step,
                                   put_composed_batch)

mesh = create_mesh({"pipeline": 2, "sequence": 2, "data": 2})
S, D, M = 2, 16, 2


def stage_fn(p, x):                       # one pipeline stage
    h = jax.nn.gelu(jnp.einsum("btd,de->bte", x, p["w"]) + p["b"])
    B, T, Dm = h.shape
    qkv = h.reshape(B, T, 1, Dm)          # ring attention over `sequence`
    a = ring_attention(qkv, qkv, qkv, axis_name="sequence", causal=True)
    return x + h + a.reshape(B, T, Dm)


def loss_fn(out, batch):
    d = (out - batch[1]) ** 2
    return jnp.sum(d), jnp.asarray(d.size, jnp.float32)


rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.randn(S, D, D) * 0.05, jnp.float32),
          "b": jnp.zeros((S, D), jnp.float32)}
step, state = make_composed_train_step(
    stage_fn, loss_fn, optax.adam(3e-3), mesh, params,
    num_microbatches=M)

x = np.asarray(rng.randn(8, 8, D), np.float32)
batch = put_composed_batch((x, x * 0.5 + 0.1), mesh)
for i in range(30):
    state, m = step(state, batch)
    if i % 10 == 0 or i == 29:
        print(f"step {i:3d}  loss {float(m['loss']):.5f}")
print("mesh axes in play:",
      {k: int(v) for k, v in mesh.shape.items() if v > 1})
