"""Train a tiny T5 on a seq2seq task and greedy-decode, sharded over
an 8-device mesh (encoder-decoder counterpart of 02_train_gpt2).

Run: python examples/09_seq2seq_t5.py
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
from ray_tpu.models import (T5, seq2seq_loss, t5_greedy_decode,
                            t5_sharding_rules, t5_tiny)
from ray_tpu.train.spmd import (TrainState, make_train_step, put_batch,
                                shard_state)

cfg = t5_tiny(vocab_size=32, dim=64, n_heads=4, hidden_dim=128)
mesh = create_mesh({"data": 2, "fsdp": 2, "tensor": 2})
model = T5(cfg)
rng = np.random.RandomState(0)
L = 6

src = rng.randint(3, cfg.vocab_size, (16, L)).astype(np.int32)
dec_in = np.concatenate([np.full((16, 1), 1), src[:, :-1]],
                        axis=1).astype(np.int32)
batch_np = {"enc": src, "dec": dec_in, "tgt": src}

params = model.init(jax.random.PRNGKey(0), jnp.asarray(src[:2]),
                    jnp.asarray(dec_in[:2]))
optimizer = optax.adam(1e-2)
state = shard_state(TrainState.create(params, optimizer),
                    t5_sharding_rules(), mesh)
step = make_train_step(
    lambda p, b: seq2seq_loss(model.apply(p, b["enc"], b["dec"]),
                              b["tgt"]),
    optimizer)

with jax.set_mesh(mesh):
    batch = put_batch(batch_np, mesh)
    for i in range(200):
        state, m = step(state, batch)
        if i % 50 == 0 or i == 199:
            print(f"step {i:3d}  loss {float(m['loss']):.4f}")

host = jax.device_get(state.params)
out = t5_greedy_decode(model, host, src[:2], max_len=L, bos_id=1)
print("source :", src[0].tolist())
print("decoded:", np.asarray(out)[0].tolist())
