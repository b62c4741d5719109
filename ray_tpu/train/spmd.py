"""SPMD train-step construction: the compiled heart of JaxTrainer.

Replaces the reference's DDP wiring (train/torch/config.py
_setup_torch_process_group + NCCL allreduce) with mesh-sharded pjit: place
params/opt-state by sharding rules, shard the batch on the data axes, jit the
whole step with donation — XLA inserts the gradient psum over ICI/DCN and
overlaps it with the backward pass.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.mesh.sharding import ShardingRules, infer_sharding
from ray_tpu.util.compile_cache import enable_compile_cache


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, optimizer: optax.GradientTransformation):
        return cls(params=params, opt_state=optimizer.init(params),
                   step=jnp.zeros((), jnp.int32))


def state_shardings(state: TrainState, rules: ShardingRules,
                    mesh: Mesh) -> TrainState:
    """Shardings for the whole state: params by rules; optimizer slots
    mirror their parameter's sharding; step replicated."""
    param_sh = infer_sharding(state.params, rules, mesh)
    # Walk the opt_state: any leaf whose shape matches a param leaf gets
    # that param's sharding (optax slots mirror params); scalars replicate.
    flat_params = {l.shape: s for l, s in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(param_sh))}
    rep = NamedSharding(mesh, P())

    def slot_sharding(leaf):
        return flat_params.get(getattr(leaf, "shape", None), rep)

    opt_sh = jax.tree_util.tree_map(slot_sharding, state.opt_state)
    return TrainState(params=param_sh, opt_state=opt_sh,
                      step=rep)


def shard_state(state: TrainState, rules: ShardingRules,
                mesh: Mesh) -> TrainState:
    sh = state_shardings(state, rules, mesh)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state, sh)


def make_train_step(loss_fn: Callable[[Any, Any], jax.Array],
                    optimizer: optax.GradientTransformation,
                    donate: bool = True):
    """loss_fn(params, batch) -> scalar loss. Returns jitted
    (state, batch) -> (state, metrics)."""
    enable_compile_cache()

    def step_fn(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, new_opt = optimizer.update(grads, state.opt_state,
                                            state.params)
        new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        return (TrainState(new_params, new_opt, state.step + 1),
                {"loss": loss, "grad_norm": gnorm,
                 "step": state.step + 1})

    return jax.jit(step_fn, donate_argnums=(0,) if donate else ())


def batch_shardings(mesh: Mesh, batch_example) -> Any:
    """Shard every batch leaf on its leading dim over (dcn, data, fsdp)."""
    sh = NamedSharding(mesh, P(("dcn", "data", "fsdp")))

    def leaf_sh(x):
        return sh
    return jax.tree_util.tree_map(leaf_sh, batch_example)


def put_batch(batch, mesh: Mesh):
    """Shard a batch's leading dim over the data axes.

    Single-process: a plain device_put. Multi-process gang (the mesh
    spans jax.distributed hosts): each process contributes its LOCAL
    batch as this host's shard of the global array — per-host data
    loading, the global batch is num_hosts x local without any
    host-to-host copy."""
    sh = NamedSharding(mesh, P(("dcn", "data", "fsdp")))
    import numpy as np

    def put(x):
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(
                sh, np.asarray(x))
        return jax.device_put(x, sh)

    return jax.tree_util.tree_map(put, batch)
