"""Parallelism-strategy tests on the 8-device CPU mesh (topology-
parameterized, the reference's collective-test pattern:
util/collective/tests/single_node_cpu)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.mesh import create_mesh
from ray_tpu.ops.attention import xla_attention
from ray_tpu.parallel import (SwitchMoE, pipeline_apply, ring_attention,
                              sequence_sharded_attention, ulysses_attention)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    B, T, H, D = 2, 64, 4, 16
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(qkv, causal, cpu_mesh_devices):
    q, k, v = qkv
    mesh = create_mesh({"sequence": 8})
    expected = xla_attention(q, k, v, causal=causal,
                             precision="highest")
    out = sequence_sharded_attention(q, k, v, mesh, causal=causal,
                                     impl="ring")
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_full(qkv, causal, cpu_mesh_devices):
    q, k, v = qkv
    mesh = create_mesh({"sequence": 4, "data": 2})  # H=4 divisible by 4
    expected = xla_attention(q, k, v, causal=causal,
                             precision="highest")
    out = sequence_sharded_attention(q, k, v, mesh, causal=causal,
                                     impl="ulysses")
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_grads_flow(qkv, cpu_mesh_devices):
    q, k, v = qkv
    mesh = create_mesh({"sequence": 8})

    def loss_ring(q, k, v):
        return jnp.sum(
            sequence_sharded_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(
            xla_attention(q, k, v, causal=True, precision="highest") ** 2)

    # jitted: eager grad of the 8-step ring dispatches (and compiles)
    # every collective-permute step on its own
    g_ring = jax.jit(jax.grad(loss_ring))(q, k, v)
    g_full = jax.jit(jax.grad(loss_full))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               rtol=1e-3, atol=1e-4)


def test_pipeline_matches_sequential(cpu_mesh_devices):
    from ray_tpu.parallel.pipeline import stack_stage_params
    S, B, D = 4, 8, 16
    mesh = create_mesh({"pipeline": S})
    rng = np.random.RandomState(1)
    per_stage = [{"w": jnp.asarray(rng.randn(D, D) / np.sqrt(D),
                                   jnp.float32),
                  "b": jnp.asarray(rng.randn(D) * 0.1, jnp.float32)}
                 for _ in range(S)]
    x = jnp.asarray(rng.randn(B, D), jnp.float32)

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"] + p["b"])

    expected = x
    for p in per_stage:
        expected = stage_fn(p, expected)

    stacked = stack_stage_params(per_stage)
    out = pipeline_apply(stage_fn, stacked, x, num_microbatches=4,
                         mesh=mesh)
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_microbatch_validation(cpu_mesh_devices):
    mesh = create_mesh({"pipeline": 4})
    with pytest.raises(ValueError):
        pipeline_apply(lambda p, a: a, {"w": jnp.ones((4, 1))},
                       jnp.ones((7, 1)), num_microbatches=3, mesh=mesh)


def test_moe_routes_and_matches_manual(cpu_mesh_devices):
    B, T, D, E, FF = 2, 16, 8, 4, 32
    moe = SwitchMoE(num_experts=E, d_model=D, d_ff=FF,
                    capacity_factor=4.0,   # no drops at this size
                    use_sharding_constraint=False)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    variables = jax.jit(moe.init)(rng, x)
    out, aux = jax.jit(
        lambda v, x: moe.apply(v, x, mutable=["losses"]))(variables, x)
    assert out.shape == (B, T, D)

    # Manual reference: route each token to its argmax expert.
    p = variables["params"]
    tokens = np.asarray(x).reshape(-1, D)
    logits = tokens @ np.asarray(p["router"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    idx = probs.argmax(-1)
    expected = np.zeros_like(tokens)
    for n, e in enumerate(idx):
        h = np.maximum(tokens[n] @ np.asarray(p["w1"])[e], 0)
        expected[n] = (h @ np.asarray(p["w2"])[e]) * probs[n, e]
    np.testing.assert_allclose(np.asarray(out).reshape(-1, D), expected,
                               rtol=1e-4, atol=1e-5)
    assert float(aux["losses"]["load_balance"][0]) > 0


def test_moe_sharded_execution(cpu_mesh_devices):
    mesh = create_mesh({"expert": 4, "data": 2})
    moe = SwitchMoE(num_experts=4, d_model=8, d_ff=16, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 8))
    variables = jax.jit(moe.init)(jax.random.PRNGKey(0), x)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda v, x: moe.apply(v, x))(variables, x)
    assert out.shape == x.shape
    # Same numbers as unsharded execution.
    expected = jax.jit(moe.apply)(variables, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-4, atol=1e-5)
