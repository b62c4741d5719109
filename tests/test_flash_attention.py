"""Custom pallas flash-attention kernel tests (interpret mode on the CPU
mesh; the same kernels run natively on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.flash_attention import flash_attention


def _rand_qkv(B=2, T=256, H=2, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(causal):
    q, k, v = _rand_qkv()
    expected = xla_attention(q, k, v, causal=causal, precision="highest")
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=2e-3, atol=2e-3)


def test_flash_multiple_kv_blocks():
    # T large enough to force several kv blocks per q block.
    q, k, v = _rand_qkv(B=1, T=512, H=1, D=64, seed=1)
    expected = xla_attention(q, k, v, causal=True, precision="highest")
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match(causal):
    q, k, v = _rand_qkv(B=1, T=256, H=2, D=64, seed=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(
            xla_attention(q, k, v, causal=causal,
                          precision="highest") ** 2)

    # jitted: eager grad dispatches the interpreted kernel op by op
    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(loss_xla, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} mismatch")


def test_flash_rejects_unaligned():
    q, k, v = _rand_qkv(T=100)
    with pytest.raises(ValueError):
        flash_attention(q, k, v)


def test_flash_rejects_causal_cross_length():
    q, _, _ = _rand_qkv(T=512, H=1)
    _, k, v = _rand_qkv(T=256, H=1, seed=3)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True)


@pytest.mark.parametrize("H,D", [
    (4, 64),    # multi-group packed path (G=2), the GPT-2-shape family
    (12, 64),   # the production GPT-2-124M head config (G=6)
    (3, 64),    # odd H: padded to H'=4
    (2, 96),    # D not a power of two: padded to D'=128
    (2, 256),   # wide heads D > 128: one head per program
])
def test_flash_packed_groups_and_padding(H, D):
    q, k, v = _rand_qkv(B=1, T=256, H=H, D=D, seed=4)
    expected = xla_attention(q, k, v, causal=True, precision="highest")
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=2e-3, atol=2e-3)
    # Gradients flow through the pad/slice wrapper correctly.
    gf = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True) ** 2)))(q, k, v)
    gx = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        xla_attention(q, k, v, causal=True,
                      precision="highest") ** 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gx),
                               rtol=5e-3, atol=5e-3)


def test_flash_impl_runs_per_shard_under_a_mesh(cpu_mesh_devices):
    """GSPMD cannot partition a Mosaic kernel (the chip's compiler
    says so; interpret mode does not), so under an ambient
    multi-device mesh impl="flash" must go through shard_map: batch
    over the data axes, heads over tensor."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.mesh import create_mesh
    from ray_tpu.ops.attention import (multi_head_attention,
                                       xla_attention)
    mesh = create_mesh({"data": 2, "tensor": 2},
                       devices=cpu_mesh_devices[:4])
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((4, 128, 4, 64)),
                           jnp.float32) for _ in range(3))
    sh = NamedSharding(mesh, P("data", None, "tensor", None))

    def attn(q, k, v):
        return multi_head_attention(q, k, v, causal=True, impl="flash")

    with jax.set_mesh(mesh):
        args = [jax.device_put(x, sh) for x in (q, k, v)]
        assert "shard_map" in str(jax.make_jaxpr(attn)(*args))
        out = jax.jit(attn)(*args)
    assert out.sharding.is_equivalent_to(sh, out.ndim)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(xla_attention(q, k, v, precision="highest")),
        rtol=2e-3, atol=2e-3)
    # no mesh: a plain kernel call
    assert "shard_map" not in str(jax.make_jaxpr(attn)(q, k, v))
