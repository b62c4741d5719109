"""Custom pallas flash-attention kernel tests (interpret mode on the CPU
mesh; the same kernels run natively on TPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.flash_attention import (_pad_to_packable,
                                         flash_attention, tile_plan)


def _rand_qkv(B=2, T=256, H=2, D=64, dtype=jnp.float32, seed=0, Tk=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, t, H, D), dtype)
                 for k, t in zip(ks, (T, Tk or T, Tk or T)))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(causal):
    q, k, v = _rand_qkv()
    expected = xla_attention(q, k, v, causal=causal, precision="highest")
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=2e-3, atol=2e-3)


def test_flash_multiple_kv_blocks():
    # T large enough to force several kv tiles per q tile.
    plan = tile_plan(512, 512, 64, True)
    assert plan.tile_k < 512 and plan.visited > plan.masked > 1
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


# (T, Tk, H, D, causal, one pass): every case crosses tiles, so the
# online rescale between tiles, the tiles skipped above the diagonal,
# the mask of the diagonal's alone and the finalize after the last
# block all run.
_TILED = [
    (1024, 1024, 2, 64, True, True),     # the train cell's sequence
    (1024, 1024, 2, 64, False, True),
    (256, 512, 2, 64, False, True),      # cross-length, kv the longer
    (512, 256, 2, 64, False, True),      # and q the longer
    (512, 512, 1, 128, True, True),      # D = 128: one head a program
    (512, 512, 3, 64, True, True),       # odd H: padded to H'=4
    (512, 512, 2, 96, True, True),       # D padded to D'=128
    (2048, 2048, 2, 64, True, False),    # past the one-pass rule:
    (2048, 1024, 1, 128, False, False),  # blocks on a grid, two passes
]


@pytest.mark.parametrize("T,Tk,H,D,causal,one_pass", _TILED)
def test_flash_across_tiles(T, Tk, H, D, causal, one_pass):
    """Forward and gradients against the float32 reference where a
    sequence is several tiles, down both routes of the backward."""
    plan = tile_plan(T, Tk, _pad_to_packable(H, D)[1], causal)
    assert plan.one_pass == one_pass
    assert plan.total >= 2 and plan.visited >= 2
    assert (plan.visited < plan.total) == causal
    q, k, v = _rand_qkv(B=1, T=T, Tk=Tk, H=H, D=D, seed=5)

    def loss(attn):
        def f(q, k, v):
            o = attn(q, k, v, causal=causal)
            return jnp.sum(o ** 2), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out), gf = loss(flash_attention)(q, k, v)
    (_, expected), gx = loss(functools.partial(
        xla_attention, precision="highest"))(q, k, v)
    np.testing.assert_allclose(np.asarray(expected), np.asarray(out),
                               rtol=2e-3, atol=2e-3)
    for a, b, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("T,Tk,causal,visited,masked,total", [
    (1024, 1024, True, 10, 4, 16),
    (8192, 8192, True, 528, 32, 1024),
    (256, 512, False, 2, 0, 2),
    (128, 128, True, 1, 1, 1),
])
def test_tile_plan(T, Tk, causal, visited, masked, total):
    """The plan the kernels' grids and loops are built from: a causal
    call computes no tile wholly above the diagonal, masks every tile
    the diagonal crosses and none below it; every tile with a score to
    keep is computed once."""
    plan = tile_plan(T, Tk, 64, causal)
    assert (plan.visited, plan.masked, plan.total) == \
        (visited, masked, total)
    assert plan.blk_q % plan.tile_q == 0 == plan.blk_k % plan.tile_k
    assert T % plan.blk_q == 0 == Tk % plan.blk_k
    tiles = list(plan.tiles(T, Tk))
    assert len(tiles) == len({(i, j) for i, j, _ in tiles}) == visited
    assert sum(m for _, _, m in tiles) == masked
    seen = {(i, j): m for i, j, m in tiles}
    for i in range(T // plan.tile_q):
        for j in range(Tk // plan.tile_k):
            first_q, last_q = i * plan.tile_q, (i + 1) * plan.tile_q - 1
            first_k, last_k = j * plan.tile_k, (j + 1) * plan.tile_k - 1
            above = causal and first_k > last_q     # nothing to keep
            below = not causal or last_k <= first_q  # nothing to drop
            assert ((i, j) in seen) == (not above), (i, j)
            if not above:
                assert seen[i, j] == (not below), (i, j)


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
