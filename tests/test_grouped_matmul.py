"""The grouped matmul's tile plan (``ops/grouped_matmul.py``
``tile_plan``) over the matrices the five mixtures present, and the
Pallas kernel under it in interpret mode against ``jax.lax.ragged_dot``.
That the plans lower for the chip is ``tests/test_chip_compile.py``'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul as gm

# (config, D, F): w1 and w3 are [E, D, F], w2 is [E, F, D]
MIXTURES = (("olmoe", 2048, 1024), ("solar-open2", 4096, 1280),
            ("axk1", 7168, 2048), ("kimi-linear", 2304, 1024),
            ("mellum2", 2304, 896))
CALLS = [pytest.param(m, k, n, id=f"{name}.{kind}.{which}")
         for name, d, f in MIXTURES
         for kind, m in (("decode", 1024 if name == "kimi-linear" else 256),
                         ("prefill", 8192))
         for which, (k, n) in (("w13", (d, f)), ("w2", (f, d)))]


def _legal(m, k, n, itemsize=2):
    tm, tk, tn = gm.tile_plan(m, k, n, itemsize)
    # no masked contraction tail, no partly empty column tile
    assert k % tk == 0 and n % tn == 0
    # a block's last dimension is whole or in 128 lanes, its rows in
    # packed sublanes (16 of bfloat16, 8 of float32)
    assert tk == k or tk % 128 == 0
    assert tn == n or tn % 128 == 0
    assert tm % (32 // itemsize) == 0
    assert gm.vmem_bytes(tm, tk, tn, itemsize) <= 16 << 20
    return tm, tk, tn


@pytest.mark.parametrize("m,k,n", CALLS)
def test_tile_plan_divides_the_matrices_the_mixtures_present(m, k, n):
    tm, tk, tn = _legal(m, k, n)
    # a visit takes few steps: the matrix block is not a sliver
    assert tk * tn * 2 >= 2 << 20


@pytest.mark.parametrize("m", [256, 8192])
@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)])
def test_tile_plan_takes_olmoes_matrices_whole(m, k, n):
    """w1 and w3 as the constants they were sized on walked them,
    (128, 2048, 1024); w2 in one 4 MiB block too, where the constants
    cut it in two column tiles (PERF.md section 6, PR 44: 4.5 % of the
    call by the timer)."""
    assert gm.tile_plan(m, k, n, 2) == (128, k, n)


@pytest.mark.parametrize("m,k,n,itemsize", [
    (256, 5120, 1536, 2), (8192, 1536, 5120, 2), (64, 6144, 768, 2),
    (256, 1408, 4096, 2), (256, 2048, 1024, 4), (100, 4096, 14336, 2),
    (256, 200, 72, 2)])
def test_tile_plan_of_a_shape_no_cell_has(m, k, n, itemsize):
    _legal(m, k, n, itemsize)


def test_tile_plan_whole_contraction_where_it_fits():
    """Hidden sizes of 2,304 (Kimi-Linear, Mellum2): the contraction in
    one step a visit, where the constants left a masked tail of 256."""
    for n in (1024, 896):
        assert gm.tile_plan(256, 2304, n, 2)[1:] == (2304, n)


def test_tile_plan_falls_back_where_nothing_divides():
    # neither dimension in 128 lanes and the whole matrix too large:
    # the kernel masks the remainders itself
    tm, tk, tn = gm.tile_plan(256, 5000, 3000, 2)
    assert tk % 128 == 0 and tn % 128 == 0
    assert gm.vmem_bytes(tm, tk, tn, 2) <= 16 << 20


# group sizes over 300 rows: empty groups, a group across the edge of
# a 128-row tile, rows past the last group
@pytest.mark.parametrize("sizes", [(0, 140, 0, 100), (128, 0, 128, 1),
                                   (0, 0, 0, 0), (7, 3, 290, 0)])
def test_kernel_matches_ragged_dot(sizes):
    """The kernel in interpret mode under the plan of a contraction the
    old tile of 2,048 did not divide (2,304 = 2^8 x 9)."""
    m, k, n = 300, 2304, 256
    assert 2304 % 2048 and gm.tile_plan(m, k, n, 4)[1] == k
    ks = jax.random.split(jax.random.PRNGKey(sum(sizes)), 2)
    rows = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32) * k ** -0.5
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda *a: gm.grouped_matmul_kernel(*a, interpret=True))(
        rows, w, group_sizes)
    want = jax.lax.ragged_dot(rows, w, group_sizes)
    assert got.shape == (m, n) and got.dtype == rows.dtype
    held = sum(sizes)
    np.testing.assert_allclose(np.asarray(got)[:held],
                               np.asarray(want)[:held],
                               rtol=2e-4, atol=2e-4)


def test_kernel_in_bfloat16_with_tiles_in_both_directions():
    """A matrix walked in several contraction AND column tiles (the
    budget shrunk so that the plan has to cut both), bfloat16 operands,
    float32 accumulation."""
    m, k, n = 96, 512, 384
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gm, "_VMEM_BUDGET",
                   gm.vmem_bytes(128, 256, 128, 2))
        tm, tk, tn = gm.tile_plan(m, k, n, 2)
        assert (k // tk, n // tn) == (2, 3)
        ks = jax.random.split(jax.random.PRNGKey(3), 2)
        rows = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
        w = (jax.random.normal(ks[1], (3, k, n)) * k ** -0.5).astype(
            jnp.bfloat16)
        sizes = jnp.asarray([40, 0, 50], jnp.int32)
        got = jax.jit(lambda *a: gm.grouped_matmul_kernel(
            *a, interpret=True))(rows, w, sizes)
    want = jax.lax.ragged_dot(rows, w, sizes,
                              preferred_element_type=jnp.float32)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32)[:90],
                               np.asarray(want)[:90], rtol=2e-2, atol=2e-2)
