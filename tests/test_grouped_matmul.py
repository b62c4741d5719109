"""The grouped matmul's tile plan (``ops/grouped_matmul.py``
``tile_plan``) over the matrices the mixtures present, and the Pallas
kernel under it (the repo's own body, which fetches a matrix by GROUP)
in interpret mode against ``jax.lax.ragged_dot``; ``visits`` against a
count by hand. That the plans lower for the chip is
``tests/test_chip_compile.py``'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul as gm

# (config, D, F): w1 and w3 are [E, D, F], w2 is [E, F, D]
MIXTURES = (("olmoe", 2048, 1024), ("solar-open2", 4096, 1280),
            ("axk1", 7168, 2048), ("kimi-linear", 2304, 1024),
            ("mellum2", 2304, 896), ("laguna-xs2", 2048, 512),
            ("dsv32", 7168, 2048))
CALLS = [pytest.param(m, k, n, id=f"{name}.{kind}.{which}")
         for name, d, f in MIXTURES
         for kind, m in (("decode", 1024 if name in ("kimi-linear",
                                                     "laguna-xs2")
                          else 256),
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


# ------------------------------------ the walk by group, and its count

# group sizes over 400 rows (four row tiles of 128 once padded)
WALKS = {
    "one_tile_each": (50, 30, 20),
    "two_tiles": (0, 200, 0, 60),
    "three_tiles": (30, 300, 0, 40),
    "empty_first_between_and_last": (0, 100, 0, 0, 150, 0),
    "all_empty": (0, 0, 0),
    "one_group_holds_every_row": (0, 400, 0),
    "a_single_group": (400,),
    "edges_on_the_tiles": (128, 128, 144),
    "one_row_each": (1, 1, 0, 1, 1),
    "rows_past_the_last_group": (129, 0, 127, 3),
}
# (tk, tn) over a 256 x 256 matrix: one block a visit, the contraction
# in two, two column tiles, both
PLANS = {"one_block": (256, 256), "k_blocks": (128, 256),
         "n_blocks": (256, 128), "k_and_n_blocks": (128, 128)}


def _by_hand(sizes, tm=128):
    """The (row tile, group) pairs that hold a row."""
    group_of_row = np.repeat(np.arange(len(sizes)), sizes)
    return len({(r // tm, g) for r, g in enumerate(group_of_row)})


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("walk", WALKS)
def test_group_keyed_kernel_matches_ragged_dot(walk, plan, monkeypatch):
    """Every way a group can lie over the row tiles, under every kind
    of plan: the copies the body starts and waits itself bring each
    visit its own group's block."""
    sizes = WALKS[walk]
    m, k, n = 400, 256, 256
    tk, tn = PLANS[plan]
    monkeypatch.setattr(gm, "tile_plan", lambda *_: (128, tk, tn))
    ks = jax.random.split(jax.random.PRNGKey(len(sizes)), 2)
    rows = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32) \
        * k ** -0.5
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda *a: gm.grouped_matmul_kernel(*a, interpret=True))(
        rows, w, group_sizes)
    want = jax.lax.ragged_dot(rows, w, group_sizes)
    assert got.shape == (m, n) and got.dtype == rows.dtype
    held = sum(sizes)
    np.testing.assert_allclose(np.asarray(got)[:held],
                               np.asarray(want)[:held],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("walk", WALKS)
def test_visits_counts_the_grid(walk):
    sizes = WALKS[walk]
    got = jax.jit(lambda s: gm.visits(s, 400))(jnp.asarray(sizes, jnp.int32))
    assert got.dtype == jnp.int32 and int(got) == _by_hand(sizes)
    # the visits that find their matrix fetched: what the walk by
    # group saves over a fetch a visit
    assert int(got) >= sum(s > 0 for s in sizes)


def test_visits_of_the_cells_calls():
    """A prefill call of 8,192 pairs over 64 held experts visits about
    twice the experts (a group of ~128 rows straddles two tiles); a
    decode call of 256 pairs visits each touched expert once, but for
    the one that lies across the two tiles' edge."""
    even = jnp.full((64,), 128, jnp.int32)
    assert int(gm.visits(even, 8192)) == 64
    rng = np.random.default_rng(0)
    sizes = rng.multinomial(8192, np.full(64, 1 / 64))
    assert int(gm.visits(jnp.asarray(sizes, jnp.int32), 8192)) \
        == _by_hand(sizes) >= 110
    sizes = rng.multinomial(256, np.full(64, 1 / 64))
    touched = int((sizes > 0).sum())
    assert touched <= int(gm.visits(jnp.asarray(sizes, jnp.int32), 256)) \
        == _by_hand(sizes) <= touched + 1


@pytest.mark.parametrize("n,plan", [(200, (128, 128, 128)),
                                    (72, (128, 200, 72))],
                         ids=["a_remainder", "narrower_than_a_lane_tile"])
def test_what_the_own_body_cannot_copy_stays_on_the_shipped_kernel(
        n, plan, monkeypatch):
    """Tiles that do not divide the matrix (the fallback of a shape no
    cell has) and a matrix narrower than whole lanes: the shipped
    ``gmm``'s block specs mask and pad, where the repo's body copies
    whole blocks of whole lanes out of HBM."""
    m, k = 200, 200
    monkeypatch.setattr(gm, "tile_plan", lambda *_: plan)
    monkeypatch.setattr(gm, "_group_keyed", None)        # not called
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    rows = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (3, k, n), jnp.float32) * k ** -0.5
    sizes = jnp.asarray([90, 0, 100], jnp.int32)
    got = jax.jit(lambda *a: gm.grouped_matmul_kernel(*a, interpret=True))(
        rows, w, sizes)
    want = jax.lax.ragged_dot(rows, w, sizes)
    np.testing.assert_allclose(np.asarray(got)[:190],
                               np.asarray(want)[:190],
                               rtol=2e-4, atol=2e-4)
