"""Phi-4-mini-flash on the normal path (ray_tpu.models.phi4flash through
LLMEngine and LlamaDeployment) against the plain float32 reference
(benchmarks/reference/phi4flash.py), on the CPU at ``phi4flash_tiny``:
8 layers that keep every kind and the boundaries (state-space 0, 2, 4;
sliding 1, 3; full 5; memory unit 6; cross 7), 8 query / 4 K/V heads of
8, a window of 8 under contexts of several windows and several chunks.

Tolerances. Both sides compute in float32 on the same weights and
differ in the order of their sums (the program scores zero-padded
128-wide rows under one softmax a row, folds blocks of keys online and
steps the state as it is stored; the reference takes the four products
of the differential form one key pair at a time and scans positions):
logits of the order of 1 agree to rtol 1e-4 / atol 2e-5, as the other
families' do. Each of the reference's controls (the cross layers given
another layer's keys, the memory taken after the gate, lambda fixed at
lambda0, the window half as wide again) moves logits by a hundred times
that or more, and a state carried in bfloat16 misses it too. The
engine's tokens are held to the reference's full forward pass
teacher-forced, and the captured log-probability of every generated
token (the whole row of logits behind it) to the reference's.
"""
import functools
import math
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kv_cache
from ray_tpu.models.kv_cache import (KIND_BORROWED, KIND_KV, KIND_RECURRENT,
                                     KIND_SLIDING, KIND_STATELESS,
                                     RecurrentState, SlidingRing,
                                     export_page_bytes, init_kv_pool,
                                     kv_layer_store, kv_layer_view,
                                     kv_pool_page_bytes, page_cols_from_bytes,
                                     refuse_unsupported, sliding_ring_len,
                                     state_bytes_per_slot)
from ray_tpu.models.phi4flash import (CROSS, FULL, GMU, SLIDING, SSM,
                                      Phi4Flash, attention_param_count,
                                      lambda_init, phi4_mini_flash,
                                      phi4flash_param_count, phi4flash_tiny,
                                      ssm_param_count)
from ray_tpu.ops.selective_scan import ssm_chunked, ssm_step
from ray_tpu.serve.engine import LLMEngine

RTOL, ATOL = 1e-4, 2e-5
PAGE, CHUNK = 8, 16


def _family():
    from benchmarks import common
    return common.load_family("phi4flash", "serve")


def _seeded(cfg, seed=0):
    """The benchmark family's seeded weights, then every norm's scale
    away from one so that a scale left out shows."""
    from benchmarks import weights
    model = Phi4Flash(cfg)
    params = _family().init_params(weights.param_shapes(model), seed)
    rng = np.random.default_rng(seed + 1)

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "subln" in name:
            return leaf * (1.0 + 0.3 * rng.standard_normal(
                leaf.shape)).astype(np.float32)
        return leaf
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = phi4flash_tiny(dtype=jnp.float32)
    model, params = _seeded(cfg)
    return cfg, model, params


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(1, 255, size=shape)


def _reference(params, ids, cfg, **control):
    fam = _family()
    return np.asarray(fam.reference_forward(
        fam.reference_weights(params, cfg), jnp.asarray(ids, jnp.int32),
        cfg, **control))


def _forward(model, params, ids):
    return np.asarray(jax.jit(model.apply)(
        params, jnp.asarray(ids, jnp.int32))[0])


def _held_to_the_reference(params, cfg, prompt, out):
    """The teacher-forced rule of the module docstring; returns the
    reference's logits of the generated positions."""
    P, G = len(prompt), len(out)
    logits = _reference(params, [list(prompt) + list(out)], cfg)[0]
    steps = logits[P - 1:P - 1 + G]
    top2 = np.sort(steps, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 10 * RTOL * np.abs(steps).max()
    assert decisive.sum() >= G * 2 // 3
    assert (steps.argmax(-1)[decisive] == np.asarray(out)[decisive]).all()
    return steps


def _drive(eng, max_rounds=5000):
    for _ in range(max_rounds):
        if not eng.step():
            return
    raise AssertionError("the engine did not quiesce")


def _engine(tiny, **kw):
    _cfg, model, params = tiny
    opts = dict(max_slots=4, page_size=PAGE, n_pages=64, chunk=4,
                prefill_chunk=CHUNK, temperature=0.0, seed=0)
    opts.update(kw)
    return LLMEngine(model, params, **opts)


# ----------------------------------------------------- the model itself

@pytest.mark.parametrize("seed", [2, 5, 6])
def test_forward_matches_the_reference(tiny, seed):
    """The whole forward pass without a cache, 45 positions: several
    windows of 8."""
    cfg, model, params = tiny
    ids = _ids((2, 45), seed=seed)
    np.testing.assert_allclose(_forward(model, params, ids),
                               _reference(params, ids, cfg),
                               rtol=RTOL, atol=ATOL)


def test_layer_kinds_and_the_published_counts():
    cfg = phi4_mini_flash()
    assert cfg.mixers.count(SSM) == 9 and cfg.mixers.count(SLIDING) == 8
    assert cfg.mixers.count(GMU) == cfg.mixers.count(CROSS) == 7
    assert cfg.mixers.index(FULL) == 17 and cfg.mixers.count(FULL) == 1
    assert cfg.mixers[:18] == (SSM, SLIDING) * 8 + (SSM, FULL)
    assert cfg.mixers[18:] == (GMU, CROSS) * 7
    assert cfg.memory_layer == 16
    kinds = cfg.layer_kinds
    assert [kinds.count(k) for k in (
        KIND_RECURRENT, KIND_SLIDING, KIND_KV, KIND_BORROWED,
        KIND_STATELESS)] == [9, 8, 1, 7, 7]
    # the stored layout: 10 pairs of 128, 40 query rows, pages of 16
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.kv_page_heads) == (40, 10, 128, 16)
    assert cfg.recurrent_state_shape == (16, 5120)
    assert cfg.recurrent_conv_shape == (3, 5120)
    # ISSUE 60's count from the equations: 3,852.6 M, by kind of layer
    mlp = 3 * 2560 * 10240 + 4 * 2560
    assert ssm_param_count(cfg) + mlp == pytest.approx(119.9e6, rel=1e-3)
    assert attention_param_count(cfg, FULL) + mlp == pytest.approx(
        98.3e6, rel=1e-3)
    assert attention_param_count(cfg, CROSS) + mlp == pytest.approx(
        91.8e6, rel=1e-3)
    assert 2 * 2560 * 5120 + mlp == pytest.approx(104.9e6, rel=1e-3)
    assert phi4flash_param_count(cfg) == pytest.approx(3852.6e6, rel=1e-3)
    fam = _family()
    from benchmarks import common
    file = common.load_json("configs", "phi-4-mini-flash-reasoning.json")
    assert fam.model_params(file) == pytest.approx(
        phi4flash_param_count(cfg), rel=1e-3)
    # the tiny preset's count is the tree's own
    tiny_cfg = phi4flash_tiny()
    shapes = jax.eval_shape(Phi4Flash(tiny_cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(
        shapes)) == phi4flash_param_count(tiny_cfg)


def test_lambda_against_hand_worked_values(tiny):
    """lambda0 by the layer's index, and lambda from the four vectors:
    a layer whose vectors are known gives the hand-worked mixing."""
    assert lambda_init(0) == pytest.approx(0.2)
    assert lambda_init(1) == pytest.approx(0.8 - 0.6 * 0.7408182, abs=1e-6)
    assert lambda_init(17) == pytest.approx(0.79635, abs=1e-5)
    assert lambda_init(31) == pytest.approx(0.799945, abs=1e-6)
    from benchmarks.reference import phi4flash as ref
    assert [ref.lambda0(l) for l in range(32)] == pytest.approx(
        [lambda_init(l) for l in range(32)])
    # lq1 . lk1 = 0.5, lq2 . lk2 = -0.25 in layer 1 of the tiny model:
    # lambda = e^0.5 - e^-0.25 + lambda0(1) = 1.22543
    cfg, model, params = tiny
    p = jax.tree_util.tree_map(lambda a: a, params)
    a = dict(p["params"]["layers_1"]["attention"])
    half = cfg.head_dim // 2
    one = jnp.zeros((half,), jnp.float32).at[0].set(1.0)
    a.update(lambda_q1=0.5 * one, lambda_k1=one, lambda_q2=-0.25 * one,
             lambda_k2=one)
    p["params"]["layers_1"] = {**p["params"]["layers_1"], "attention": a}
    lam = math.exp(0.5) - math.exp(-0.25) + lambda_init(1)
    assert lam == pytest.approx(1.22543, abs=1e-5)
    ids = _ids((1, 20), seed=4)
    got = _forward(model, p, ids)
    np.testing.assert_allclose(got, _reference(p, ids, cfg),
                               rtol=RTOL, atol=ATOL)
    # and the vectors matter: the tiny model's own give other logits
    assert np.abs(got - _forward(model, params, ids)).max() > 100 * ATOL


# ------------------------------------------------- the controls must fail

CONTROLS = ["cross_from_sliding", "memory_after_gate", "lambda_fixed",
            "wide_window", "bf16_state", "lower_precision"]


@pytest.mark.parametrize("what", CONTROLS)
def test_the_comparison_fails_whatever_is_changed(tiny, what):
    """Each control of the reference moves the logits past the
    tolerance the program is held to, at 45 positions (several windows,
    the cross layers' keys several windows back)."""
    cfg, model, params = tiny
    assert set(CONTROLS) == set(_family().CONTROLS)
    ids = _ids((2, 45), seed=8)
    got = _forward(model, params, ids)
    want = _reference(params, ids, cfg, **{what: True})
    gap = np.abs(got - want) - (ATOL + RTOL * np.abs(want))
    assert gap.max() > 10 * ATOL, gap.max()


# ---------------------------------------------------------- the two rules

def _scan_inputs(B, T, C, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    u, Bm, Cm = f(B, T, C), f(B, T, N), f(B, T, N)
    delta = jax.nn.softplus(f(B, T, C) - 2.0)
    A, D = -jnp.exp(f(N, C)), f(C)
    return u, delta, A, Bm, Cm, D


def test_step_chunked_and_the_reference_loop_agree():
    """``ssm_step`` T times = ``ssm_chunked`` in one piece = in chunks
    from a carried state = the plain reference's loop, and positions
    that are not real move nothing."""
    from benchmarks.reference import phi4flash as ref
    B, T, C, N = 3, 21, 24, 8
    u, delta, A, Bm, Cm, D = _scan_inputs(B, T, C, N)
    want = np.asarray(ref.selective_scan(u, delta, A, Bm, Cm, D))
    zero = jnp.zeros((B, N, C), jnp.float32)
    ok = jnp.ones((B, T), bool)
    whole, end = ssm_chunked(u, delta, A, Bm, Cm, D, zero, ok)
    np.testing.assert_allclose(whole, want, rtol=1e-5, atol=1e-6)
    # chunks of 8, 8 and 5 from the carried state
    state, got = zero, []
    for s in (0, 8, 16):
        e = min(s + 8, T)
        y, state = ssm_chunked(u[:, s:e], delta[:, s:e], A, Bm[:, s:e],
                               Cm[:, s:e], D, state, ok[:, s:e])
        got.append(y)
    np.testing.assert_allclose(jnp.concatenate(got, 1), want,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(state, end, rtol=1e-5, atol=1e-6)
    # a step at a time; row 1 starts over at position 10 (fresh)
    state, got = zero, []
    for t in range(T):
        fresh = jnp.asarray([False, t == 10, False])
        y, state = ssm_step(u[:, t], delta[:, t], A, Bm[:, t], Cm[:, t], D,
                            state, jnp.ones((B,), bool), fresh)
        got.append(y)
    got = np.asarray(jnp.stack(got, 1))
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1, :10], want[1, :10],
                               rtol=1e-5, atol=1e-6)
    again = np.asarray(ref.selective_scan(
        u[1:2, 10:], delta[1:2, 10:], A, Bm[1:2, 10:], Cm[1:2, 10:], D))
    np.testing.assert_allclose(got[1, 10:], again[0], rtol=1e-5, atol=1e-6)
    # positions that are not real: rows of 21, 13 and 0 real positions
    n_real = jnp.asarray([21, 13, 0])
    valid = jnp.arange(T)[None] < n_real[:, None]
    seeded = jnp.asarray(np.random.default_rng(5).standard_normal(
        (B, N, C)), jnp.float32)
    _y, after = ssm_chunked(u, delta, A, Bm, Cm, D, seeded, valid)
    _y, short = ssm_chunked(u[:, :13], delta[:, :13], A, Bm[:, :13],
                            Cm[:, :13], D, seeded, valid[:, :13])
    np.testing.assert_allclose(after[1], short[1], rtol=1e-6)
    np.testing.assert_array_equal(after[2], seeded[2])
    _y, stepped = ssm_step(u[:, 0], delta[:, 0], A, Bm[:, 0], Cm[:, 0], D,
                           seeded, jnp.asarray([True, True, False]))
    np.testing.assert_array_equal(stepped[2], seeded[2])
    assert np.abs(np.asarray(stepped[0] - seeded[0])).max() > 0


# ------------------------------------------- the chunk's kernel (interpret)

@pytest.fixture
def scan_kernel(monkeypatch):
    """The module's rule steered as the chip answers it, and its kernel
    run in interpret mode: ``ssm_chunked`` then serves a chunk as it
    does on one TPU. Yields the shapes the kernel was handed."""
    from ray_tpu.ops import selective_scan as ss
    seen, kernel = [], jax.jit(functools.partial(ss.selective_scan,
                                                 interpret=True))

    def interpreted(u, *rest):
        seen.append(tuple(u.shape))
        return kernel(u, *rest)
    monkeypatch.setattr(ss, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(ss, "selective_scan", interpreted)
    return seen


def _unsteered(*args):
    """``ssm_chunked``'s ``lax.scan``, whatever the rule is steered to."""
    from unittest import mock

    from ray_tpu.ops import selective_scan as ss
    with mock.patch.object(ss, "_on_one_tpu", lambda: False):
        return ssm_chunked(*args)


@pytest.mark.parametrize("B,T,C,N,dtype", [
    (2, 8, 1024, 16, jnp.float32), (3, 24, 2048, 16, jnp.float32),
    (1, 16, 1024, 8, jnp.float32), (2, 16, 1024, 16, jnp.bfloat16)],
    ids=["one_group", "two_blocks", "eight_states", "bfloat16_operands"])
def test_the_scan_kernel_agrees_with_the_scan_and_the_reference(
        scan_kernel, B, T, C, N, dtype):
    """From zeros over real positions alone: the kernel = the
    ``lax.scan`` = the plain reference's loop, float32 (``u``, ``B``,
    ``C`` in the model's type are widened by both forms alike)."""
    from benchmarks.reference import phi4flash as ref
    u, delta, A, Bm, Cm, D = _scan_inputs(B, T, C, N, seed=T)
    u, Bm, Cm = (x.astype(dtype) for x in (u, Bm, Cm))
    zero, ok = jnp.zeros((B, N, C), jnp.float32), jnp.ones((B, T), bool)
    y, end = ssm_chunked(u, delta, A, Bm, Cm, D, zero, ok)
    assert scan_kernel == [(B, T, C)]
    want_y, want_end = _unsteered(u, delta, A, Bm, Cm, D, zero, ok)
    assert y.dtype == end.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(end, want_end, rtol=1e-5, atol=1e-6)
    plain = ref.selective_scan(*(x.astype(jnp.float32) for x in (
        u, delta, A, Bm, Cm, D)))
    np.testing.assert_allclose(y, plain, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cut", [8, 16, 24])
def test_the_scan_kernel_carries_the_state_between_chunks(scan_kernel, cut):
    """A chunk in one piece = in two pieces carrying the state, from a
    seeded non-zero state."""
    B, T, C, N = 2, 32, 1024, 16
    u, delta, A, Bm, Cm, D = _scan_inputs(B, T, C, N, seed=3)
    seeded = jnp.asarray(np.random.default_rng(5).standard_normal(
        (B, N, C)), jnp.float32)
    ok = jnp.ones((B, T), bool)
    whole, end = ssm_chunked(u, delta, A, Bm, Cm, D, seeded, ok)
    a, mid = ssm_chunked(u[:, :cut], delta[:, :cut], A, Bm[:, :cut],
                         Cm[:, :cut], D, seeded, ok[:, :cut])
    b, last = ssm_chunked(u[:, cut:], delta[:, cut:], A, Bm[:, cut:],
                          Cm[:, cut:], D, mid, ok[:, cut:])
    assert scan_kernel == [(B, T, C), (B, cut, C), (B, T - cut, C)]
    np.testing.assert_allclose(jnp.concatenate([a, b], 1), whole,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(last, end, rtol=1e-6, atol=1e-6)
    want, want_end = _unsteered(u, delta, A, Bm, Cm, D, seeded, ok)
    np.testing.assert_allclose(whole, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(end, want_end, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_real", [0, 13, 24])
def test_the_scan_kernel_leaves_the_state_where_the_scan_does(scan_kernel,
                                                              n_real):
    """``valid`` cut at 0, 13 and T positions of a seeded state: the
    state is the ``lax.scan``'s (untouched at 0, bit for bit), and so is
    every position's read-out, the unreal ones' too."""
    B, T, C, N = 2, 24, 1024, 16
    u, delta, A, Bm, Cm, D = _scan_inputs(B, T, C, N, seed=11)
    seeded = jnp.asarray(np.random.default_rng(6).standard_normal(
        (B, N, C)), jnp.float32)
    valid = jnp.arange(T)[None] < jnp.asarray([n_real, T])[:, None]
    y, end = ssm_chunked(u, delta, A, Bm, Cm, D, seeded, valid)
    assert scan_kernel == [(B, T, C)]
    want_y, want_end = _unsteered(u, delta, A, Bm, Cm, D, seeded, valid)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(end, want_end, rtol=1e-5, atol=1e-6)
    if n_real == 0:
        np.testing.assert_array_equal(end[0], seeded[0])
    else:
        assert np.abs(np.asarray(end[0] - seeded[0])).max() > 0


@pytest.mark.parametrize("T", [64, 128, 256])
def test_every_prefill_width_of_the_cell_goes_through_the_kernel(
        scan_kernel, T):
    """The widths the engine builds for ``.reason-sat`` (powers of two
    from a page of 64 to the chunk of 256), one block of the cell's
    5,120 channels, a row cut short beside a whole one."""
    from ray_tpu.ops import selective_scan as ss
    assert ss.serves(T, jax.ShapeDtypeStruct((4, 16, 5120), jnp.float32))
    B, C, N = 2, 1024, 16
    u, delta, A, Bm, Cm, D = _scan_inputs(B, T, C, N, seed=T)
    u, Bm, Cm = (x.astype(jnp.bfloat16) for x in (u, Bm, Cm))
    zero = jnp.zeros((B, N, C), jnp.float32)
    valid = jnp.arange(T)[None] < jnp.asarray([T, T - 29])[:, None]
    y, end = ssm_chunked(u, delta, A, Bm, Cm, D, zero, valid)
    assert scan_kernel == [(B, T, C)]
    want_y, want_end = _unsteered(u, delta, A, Bm, Cm, D, zero, valid)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(end, want_end, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,state,why", [
    (21, (3, 8, 1024), "positions that are no whole sublane tiles"),
    (16, (3, 8, 96), "channels that are no whole blocks"),
    (1024, (1, 8, 1024), "a whole sequence, not a chunk"),
    (16, (3, 2, 8, 1024), "a delta-rule state"),
], ids=["T_21", "C_96", "T_1024", "delta_rule"])
def test_a_shape_the_rule_refuses_keeps_the_scan(scan_kernel, T, state,
                                                 why):
    """``serves`` reads shapes, types, backend and mesh alone; what it
    refuses is the ``lax.scan``'s, with the same answer."""
    from ray_tpu.ops import selective_scan as ss
    assert not ss.serves(T, jax.ShapeDtypeStruct(state, jnp.float32)), why
    assert ss.serves(16, jax.ShapeDtypeStruct((3, 8, 1024), jnp.float32))
    assert not ss.serves(
        16, jax.ShapeDtypeStruct((3, 8, 1024), jnp.bfloat16))
    assert not ss.serves(1, jax.ShapeDtypeStruct((3, 8, 1024), jnp.float32))
    if len(state) == 3:
        B, N, C = state
        u, delta, A, Bm, Cm, D = _scan_inputs(B, T, C, N, seed=2)
        args = (u, delta, A, Bm, Cm, D, jnp.zeros(state, jnp.float32),
                jnp.ones((B, T), bool))
        got, want = ssm_chunked(*args), _unsteered(*args)
        assert not scan_kernel
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)


def test_off_one_tpu_the_rule_refuses_every_shape():
    """On the CPU (and under a multi-device mesh) no shape is served."""
    from ray_tpu.ops import selective_scan as ss
    assert not ss.serves(256, jax.ShapeDtypeStruct((4, 16, 5120),
                                                   jnp.float32))


# ------------------------------------ the paged path against the reference

def _call(model, params, table, slots):
    @jax.jit
    def call(pool, chunk, pos, n_real):
        valid = jnp.arange(chunk.shape[1])[None] < n_real[:, None]
        views = [kv_layer_view(layer, table, slots, lambda: valid)
                 for layer in pool]
        logits, new = model.apply(params, chunk, kv_caches=views,
                                  cache_len=pos)
        return logits, [kv_layer_store(v) for v in new]
    return call


def _pool(cfg, n_pages=40, n_slots=4):
    return init_kv_pool(cfg, n_pages, PAGE, n_slots=n_slots,
                        ring_len=sliding_ring_len(cfg, PAGE, CHUNK))


def test_the_pool_holds_each_layer_by_its_kind(tiny):
    """A borrowed layer and a stateless one allocate NOTHING: the pool's
    entry is the empty tuple, a page's bytes and an exported page count
    the one layer that has pages, by hand."""
    cfg, _model, _params = tiny
    ring = sliding_ring_len(cfg, PAGE, CHUNK)
    assert ring == 8 + 16 + PAGE
    pool = _pool(cfg)
    assert len(pool) == 8
    for kind, entry in zip(cfg.layer_kinds, pool):
        if kind == KIND_RECURRENT:
            assert isinstance(entry, RecurrentState)
            assert entry.state.shape == (4, 8, 128)        # [slots, N, C]
            assert entry.state.dtype == jnp.float32
            assert entry.conv.shape == (4, 3, 128)
        elif kind == KIND_SLIDING:
            assert isinstance(entry, SlidingRing)
            assert entry.k.shape == (4, 2, ring, 16)       # 2 pairs of 16
        elif kind == KIND_KV:
            assert [t.shape for t in entry] == [(40, PAGE, 16, 16)] * 2
        else:
            assert entry == ()
    assert [kv_cache.page_layout(cfg, k, PAGE) for k in (
        KIND_BORROWED, KIND_STATELESS)] == [(), ()]
    # ONE layer's K and V, 16 head rows of 16 a token, float32 here
    assert kv_pool_page_bytes(cfg, PAGE) == 2 * PAGE * 16 * 16 * 4
    assert state_bytes_per_slot(cfg, ring) == (
        3 * (4 * 8 * 128 + 4 * 3 * 128) + 2 * 2 * ring * 2 * 16 * 4)
    # an exported page is the owner's two tensors, and lands again
    only_pages = types.SimpleNamespace(
        layer_kinds=(KIND_KV, KIND_STATELESS, KIND_BORROWED),
        n_kv_heads=2, head_dim=16, dtype=jnp.float32, n_layers=3)
    small = init_kv_pool(only_pages, 5, PAGE)
    assert [len(e) for e in small] == [2, 0, 0]
    blobs = export_page_bytes(small, 3)
    assert [[len(b) for b in layer] for layer in blobs] == [
        [PAGE * 2 * 16 * 4] * 2]
    assert sum(len(b) for layer in blobs for b in layer) == \
        kv_pool_page_bytes(only_pages, PAGE)
    cols = page_cols_from_bytes(only_pages, PAGE, "fp", blobs)
    assert len(cols) == 1 and cols[0][0].shape == (PAGE, 2, 16)
    # the views: a layer that keeps nothing is handed nothing
    assert kv_layer_view((), None) == () and kv_layer_store(()) == ()


def test_paged_logits_match_the_reference(tiny):
    """Two rows of a prefill call of three (the third carries no
    request), 45 and 20 tokens in chunks of 16 (three and two chunks:
    the state, the tail, the rings and the pages cross calls, ``m``
    within one; the last chunks padded inside), then five decode steps
    through state, rings and the shared pages, against the plain
    reference's full forward pass, ON LOGITS at every position."""
    cfg, model, params = tiny
    lens, G = (45, 20), 5
    ids = [_ids((n + G,), seed=30 + n) for n in lens]
    want = [_reference(params, [row], cfg)[0] for row in ids]
    pool = _pool(cfg)
    table = np.zeros((3, 8), np.int32)
    table[0, :7] = 1 + np.arange(7)
    table[1, :4] = 10 + np.arange(4)
    # rows 0 and 1 carry slots 2 and 0; row 2 names no slot (4)
    prefill = _call(model, params, jnp.asarray(table),
                    jnp.asarray([2, 0, 4], jnp.int32))
    got = [[], []]
    for start in (0, CHUNK, 2 * CHUNK):
        chunk = np.zeros((3, CHUNK), np.int32)
        n_real = [max(0, min(CHUNK, n - start)) for n in lens] + [0]
        for r, n in enumerate(n_real[:2]):
            chunk[r, :n] = ids[r][start:start + n]
        # a row whose prompt is done rides the call as a dummy
        live_table = table.copy()
        for r, n in enumerate(n_real[:2]):
            if not n:
                live_table[r] = 0
        call = prefill if all(n_real[:2]) else _call(
            model, params, jnp.asarray(live_table),
            jnp.asarray([2 if n_real[0] else 4, 0 if n_real[1] else 4, 4],
                        jnp.int32))
        logits, pool = call(
            pool, jnp.asarray(chunk),
            jnp.asarray([start, start if n_real[1] else 977, 977],
                        jnp.int32),
            jnp.asarray(n_real, jnp.int32))
        for r, n in enumerate(n_real[:2]):
            got[r].append(np.asarray(logits[r, :n]))
    # decode: row i IS slot i (slots None), every slot rides
    dtable = np.zeros((4, 8), np.int32)
    dtable[2], dtable[0] = table[0], table[1]
    decode = _call(model, params, jnp.asarray(dtable), None)
    row_of = {0: 2, 1: 0}
    before = [np.asarray(e.state[jnp.asarray([1, 3])])
              for e in pool if isinstance(e, RecurrentState)]
    for step in range(G):
        tok = np.zeros((4, 1), np.int32)
        pos = np.zeros((4,), np.int32)
        for r, n in enumerate(lens):
            tok[row_of[r], 0] = ids[r][n + step]
            pos[row_of[r]] = n + step
        live = np.asarray([1, 0, 1, 0], np.int32)
        logits, pool = decode(pool, jnp.asarray(tok), jnp.asarray(pos),
                              jnp.asarray(live))
        for r in range(2):
            got[r].append(np.asarray(logits[row_of[r], :1]))
    for r in range(2):
        np.testing.assert_allclose(np.concatenate(got[r]), want[r],
                                   rtol=RTOL, atol=ATOL)
    # the slots that carried nothing hold nothing: state, tail, rings
    for entry in pool:
        if isinstance(entry, RecurrentState):
            assert not np.asarray(entry.state[jnp.asarray([1, 3])]).any()
            assert not np.asarray(entry.conv[jnp.asarray([1, 3])]).any()
            assert np.abs(np.asarray(entry.state[2])).max() > 0
        elif isinstance(entry, SlidingRing):
            assert not np.asarray(entry.k[jnp.asarray([1, 3])]).any()
            assert np.abs(np.asarray(entry.k[2])).max() > 0
    assert all(not b.any() for b in before)


def test_a_prefill_call_narrows_before_the_layers_that_keep_nothing(tiny):
    """The chunked-prefill program's call of the model (``logits_at`` =
    each row's ``last_idx``) over three rows at their SECOND chunk: one
    ends its prompt mid-chunk (``last_idx`` 6), one stands mid-prompt
    (``last_idx`` = T - 1), one is a dummy (a null page-table row, no
    slot, a stale start). The memory unit and the cross layer (6 and 7
    of the toy's 8: ``sampled_only_from``) see ONE position a row, every
    layer before them all sixteen; the logits are the every-position
    call's at ``last_idx``, and every entry of the pool (the pages, the
    rings, the states, the tails) is bit for bit what the every-position
    call leaves."""
    from ray_tpu.serve import step_programs
    cfg, model, params = tiny
    assert kv_cache.sampled_only_from(cfg) == 6
    B, T = 3, CHUNK
    table = np.zeros((B, 8), np.int32)
    table[0, :4] = 1 + np.arange(4)
    table[1, :4] = 10 + np.arange(4)
    table = jnp.asarray(table)
    slots = jnp.asarray([2, 0, 4], jnp.int32)
    apply = step_programs._moe_apply(model, None)

    def call(pool, ids, start, last_idx, sampled):
        def live():
            return (table[:, :1] != 0) & (
                jnp.arange(T)[None] <= last_idx[:, None])
        logits, new, _moe = apply(
            params, ids, step_programs._views(pool, table, live, slots),
            start, live, last_idx if sampled else None)
        return logits, [kv_layer_store(c) for c in new]
    every = jax.jit(lambda *a: call(*a, False))
    narrowed = jax.jit(lambda *a: call(*a, True))
    first = jnp.asarray(_ids((B, T), seed=71), jnp.int32).at[2].set(0)
    zeros = jnp.zeros((B,), jnp.int32)
    _logits, pool = every(_pool(cfg), first, zeros,
                          jnp.asarray([T - 1, T - 1, 0], jnp.int32))
    ids = jnp.asarray(_ids((B, T), seed=72), jnp.int32)
    ids = ids.at[0, 7:].set(0).at[2].set(0)
    start = jnp.asarray([T, T, 977], jnp.int32)
    last_idx = jnp.asarray([6, T - 1, 0], jnp.int32)
    full, want_pool = every(pool, ids, start, last_idx)
    rows, got_pool = narrowed(pool, ids, start, last_idx)
    assert full.shape == (B, T, cfg.vocab_size)
    assert rows.shape == (B, cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(rows)[:2],
        np.asarray(full)[np.arange(2), np.asarray(last_idx)[:2]],
        rtol=1e-5, atol=1e-6)
    assert np.isfinite(np.asarray(rows)).all()       # the dummy row too
    same = jax.tree_util.tree_map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        got_pool, want_pool)
    assert jax.tree_util.tree_structure(got_pool) == \
        jax.tree_util.tree_structure(want_pool)
    assert all(jax.tree_util.tree_leaves(same)), same
    # and the second chunk moved the rows' entries of every kind
    moved = jax.tree_util.tree_map(
        lambda a, b: bool((np.asarray(a) != np.asarray(b)).any()),
        got_pool, pool)
    assert all(jax.tree_util.tree_leaves(moved)), moved
    # what ran where: the feed-forwards' two up-projections (no other
    # extent of the toy is its hidden 96) at sixteen positions a row in
    # all eight layers, or in the first six and at ONE in the last two
    def ups(text, t):
        return len(re.findall(r"stablehlo\.dot_general[^\n]*-> tensor<"
                              f"{B}x{t}x{cfg.hidden_dim}xf32>", text))
    every, narrowed = (f.lower(pool, ids, start, last_idx).as_text()
                       for f in (every, narrowed))
    assert (ups(every, T), ups(every, 1)) == (16, 0)
    assert (ups(narrowed, T), ups(narrowed, 1)) == (12, 4)


def test_the_round_says_how_many_layers_ran_on_the_sampled_positions(tiny):
    """``prefill_sampled_only_layers`` of the ``round`` event: the toy's
    two trailing layers that keep nothing (14 of the published 32) in
    every round with a prefill call, 0 without one; the stats sum it."""
    eng = _engine(tiny)
    handles = [eng.submit(_ids((n,), seed=40 + n).tolist(),
                          max_new_tokens=6) for n in (37, 9)]
    _drive(eng)
    assert all(len(h.result()) == 6 for h in handles)
    assert eng.accounts.sampled_only_layers == 2
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    said = {(bool(r["prefill_rows"]), r["prefill_head_rows"],
             r["prefill_sampled_only_layers"]) for r in rounds}
    assert said == {(True, 4, 2), (False, 0, 0)}
    assert eng.stats["prefill_sampled_only_layers"] == \
        2 * eng.stats["prefills"] > 0


def test_the_round_says_how_many_positions_the_scan_kernel_walked(
        tiny, monkeypatch):
    """``prefill_scan_kernel_positions`` of the ``round`` event: 0 where
    the prefill program holds no kernel for the scan (the CPU), and with
    the module's rule steered as the chip answers it (the toy's 128
    channels one block) the call's ``B x T``, dummy rows included, in
    every round with a prefill call; the stats sum it. The accounts ask
    ``serves`` of a chunk of the call's width and one layer's states as
    the pool keeps them."""
    from ray_tpu.ops import selective_scan as ss
    eng = _engine(tiny)

    def drive():
        seen = len(eng.events.snapshot())
        handles = [eng.submit(_ids((n,), seed=40 + n).tolist(),
                              max_new_tokens=3) for n in (37, 9)]
        _drive(eng)
        assert all(len(h.result()) == 3 for h in handles)
        return [e[5] for e in eng.events.snapshot()[seen:]
                if e[2] == "round"]
    off = drive()
    assert {r["prefill_scan_kernel_positions"] for r in off} == {0}
    assert eng.stats["prefill_scan_kernel_positions"] == 0
    asked, serves = [], ss.serves
    monkeypatch.setattr(ss, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(ss, "_BLOCK", 128)
    monkeypatch.setattr(
        ss, "serves", lambda T, state: asked.append(
            (T, state.shape, state.dtype)) or serves(T, state))
    on = drive()           # the same widths: no program is built again
    assert {(r["prefill_width"], r["prefill_scan_kernel_positions"])
            for r in on} == {(16, 4 * 16), (8, 4 * 8), (0, 0)}
    assert eng.stats["prefill_scan_kernel_positions"] == sum(
        4 * r["prefill_width"] for r in on) > 0
    cfg = tiny[0]
    assert set(asked) == {
        (T, (4, cfg.ssm_state, cfg.d_inner), jnp.dtype(jnp.float32))
        for T in (8, 16)}


def test_the_differential_kernel_path_is_the_four_product_form(tiny):
    """What the kernels are handed (a pair side by side, two query rows
    of 128 a pair with a zero half each, ONE softmax a row, the ring's
    rows scaled by sqrt(2), a decode step's groups padded to eight rows)
    against the four products written out, for ONE layer's attention
    module: the same numbers through the cache-less form, the ring and
    the pages."""
    from ray_tpu.models.phi4flash import DiffAttention
    cfg, _model, _params = tiny
    B, T, D = 2, 12, cfg.dim
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((B, T, D)), jnp.float32)
    for mixer in (SLIDING, FULL):
        mod = DiffAttention(cfg, mixer, 3)
        p = mod.init(jax.random.PRNGKey(1), x)
        a = p["params"]
        half, H, KH = cfg.head_dim // 2, cfg.attn_heads, cfg.attn_kv_heads
        q = (x @ a["wq"]["kernel"] + a["wq"]["bias"]).reshape(B, T, H, half)
        k = (x @ a["wk"]["kernel"] + a["wk"]["bias"]).reshape(B, T, KH, half)
        v = (x @ a["wv"]["kernel"] + a["wv"]["bias"]).reshape(B, T, KH, half)
        i, j = np.arange(T)[:, None], np.arange(T)[None, :]
        seen = (j <= i) & ((j > i - cfg.sliding_window)
                           if mixer == SLIDING else True)
        lam = (jnp.exp(jnp.sum(a["lambda_q1"] * a["lambda_k1"]))
               - jnp.exp(jnp.sum(a["lambda_q2"] * a["lambda_k2"]))
               + lambda_init(3))
        out = []
        for pair in range(H // 2):
            g = pair // (H // KH)
            def soft(qh, kh):
                s = jnp.einsum("btd,bsd->bts", qh, kh) / np.sqrt(half)
                return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            vg = jnp.concatenate([v[:, :, 2 * g], v[:, :, 2 * g + 1]], -1)
            o = (soft(q[:, :, 2 * pair], k[:, :, 2 * g]) @ vg
                 - lam * soft(q[:, :, 2 * pair + 1], k[:, :, 2 * g + 1]) @ vg)
            o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                             + cfg.norm_eps)
            out.append(o * a["subln"] * (1.0 - lambda_init(3)))
        want = jnp.concatenate(out, -1) @ a["wo"]["kernel"] + a["wo"]["bias"]
        got, _none, _kv = mod.apply(p, x)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
        # through the cache: one call of the whole sequence, then a
        # decode step's shape (T = 1) for the last position again
        pool = _pool(cfg, n_slots=B)
        entry = pool[1 if mixer == SLIDING else 5]
        table = jnp.asarray(1 + np.arange(2 * B).reshape(B, 2), jnp.int32)
        view = kv_layer_view(entry, table, None,
                             lambda: jnp.ones((B, T), bool))
        zero = jnp.zeros((B,), jnp.int32)
        got, new, _kv = mod.apply(p, x, view, zero)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
        view = kv_layer_view(kv_layer_store(new), table, None,
                             lambda: jnp.ones((B, 1), bool))
        got, _new, _kv = mod.apply(p, x[:, -1:], view, zero + T - 1)
        np.testing.assert_allclose(got, want[:, -1:], rtol=RTOL, atol=ATOL)


def test_the_differential_rows_through_both_kernels_in_interpret_mode(
        monkeypatch):
    """The rows the model hands the chip's kernels, through the kernels
    themselves (interpret mode, float32): a sliding layer's chunk (groups
    of four rows) and decode step (groups padded to EIGHT rows, 16 a
    step: whole sublane tiles) through ``ring_window``, the full layer's
    decode step through ``paged_decode`` over pages of 16 head rows for
    2 pairs (14 rows of zeros with zero query groups of their own), each
    against the ``jax.numpy`` form the CPU runs."""
    from ray_tpu.models import phi4flash as mod
    from ray_tpu.models.phi4flash import DiffAttention
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.ops import ring_window_attention as rw
    cfg = phi4flash_tiny(dtype=jnp.float32, dim=256, attn_heads=4,
                         attn_kv_heads=2, sliding_window=40)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (4, 1, 128)
    assert cfg.query_heads_by_kind[KIND_SLIDING] == 8
    B, T, page = 2, 32, 64
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((B, T + 1, cfg.dim)), jnp.float32)
    ring = sliding_ring_len(cfg, page, T)                  # 40 + 32 -> 128
    pool = init_kv_pool(cfg, 5, page, n_slots=B, ring_len=ring)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    zero = jnp.zeros((B,), jnp.int32)
    seen = []

    def kernel_ring(q, k, v, rk, rv, slots, pos, valid, window):
        seen.append(("ring", q.shape))
        return rw.ring_window_kernel(q, k, v, rk, rv, slots, pos, valid,
                                     window=window, interpret=True)

    def kernel_pages(q, pk, pv, sk, sv, page_table, pos,
                     softmax_scale=None, value_dim=None):
        if q.shape[1] != 1:
            return window(q, pk, pv, sk, sv, page_table, pos,
                          softmax_scale, value_dim)
        seen.append(("pages", q.shape))
        return pd.paged_decode_attention(
            q, pk, pv, page_table, pos, softmax_scale=softmax_scale,
            interpret=True)
    window = pa._paged_window_attention
    for mixer, entry in ((SLIDING, pool[1]), (FULL, pool[5])):
        module = DiffAttention(cfg, mixer, 1 if mixer == SLIDING else 5)
        p = module.init(jax.random.PRNGKey(2), x[:, :T])

        def run(patched):
            with monkeypatch.context() as m:
                if patched:
                    m.setattr(mod, "ring_window_attention", kernel_ring)
                    m.setattr(mod, "_paged_window_attention", kernel_pages)
                view = kv_layer_view(entry, table, None,
                                     lambda: jnp.ones((B, T), bool))
                y, new, _kv = module.apply(p, x[:, :T], view, zero)
                view = kv_layer_view(kv_layer_store(new), table, None,
                                     lambda: jnp.ones((B, 1), bool))
                y1, _new, _kv = module.apply(p, x[:, T:], view, zero + T)
            return np.asarray(y), np.asarray(y1)
        want, got = run(False), run(True)
        np.testing.assert_allclose(got[0], want[0], rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(got[1], want[1], rtol=RTOL, atol=ATOL)
    # a chunk's groups of four rows, a step's of eight; the pages' 16
    # head rows with a group of four a row
    assert seen == [("ring", (B, T, 4, 128)), ("ring", (B, 1, 8, 128)),
                    ("pages", (B, 1, 64, 128))]


# ------------------------------------------------------------ the engine

def test_the_engine_matches_the_reference(tiny):
    """The real engine: three prompts of 52, 7 and 21 tokens in a
    prefill call of four rows of chunks of 16 (the longest crosses four
    rounds with its state, its tail and its rings handed over, and is
    several windows long), then decoding in dispatches of four steps
    through state, rings and the shared pages. The tokens are the
    reference's teacher-forced, the captured log-probability of every
    generated token is the reference's, and the counters are the hand
    counts."""
    cfg, _model, params = tiny
    eng = _engine(tiny, capture_logprobs=True)
    prompts = [_ids((n,), seed=10 + n).tolist() for n in (52, 7, 21)]
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drive(eng)
    rounds = [e[5] for e in eng.events.snapshot() if e[2] == "round"]
    assert max(r["prefill_rows"] for r in rounds) == 3
    assert eng.stats["decode_kernel_pages"] == 0      # the CPU: the loop
    assert eng.stats["sliding_kernel_keys"] == 0
    for p, h in zip(prompts, handles):
        out = h.result()
        assert len(out) == 12
        steps = _held_to_the_reference(params, cfg, p, out)
        want = np.asarray(jax.nn.log_softmax(steps))[
            np.arange(len(out)), out]
        np.testing.assert_allclose(h.logprobs, want, rtol=RTOL, atol=ATOL)
    # the counters: a decode dispatch's riders' contexts cut at the
    # window of 8, and their whole contexts times the TWO layers that
    # read pages (the full layer and the one cross layer of the tiny
    # model); state_slots as the other families'
    decodes = [r for r in rounds if r["decode_steps"]]
    assert decodes and all(
        r["decode_shared_kv_reads"] == 2 * r["decode_context_tokens"]
        and r["decode_sliding_keys"] == 8 * r["decode_riders"]
        for r in decodes)
    assert eng.accounts.page_readers == 2
    assert eng.stats["decode_shared_kv_reads"] == sum(
        r["decode_shared_kv_reads"] for r in decodes) > 0
    assert sum(r.get("state_slots", 0) for r in rounds) == \
        eng.stats["state_slots"] > 0
    report = eng.load_report()
    ring = sliding_ring_len(cfg, PAGE, CHUNK)
    assert report["state_bytes_in_use"] == 0
    assert report["state_bytes_total"] == 4 * state_bytes_per_slot(cfg, ring)
    assert report["sliding_bytes_per_slot"] == 2 * 2 * ring * 2 * 16 * 4
    # kv_bytes_* count ONE layer's pages
    assert report["kv_page_bytes"] == kv_pool_page_bytes(cfg, PAGE) \
        == 2 * PAGE * 16 * 16 * 4
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


def test_a_reused_slot_starts_from_zeros(tiny):
    """One slot, two requests in turn: the second finds the first's
    state, tail, rings and (behind re-allocated page ids) pages in its
    slot and must not see them: a borrowed layer follows its owner's
    pages through free and re-allocation."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=1, n_pages=9)        # 8 usable pages
    first, second = _ids((40,), seed=20).tolist(), _ids((19,), 21).tolist()
    h1 = eng.submit(first, max_new_tokens=8)
    _drive(eng)
    state = [np.asarray(e.state) for e in eng.pages
             if isinstance(e, RecurrentState)]
    assert len(state) == 3 and all(np.abs(s).max() > 0 for s in state)
    assert eng.alloc.occupancy() == 0
    h2 = eng.submit(second, max_new_tokens=10)
    _drive(eng)
    _held_to_the_reference(params, cfg, first, h1.result())
    _held_to_the_reference(params, cfg, second, h2.result())
    alone = _engine(tiny, max_slots=1)
    h = alone.submit(second, max_new_tokens=10)
    _drive(alone)
    assert h.result() == h2.result()


def test_free_slots_ride_without_moving_what_they_hold(tiny):
    """One request in an engine of four slots: the other three ride
    every decode call, and their state, tail and rings stay what they
    were, bit for bit; the pages no request holds stay too."""
    cfg, _model, params = tiny
    eng = _engine(tiny)

    def marked(e):
        if isinstance(e, RecurrentState):
            return RecurrentState(e.state.at[1:].set(7.0),
                                  e.conv.at[1:].set(3.0))
        if isinstance(e, SlidingRing):
            return SlidingRing(e.k.at[1:].set(5.0), e.v.at[1:].set(2.0))
        return tuple(t.at[40:].set(9.0) for t in e)
    eng.pages = [marked(e) for e in eng.pages]
    prompt = _ids((37,), seed=30).tolist()
    h = eng.submit(prompt, max_new_tokens=9)
    _drive(eng)
    _held_to_the_reference(params, cfg, prompt, h.result())
    for entry in eng.pages:
        if isinstance(entry, RecurrentState):
            assert (np.asarray(entry.state[1:]) == 7.0).all()
            assert (np.asarray(entry.conv[1:]) == 3.0).all()
            assert np.abs(np.asarray(entry.state[0])).max() > 0
        elif isinstance(entry, SlidingRing):
            assert (np.asarray(entry.k[1:]) == 5.0).all()
            assert (np.asarray(entry.v[1:]) == 2.0).all()
        elif entry:
            assert all((np.asarray(t[40:]) == 9.0).all() for t in entry)


def test_more_requests_than_slots(tiny):
    """Seven requests on two slots and a pool that holds two requests'
    pages: every slot and every page id is reused, and each request
    gives the tokens the reference gives."""
    cfg, _model, params = tiny
    eng = _engine(tiny, max_slots=2, n_pages=17)
    prompts = [_ids((9 + 7 * i,), seed=60 + i).tolist() for i in range(7)]
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _drive(eng)
    for p, h in zip(prompts, handles):
        _held_to_the_reference(params, cfg, p, h.result())
    assert eng.alloc.occupancy() == 0 and eng.alloc.leak_report() == []


# ------------------------------------------------------------- refusals

def test_the_table_has_the_two_kinds_rows():
    """A borrowed layer's and a stateless layer's own rows, by their
    words; a model of pages with readers (no state, no ring) is refused
    what its readers cannot do and nothing else."""
    cfg = types.SimpleNamespace(
        layer_kinds=(KIND_KV, KIND_STATELESS, KIND_BORROWED), n_layers=3)
    refuse_unsupported(cfg, prefix_cache=True, spec_len=4)
    for option in ("kv_dtype", "kv_migration", "sharding"):
        with pytest.raises(ValueError) as refused:
            refuse_unsupported(cfg, **{option: "asked"})
        keeps, why = kv_cache.KIND_REFUSALS[KIND_BORROWED]
        assert str(refused.value) == (
            f"{option}='asked' is not supported for SimpleNamespace: it "
            f"has layers that keep {keeps}; {why[option]}")
    only = types.SimpleNamespace(layer_kinds=(KIND_KV, KIND_STATELESS),
                                 n_layers=2)
    refuse_unsupported(only, kv_dtype="int8", kv_migration=True)
    with pytest.raises(ValueError, match="no request state at all"):
        refuse_unsupported(only, sharding=True)


@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache=True), "prefix_cache.*recurrent state"),
    (dict(spec_len=2), "spec_len.*recurrent state"),
    (dict(kv_dtype="int8"), "kv_dtype.*ring"),
    (dict(sharding=object()), "sharding.*recurrent state")],
    ids=["prefix_cache", "spec_len", "kv_dtype", "sharding"])
def test_the_engine_refuses_what_its_kinds_cannot_do(tiny, option, match):
    """The model answers through the rows of the kinds it has, the first
    in the table's order that refuses the option."""
    with pytest.raises(ValueError, match=match) as refused:
        _engine(tiny, **option)
    assert "Phi4FlashConfig" in str(refused.value)


def test_kv_export_is_refused(tiny):
    eng = _engine(tiny)
    with pytest.raises(ValueError, match="kv_migration.*recurrent state"):
        eng.kv_export_pages([1])


@pytest.mark.parametrize("option,match", [
    (dict(disaggregate=True, prefix_cache=True), "disaggregate"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_len=3), "spec_len"),
    (dict(tensor_parallel=2), "sharding")],
    ids=["disaggregate", "prefix_cache", "spec_len", "tensor_parallel"])
def test_the_deployment_refuses_at_construction(tiny, option, match):
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    with pytest.raises(ValueError, match=match + ".*recurrent state"):
        LlamaDeployment(config=cfg, params=params, **option)


def test_the_static_cache_path_refuses_it(tiny):
    from ray_tpu.models.llama import generate
    _cfg, model, params = tiny
    with pytest.raises(TypeError, match="recurrent state"):
        generate(model, params, jnp.asarray(_ids((1, 8))), 4)


# ------------------------------------------------------------ serve.run

def test_serve_run_serves_it_through_the_deployment(tiny, rt):
    """ray_tpu.init() -> serve.run() of LlamaDeployment, as a user
    deploys it: no side script, no option that selects a path."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment
    cfg, _model, params = tiny
    holder = {}

    @serve.deployment
    class PhiLLM(LlamaDeployment):
        def __init__(self):
            super().__init__(config=cfg, params=params, max_slots=4,
                             page_size=PAGE, n_pages=64,
                             prefill_chunk=CHUNK)
            holder["dep"] = self

    try:
        handle = serve.run(PhiLLM.bind(), timeout_s=300)
        prompt = _ids((41,), seed=80).tolist()
        out = rt.get(handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}), timeout=300)
        assert out[:41] == prompt and len(out) == 51
        _held_to_the_reference(params, cfg, prompt, out[41:])
        report = holder["dep"].engine().load_report()
        assert report["state_bytes_total"] == 4 * state_bytes_per_slot(
            cfg, sliding_ring_len(cfg, PAGE, CHUNK))
    finally:
        serve.shutdown()
